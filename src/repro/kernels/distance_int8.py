"""Pallas TPU kernel: int8 partial-distance accumulate with int32 MXU
accumulation — the quantized stage-1 of the two-stage search path.

Corpus and query share one affine quantization grid per dimension block
(scale s_b, zero-point z_b), so the zero-points cancel in the difference:

    d̂²_b(p, q) = s_b² · Σ_j (P_j − Q_j)²
               = s_b²·ΣQ² − 2·s_b²·(Q·P) + s_b²·ΣP²

The norm inputs (``xn2``/``qn2``) carry the already-dequantized s²·Σcode²
terms in f32; only the Q·P term runs on the MXU, as a pure int8×int8
matmul with ``preferred_element_type=jnp.int32`` (no f32 casts of the
operands — the 4× narrower codes are what the MXU reads from VMEM).

Everything else is ``distance.py``'s: the same ``pallas_call`` over the
grid (m_tiles, n_tiles, k_chunks) with k minor-most, the same +inf/−inf
padding conventions, the same tile-granular ``pl.when`` early-stop on a
scalar-prefetched skip map. The shared s² is read as an SMEM scalar. L2
only — the quantized difference form has no inner-product analogue here.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.distance import pad_to, tile_skip_map, tiled_distance_call


def _kernel(
    live_ref,   # SMEM [m_tiles * n_tiles] int32, 1 = tile has a live pair
    x_ref,      # [bn, bk] int8 corpus codes
    xn2_ref,    # [1, bn]  f32, s²·ΣP² for this dimension block
    q_ref,      # [bm, bk] int8 query codes
    qn2_ref,    # [bm, 1]  f32, s²·ΣQ² for this dimension block
    s2_ref,     # SMEM [1, 1] f32, s² shared by corpus and query
    acc_ref,    # [bm, bn]
    tau_ref,    # [bm, 1]
    out_ref,    # [bm, bn]
    *,
    nn: int,
    nk: int,
    prune: bool,
):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    acc_in = acc_ref[...]
    alive = jnp.isfinite(acc_in)

    @pl.when(k == 0)
    def _init():
        base = acc_in + qn2_ref[...] + xn2_ref[...]
        out_ref[...] = jnp.where(alive, base, jnp.inf)

    @pl.when(live_ref[i * nn + j] > 0)
    def _matmul():
        dot = jax.lax.dot_general(
            q_ref[...],
            x_ref[...],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        out_ref[...] = out_ref[...] - (2.0 * s2_ref[0, 0]) * dot.astype(
            jnp.float32
        )

    @pl.when(k == nk - 1)
    def _finalize():
        out = jnp.where(alive, out_ref[...], jnp.inf)
        if prune:
            out = jnp.where(out > tau_ref[...], jnp.inf, out)
        out_ref[...] = out


@functools.partial(
    jax.jit,
    static_argnames=("prune", "tile_m", "tile_n", "tile_k", "interpret"),
)
def int8_partial_distance_update(
    x: jnp.ndarray,       # [N, Db] int8 codes
    xn2: jnp.ndarray,     # [N] f32, s²·ΣP²
    q: jnp.ndarray,       # [M, Db] int8 codes
    qn2: jnp.ndarray,     # [M] f32, s²·ΣQ²
    scale2: jnp.ndarray,  # scalar f32, shared s² of this dimension block
    acc: jnp.ndarray,     # [M, N] f32, +inf = pruned
    tau: jnp.ndarray,     # [M]
    *,
    prune: bool = True,
    tile_m: int = 128,
    tile_n: int = 128,
    tile_k: int = 128,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (acc' [M, N] f32, tile_skipped [m_tiles, n_tiles] int32).

    Code pad value is 0 on both operands, so padded contraction dims add
    (0−0)² = 0 exactly; padded rows/queries follow the fp32 kernel's
    +inf/−inf conventions.

    >>> import jax.numpy as jnp
    >>> x8 = jnp.array([[3, -2]], jnp.int8)    # one candidate row's codes
    >>> q8 = jnp.array([[1, 2]], jnp.int8)     # one query's codes
    >>> s2 = jnp.float32(0.25)                 # shared grid, s = 0.5
    >>> xn2 = s2 * jnp.array([13.0]); qn2 = s2 * jnp.array([5.0])
    >>> acc = jnp.zeros((1, 1), jnp.float32); tau = jnp.array([jnp.inf])
    >>> out, _ = int8_partial_distance_update(
    ...     x8, xn2, q8, qn2, s2, acc, tau,
    ...     tile_m=8, tile_n=8, tile_k=8, interpret=True)
    >>> float(out[0, 0])   # 0.25 * ((3-1)² + (-2-2)²)
    5.0
    """
    m, n = q.shape[0], x.shape[0]
    xp = pad_to(x, (tile_n, tile_k), 0)
    qp = pad_to(q, (tile_m, tile_k), 0)
    xn2p = pad_to(xn2.reshape(1, -1), (1, tile_n), 0)
    qn2p = pad_to(qn2.reshape(-1, 1), (tile_m, 1), 0)
    taup = pad_to(tau.reshape(-1, 1), (tile_m, 1), jnp.float32(-jnp.inf))
    accp = pad_to(acc, (tile_m, tile_n), jnp.float32(jnp.inf))
    s2p = jnp.asarray(scale2, jnp.float32).reshape(1, 1)
    skip = tile_skip_map(accp, tile_m, tile_n)

    out = tiled_distance_call(
        functools.partial(_kernel, prune=prune),
        (xp, xn2p, qp, qn2p, s2p, accp, taup),
        [
            pl.BlockSpec((tile_n, tile_k), lambda i, j, k, live: (j, k)),   # x codes
            pl.BlockSpec((1, tile_n), lambda i, j, k, live: (0, j)),        # xn2
            pl.BlockSpec((tile_m, tile_k), lambda i, j, k, live: (i, k)),   # q codes
            pl.BlockSpec((tile_m, 1), lambda i, j, k, live: (i, 0)),        # qn2
            pl.BlockSpec(memory_space=pltpu.SMEM),                          # s²
            pl.BlockSpec((tile_m, tile_n), lambda i, j, k, live: (i, j)),   # acc
            pl.BlockSpec((tile_m, 1), lambda i, j, k, live: (i, 0)),        # tau
        ],
        skip=skip, tile_m=tile_m, tile_n=tile_n, tile_k=tile_k,
        dp=xp.shape[1], interpret=interpret,
    )
    return out[:m, :n], skip
