"""Pallas TPU kernel: partial-distance accumulate + monotone prune.

This is HARMONY's compute hot-spot (the paper: "over 90 % of ANNS time is
distance computation"), adapted to the TPU memory hierarchy:

* the [M, N] accumulator tile, the [bm, bk]/[bn, bk] operand tiles, and the
  per-row norm/threshold vectors live in VMEM via ``BlockSpec``;
* the partial distance is computed on the MXU as
  ``acc + ‖p‖²_b − 2·Q@Xᵀ + ‖q‖²_b`` with f32 accumulation at full f32
  contraction precision (the engine's results must equal the exact
  oracle's, so the MXU may not round operands to bf16);
* **tile-granular early-stop**: if every pair in the [bm, bn] accumulator
  tile is already pruned (+inf), the MXU matmul for this tile is skipped
  via ``pl.when`` — the TPU-native replacement for the paper's per-element
  CPU branch. The per-tile skip map is computed from the accumulator
  before the call and handed to the kernel as a scalar-prefetch operand
  in SMEM, so the branch reads one scalar; the same map is returned so
  benchmarks can report the realized compute saving.

Grid: (m_tiles, n_tiles, k_chunks); the k axis is minor-most so the output
tile is revisited across the contraction and stays resident in VMEM.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    live_ref,   # SMEM [m_tiles * n_tiles] int32, 1 = tile has a live pair
    x_ref,      # [bn, bk]
    xn2_ref,    # [1, bn]
    q_ref,      # [bm, bk]
    qn2_ref,    # [bm, 1]
    acc_ref,    # [bm, bn]
    tau_ref,    # [bm, 1]
    out_ref,    # [bm, bn]
    *,
    nn: int,
    nk: int,
    prune: bool,
    metric: str,
):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    acc_in = acc_ref[...]
    alive = jnp.isfinite(acc_in)

    @pl.when(k == 0)
    def _init():
        # base = acc + per-block norms (L2) — constant over k chunks
        if metric == "l2":
            base = acc_in + qn2_ref[...] + xn2_ref[...]
        else:
            base = acc_in
        out_ref[...] = jnp.where(alive, base, jnp.inf)

    @pl.when(live_ref[i * nn + j] > 0)
    def _matmul():
        xf = x_ref[...].astype(jnp.float32)
        qf = q_ref[...].astype(jnp.float32)
        dot = jax.lax.dot_general(
            qf,
            xf,
            (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        scale = 2.0 if metric == "l2" else 1.0
        out_ref[...] = out_ref[...] - scale * dot

    @pl.when(k == nk - 1)
    def _finalize():
        out = jnp.where(alive, out_ref[...], jnp.inf)
        if prune:
            out = jnp.where(out > tau_ref[...], jnp.inf, out)
        out_ref[...] = out


def pad_to(a: jnp.ndarray, mult: Tuple[int, ...], value) -> jnp.ndarray:
    """Pad each axis of ``a`` up to a multiple of ``mult`` with ``value``."""
    pads = []
    for dim, m in zip(a.shape, mult):
        rem = (-dim) % m
        pads.append((0, rem))
    if any(p[1] for p in pads):
        a = jnp.pad(a, pads, constant_values=value)
    return a


def tile_skip_map(acc: jnp.ndarray, tile_m: int, tile_n: int) -> jnp.ndarray:
    """Which [tile_m, tile_n] tiles of ``acc`` are fully pruned (+inf):
    [m_tiles, n_tiles] int32, 1 = the tile's matmul is skipped."""
    m, n = acc.shape
    a = pad_to(acc, (tile_m, tile_n), jnp.float32(jnp.inf))
    a = a.reshape(a.shape[0] // tile_m, tile_m, a.shape[1] // tile_n, tile_n)
    alive = jnp.isfinite(a).any(axis=(1, 3))
    return (~alive).astype(jnp.int32)


def tiled_distance_call(kernel, operands, specs, *, skip, tile_m, tile_n,
                        tile_k, dp, interpret):
    """``pallas_call`` over the (m_tiles, n_tiles, k_chunks) grid shared by
    the fp32 and int8 kernels. ``operands``/``specs`` are the kernel's
    tensor inputs and their ``BlockSpec``s; the live-tile map (1 − skip)
    goes first, as the scalar-prefetch operand."""
    nm, nn = skip.shape
    out_spec = pl.BlockSpec((tile_m, tile_n), lambda i, j, k, live: (i, j))
    return pl.pallas_call(
        functools.partial(kernel, nn=nn, nk=dp // tile_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nm, nn, dp // tile_k),
            in_specs=specs,
            out_specs=out_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((nm * tile_m, nn * tile_n), jnp.float32),
        interpret=interpret,
    )((1 - skip).reshape(-1), *operands)


@functools.partial(
    jax.jit,
    static_argnames=("prune", "metric", "tile_m", "tile_n", "tile_k", "interpret"),
)
def partial_distance_update(
    x: jnp.ndarray,       # [N, Db]
    xn2: jnp.ndarray,     # [N]
    q: jnp.ndarray,       # [M, Db]
    qn2: jnp.ndarray,     # [M]
    acc: jnp.ndarray,     # [M, N] f32, +inf = pruned
    tau: jnp.ndarray,     # [M]
    *,
    prune: bool = True,
    metric: str = "l2",
    tile_m: int = 128,
    tile_n: int = 128,
    tile_k: int = 128,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (acc' [M, N] f32, tile_skipped [m_tiles, n_tiles] int32)."""
    m, n = q.shape[0], x.shape[0]
    # Pad to tile multiples. Padded candidate rows get acc=+inf (excluded);
    # padded query rows get tau=-inf so everything in them prunes away.
    xp = pad_to(x, (tile_n, tile_k), 0)
    qp = pad_to(q, (tile_m, tile_k), 0)
    xn2p = pad_to(xn2.reshape(1, -1), (1, tile_n), 0)
    qn2p = pad_to(qn2.reshape(-1, 1), (tile_m, 1), 0)
    taup = pad_to(tau.reshape(-1, 1), (tile_m, 1), jnp.float32(-jnp.inf))
    accp = pad_to(acc, (tile_m, tile_n), jnp.float32(jnp.inf))
    skip = tile_skip_map(accp, tile_m, tile_n)

    out = tiled_distance_call(
        functools.partial(_kernel, prune=prune, metric=metric),
        (xp, xn2p, qp, qn2p, accp, taup),
        [
            pl.BlockSpec((tile_n, tile_k), lambda i, j, k, live: (j, k)),   # x
            pl.BlockSpec((1, tile_n), lambda i, j, k, live: (0, j)),        # xn2
            pl.BlockSpec((tile_m, tile_k), lambda i, j, k, live: (i, k)),   # q
            pl.BlockSpec((tile_m, 1), lambda i, j, k, live: (i, 0)),        # qn2
            pl.BlockSpec((tile_m, tile_n), lambda i, j, k, live: (i, j)),   # acc
            pl.BlockSpec((tile_m, 1), lambda i, j, k, live: (i, 0)),        # tau
        ],
        skip=skip, tile_m=tile_m, tile_n=tile_n, tile_k=tile_k,
        dp=xp.shape[1], interpret=interpret,
    )
    return out[:m, :n], skip
