"""Pallas TPU kernel: fused running-top-K update.

The second hot op of the ANNS inner loop (after distance scoring): merge a
chunk of candidate scores into the per-query running top-K. The jnp path
concatenates [K + chunk] and re-sorts per chunk — O((K+C)·log) with an HBM
round-trip of the running state. This kernel keeps the running (scores,
ids) tile in VMEM and inserts the chunk's candidates into it one at a
time, smallest first — no [QG, K+C] concatenate buffer, exact.

**Pass count.** A pass takes each row's smallest remaining candidate and
inserts it if it lies below the row's current K-th score. Candidates come
in ascending order and the K-th score only falls as they go in, so a
candidate at or above the row's *starting* K-th score can never enter,
and of those below it at most K can. A tile of ``tile_m`` rows therefore
needs

    n[tile] = min(K, max over its rows of count(scores < run_s[:, K-1]))

passes, and gets exactly that many. :func:`topk_pass_counts` computes the
count outside the kernel (Mosaic will not branch on an in-kernel vector
reduction); it reaches the kernel as a scalar-prefetch operand in SMEM,
and each of the K unrolled passes is guarded by ``pl.when(p < n[tile])``.
The count holds for any input: with the partial-distance kernel's prune
most rows have no finite candidate at all, and most tiles run 0 passes.

**Ties**, as ``ref.running_topk_ref``'s stable ``top_k`` over
[run, candidates] has them: a running entry wins a tie with a candidate
(a candidate goes in after every entry ``<=`` it); among equal candidates
the lower column goes first; a candidate equal to the K-th score is not
taken; ids of +inf slots stay as they are.

Grid: one program per query tile; the chunk axis stays resident.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_M = 8      # query rows per program: one sublane tile


def _kernel(n_ref, scores_ref, ids_ref, run_s_ref, run_i_ref,
            out_s_ref, out_i_ref, cand_ref, *, k: int):
    """n [tiles] int32 in SMEM (passes per tile), scores [bm, C] f32
    (+inf = invalid), ids [bm, C] i32, run_s/run_i [bm, K] (ascending).
    The outputs carry the running list from pass to pass; ``cand_ref``
    the candidates not yet taken.

    Every per-row pick is a compare-and-select against a lane iota
    followed by a lane reduction — no gathers, which Mosaic cannot
    lower — and every intermediate stays 2-D ([bm, 1] columns)."""
    n = n_ref[pl.program_id(0)]
    out_s_ref[...] = run_s_ref[...]
    out_i_ref[...] = run_i_ref[...]
    cand_ref[...] = scores_ref[...]
    bm, c = cand_ref.shape
    cand_col = jax.lax.broadcasted_iota(jnp.int32, (bm, c), 1)
    first = jax.lax.broadcasted_iota(jnp.int32, (bm, k), 1) == 0
    int_min = jnp.iinfo(jnp.int32).min

    def insert_smallest():
        cand = cand_ref[...]
        # smallest remaining candidate per row, first column among equals
        cmin = jnp.min(cand, axis=1, keepdims=True)
        carg = jnp.min(jnp.where(cand == cmin, cand_col, c), axis=1,
                       keepdims=True)
        at_carg = cand_col == carg
        cid = jnp.max(jnp.where(at_carg, ids_ref[...], int_min), axis=1,
                      keepdims=True)
        cand_ref[...] = jnp.where(at_carg, jnp.inf, cand)
        # element-wise insert: slot j keeps its entry if it is <= cmin,
        # takes cmin if the slot before it is <= cmin, else the entry
        # before it. A cmin at or above the K-th score changes nothing.
        s, i = out_s_ref[...], out_i_ref[...]
        prev_s = jnp.where(first, -jnp.inf, pltpu.roll(s, 1, 1))
        prev_i = pltpu.roll(i, 1, 1)
        keep = s <= cmin
        put = prev_s <= cmin
        out_s_ref[...] = jnp.where(keep, s, jnp.where(put, cmin, prev_s))
        out_i_ref[...] = jnp.where(keep, i, jnp.where(put, cid, prev_i))

    for p in range(k):                      # static K unroll
        pl.when(p < n)(insert_smallest)


def topk_pass_counts(scores: jnp.ndarray, run_s: jnp.ndarray, *, k: int,
                     tile_m: int = TILE_M) -> jnp.ndarray:
    """Insertion passes each ``tile_m``-row tile of a merge needs:
    [ceil(M / tile_m)] int32, min(K, the most candidates of any of the
    tile's rows that lie below the row's K-th running score)."""
    below = jnp.sum(scores < run_s[:, k - 1:k], axis=1, dtype=jnp.int32)
    below = jnp.pad(below, (0, (-below.shape[0]) % tile_m))
    return jnp.minimum(below.reshape(-1, tile_m).max(axis=1), k)


@functools.partial(
    jax.jit, static_argnames=("k", "tile_m", "interpret")
)
def running_topk_update(
    scores: jnp.ndarray,      # [M, C] f32, +inf = invalid
    ids: jnp.ndarray,         # [M, C] i32
    run_s: jnp.ndarray,       # [M, K] f32 ascending
    run_i: jnp.ndarray,       # [M, K] i32
    passes: Optional[jnp.ndarray] = None,   # [ceil(M / tile_m)] i32
    *,
    k: int,
    tile_m: int = TILE_M,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Merged (scores [M, K], ids [M, K]). ``passes`` is
    :func:`topk_pass_counts` of the same operands, for a caller that
    counts them too; it is computed here when not given."""
    m, c = scores.shape
    if passes is None:
        passes = topk_pass_counts(scores, run_s, k=k, tile_m=tile_m)
    mp = -(-m // tile_m) * tile_m
    pad = ((0, mp - m), (0, 0))
    scores_p = jnp.pad(scores, pad, constant_values=jnp.inf)
    ids_p = jnp.pad(ids, pad, constant_values=-1)
    run_s_p = jnp.pad(run_s, pad, constant_values=jnp.inf)
    run_i_p = jnp.pad(run_i, pad, constant_values=-1)

    out_s, out_i = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(mp // tile_m,),
            in_specs=[
                pl.BlockSpec((tile_m, c), lambda i, n: (i, 0)),
                pl.BlockSpec((tile_m, c), lambda i, n: (i, 0)),
                pl.BlockSpec((tile_m, k), lambda i, n: (i, 0)),
                pl.BlockSpec((tile_m, k), lambda i, n: (i, 0)),
            ],
            out_specs=[
                pl.BlockSpec((tile_m, k), lambda i, n: (i, 0)),
                pl.BlockSpec((tile_m, k), lambda i, n: (i, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((tile_m, c), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((mp, k), jnp.float32),
            jax.ShapeDtypeStruct((mp, k), jnp.int32),
        ],
        interpret=interpret,
    )(passes, scores_p, ids_p, run_s_p, run_i_p)
    return out_s[:m], out_i[:m]
