"""Pallas TPU kernel: fused running-top-K update.

The second hot op of the ANNS inner loop (after distance scoring): merge a
chunk of candidate scores into the per-query running top-K. The jnp path
concatenates [K + chunk] and re-sorts per chunk — O((K+C)·log) with an HBM
round-trip of the running state. This kernel keeps the running (scores,
ids) tile in VMEM and performs K passes of masked min-extraction over the
chunk — O(K·C) vector work, no HBM churn, exact.

Grid: one program per query tile; the chunk axis stays resident. For the
K ≤ 16, C ≤ 64k regime of the serving engine, K·C vector ops beat the
sort-based merge and, more importantly, remove the [QG, K+C] concatenate
buffer entirely. Oracle: ``ref.running_topk_ref``.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(scores_ref, ids_ref, run_s_ref, run_i_ref, out_s_ref, out_i_ref,
            *, k: int):
    """scores [bm, C] f32 (+inf = invalid), ids [bm, C] i32,
    run_s/run_i [bm, K] (ascending). Outputs the merged top-K.

    Every per-row pick is a compare-and-select against a lane iota
    followed by a lane reduction — no gathers, which Mosaic cannot
    lower — and every intermediate stays 2-D ([bm, 1] columns)."""
    cand_s = scores_ref[...]
    cand_i = ids_ref[...]
    run_s = run_s_ref[...]
    run_i = run_i_ref[...]
    bm, c = cand_s.shape
    cand_col = jax.lax.broadcasted_iota(jnp.int32, (bm, c), 1)
    run_col = jax.lax.broadcasted_iota(jnp.int32, (bm, k), 1)

    def pick(mask, vals, fill):
        # the single selected entry of each row (``fill`` where none is)
        if jnp.issubdtype(vals.dtype, jnp.floating):
            return jnp.min(jnp.where(mask, vals, fill), axis=1, keepdims=True)
        return jnp.max(jnp.where(mask, vals, fill), axis=1, keepdims=True)

    # K passes: extract the global min among (remaining run slot, remaining
    # candidates). run is sorted ascending, so its "cursor" is an index.
    out_s = jnp.full((bm, k), jnp.inf, jnp.float32)
    out_i = jnp.full((bm, k), -1, jnp.int32)
    cursor = jnp.zeros((bm, 1), jnp.int32)
    int_min = jnp.iinfo(jnp.int32).min
    for slot in range(k):                   # static K unroll
        # current head of the running list per row
        at_head = run_col == cursor
        head_s = pick(at_head, run_s, jnp.inf)
        head_i = pick(at_head, run_i, int_min)
        # best remaining candidate per row (first index among equal minima)
        cmin = jnp.min(cand_s, axis=1, keepdims=True)
        carg = jnp.min(jnp.where(cand_s == cmin, cand_col, c), axis=1,
                       keepdims=True)
        at_carg = cand_col == carg
        cid = pick(at_carg, cand_i, int_min)
        take_run = head_s <= cmin
        sel_s = jnp.where(take_run, head_s, cmin)
        sel_i = jnp.where(take_run, head_i, cid)
        out_s = jnp.where(run_col == slot, sel_s, out_s)
        out_i = jnp.where(run_col == slot, sel_i, out_i)
        cursor = jnp.where(take_run, cursor + 1, cursor)
        # knock out the taken candidate
        cand_s = jnp.where(at_carg & ~take_run, jnp.inf, cand_s)
    out_s_ref[...] = out_s
    out_i_ref[...] = out_i


@functools.partial(
    jax.jit, static_argnames=("k", "tile_m", "interpret")
)
def running_topk_update(
    scores: jnp.ndarray,      # [M, C] f32, +inf = invalid
    ids: jnp.ndarray,         # [M, C] i32
    run_s: jnp.ndarray,       # [M, K] f32 ascending
    run_i: jnp.ndarray,       # [M, K] i32
    *,
    k: int,
    tile_m: int = 8,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    m, c = scores.shape
    mp = -(-m // tile_m) * tile_m
    pad = ((0, mp - m), (0, 0))
    scores_p = jnp.pad(scores, pad, constant_values=jnp.inf)
    ids_p = jnp.pad(ids, pad, constant_values=-1)
    run_s_p = jnp.pad(run_s, pad, constant_values=jnp.inf)
    run_i_p = jnp.pad(run_i, pad, constant_values=-1)

    out_s, out_i = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(mp // tile_m,),
        in_specs=[
            pl.BlockSpec((tile_m, c), lambda i: (i, 0)),
            pl.BlockSpec((tile_m, c), lambda i: (i, 0)),
            pl.BlockSpec((tile_m, k), lambda i: (i, 0)),
            pl.BlockSpec((tile_m, k), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile_m, k), lambda i: (i, 0)),
            pl.BlockSpec((tile_m, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, k), jnp.float32),
            jax.ShapeDtypeStruct((mp, k), jnp.int32),
        ],
        interpret=interpret,
    )(scores_p, ids_p, run_s_p, run_i_p)
    return out_s[:m], out_i[:m]
