"""Monotone dimension-level pruning: thresholds, prewarm, heap merge.

The invariant everything here preserves (property-tested):

    S_k²(p,q) = Σ_{j≤k} d_j²(p,q) is non-decreasing in k, so once
    S_k² > τ² ≥ (final kth-best distance), p can never enter the top-K.

τ is only ever an *upper bound* on the final kth-best distance — it starts
at the kth-best among the prewarm sample (a subset of real candidates, so
an upper bound) and is tightened monotonically as heaps fill. Pruning is
therefore exact: it changes work, never results.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from repro.core.index import IVFIndex


@dataclass
class TopKHeap:
    """Vectorized per-query top-K state (scores ascending, -1 padded ids)."""

    scores: np.ndarray   # [NQ, K] float32, +inf padded
    ids: np.ndarray      # [NQ, K] int64, -1 padded

    @classmethod
    def empty(cls, nq: int, k: int) -> "TopKHeap":
        return cls(np.full((nq, k), np.inf, np.float32), np.full((nq, k), -1, np.int64))

    @property
    def tau(self) -> np.ndarray:
        """Current per-query pruning threshold = kth best so far (+inf if
        the heap is not full — can't prune before K real candidates)."""
        return self.scores[:, -1].copy()

    def merge_rows(self, rows: np.ndarray, new_scores: np.ndarray, new_ids: np.ndarray):
        """Merge candidates for a subset of queries.

        rows: [m] query indices; new_scores/new_ids: [m, C].
        """
        if rows.size == 0 or new_scores.shape[1] == 0:
            return
        k = self.scores.shape[1]
        cat_s = np.concatenate([self.scores[rows], new_scores.astype(np.float32)], axis=1)
        cat_i = np.concatenate([self.ids[rows], new_ids.astype(np.int64)], axis=1)
        # stable partial sort per row
        part = np.argpartition(cat_s, kth=k - 1, axis=1)[:, :k]
        take_s = np.take_along_axis(cat_s, part, axis=1)
        take_i = np.take_along_axis(cat_i, part, axis=1)
        order = np.argsort(take_s, axis=1, kind="stable")
        self.scores[rows] = np.take_along_axis(take_s, order, axis=1)
        self.ids[rows] = np.take_along_axis(take_i, order, axis=1)


def exact_scores(
    x: np.ndarray, q: np.ndarray, metric: str = "l2"
) -> np.ndarray:
    """Full-dimension scores, ascending-better. [NQ, N]."""
    if metric == "l2":
        return (
            np.sum(q * q, axis=1)[:, None]
            - 2.0 * (q @ x.T)
            + np.sum(x * x, axis=1)[None, :]
        ).astype(np.float32)
    elif metric == "ip":
        return (-(q @ x.T)).astype(np.float32)
    raise ValueError(metric)


def partial_scores_block(
    x_blk: np.ndarray,
    q_blk: np.ndarray,
    xnorm2_blk: np.ndarray,
    metric: str = "l2",
) -> np.ndarray:
    """One dimension block's contribution d_b² (or -partial dot). [NQ, N].

    Self-contained per block: ‖p‖²_b − 2 p·q|_b + ‖q‖²_b  (L2), or
    −p·q|_b (IP). Summing over blocks reconstructs the exact score.
    """
    if metric == "l2":
        qn = np.sum(q_blk * q_blk, axis=1)[:, None]
        return (qn - 2.0 * (q_blk @ x_blk.T) + xnorm2_blk[None, :]).astype(np.float32)
    elif metric == "ip":
        return (-(q_blk @ x_blk.T)).astype(np.float32)
    raise ValueError(metric)


def prewarm_tau(
    index: IVFIndex,
    q: np.ndarray,
    probes: np.ndarray,
    k: int,
    samples_per_cluster: int = 4,
    metric: str = "l2",
    dead_rows: Optional[np.ndarray] = None,
) -> np.ndarray:
    """PrewarmHeap (Alg. 1, lines 1–5): exactly score a small sample of real
    candidates per probed cluster; the kth-smallest sampled distance is a
    valid initial τ (the sample is a subset of the candidate set, so its
    kth-best upper-bounds the candidate set's kth-best).

    Sampled rows are *not* inserted into result heaps — they are re-scored
    by the main scan, which avoids duplicate ids in merged top-K lists.

    ``dead_rows`` (bool [NB], packed-row tombstones of the mutable data
    plane) excludes dead rows from the sample — a tombstoned vector must
    not tighten τ below the live candidate set's kth-best, or pruning
    would stop being exact.

    Returns tau0 [NQ] float32 (+inf where the sample was smaller than K).
    """
    nq = q.shape[0]
    take = np.minimum(index.sizes, samples_per_cluster)
    sample_rows_per_cluster = [
        np.arange(index.offsets[c], index.offsets[c] + take[c], dtype=np.int64)
        for c in range(index.nlist)
    ]
    # a probe table may repeat a cluster (a filter duplicate-fills the
    # slots of excluded ones): sample each cluster once, or a repeated
    # row counts twice toward K and τ drops below the kth-best
    probes = np.sort(probes, axis=1)
    first = np.ones(probes.shape, bool)
    first[:, 1:] = probes[:, 1:] != probes[:, :-1]
    all_rows = [
        np.concatenate([sample_rows_per_cluster[c] for c in probes[i][first[i]]])
        if probes.shape[1]
        else np.zeros((0,), np.int64)
        for i in range(nq)
    ]
    width = max((len(r) for r in all_rows), default=0)
    tau0 = np.full((nq,), np.inf, np.float32)
    if width == 0:
        return tau0
    mat = np.zeros((nq, width), np.int64)
    msk = np.zeros((nq, width), bool)
    for i, rows in enumerate(all_rows):
        mat[i, : len(rows)] = rows
        msk[i, : len(rows)] = True
    if dead_rows is not None:
        msk &= ~dead_rows[mat]
    cand = index.x[mat]                                    # [NQ, W, D]
    if metric == "l2":
        diff = cand - q[:, None, :]
        sc = np.sum(diff * diff, axis=2).astype(np.float32)
    else:
        sc = -np.sum(cand * q[:, None, :], axis=2).astype(np.float32)
    sc = np.where(msk, sc, np.inf)
    kth = np.sort(sc, axis=1)[:, k - 1] if width >= k else np.full((nq,), np.inf)
    counts = msk.sum(axis=1)
    tau0 = np.where(counts >= k, kth, np.inf).astype(np.float32)
    return tau0
