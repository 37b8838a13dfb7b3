"""End-to-end search paths.

Two engines, both exact w.r.t. the probed candidate set:

* :func:`search_oracle` — single-node Faiss-like IVF scan (the paper's
  baseline and our ground truth for all exactness tests).
* :func:`harmony_search` — the paper's Algorithm 1 as a host-scheduled,
  stage-synchronous engine with **dynamic candidate compaction** between
  dimension stages. This is the CPU-measured reproduction path; the
  TPU-target SPMD path (masked accumulators + Pallas tile-skip) lives in
  ``repro.core.pipeline`` and is validated against the same oracle.

Schedule realized here (per DESIGN.md):

* vector-level pipeline = queries visit their probed vector shards in ring
  order, one shard per stage; top-K heaps tighten τ between stages
  (Fig. 5(a): stage A's results prune stage B's work).
* dimension-level pipeline = within a visit, dimension blocks are processed
  in a per-shard rotated order (``plan.ring_offsets``), partial sums are
  accumulated, and pairs whose running S² exceeds τ are pruned; rows dead
  for every query are compacted away (Fig. 5(b)).
"""

from __future__ import annotations

import time
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.config import HarmonyConfig
from repro.core.index import IVFIndex, ShardedCorpus, assign_queries, dim_block_bounds
from repro.core.pruning import TopKHeap, partial_scores_block, prewarm_tau
from repro.core.types import Filter, PartitionPlan, SearchResult


# ---------------------------------------------------------------------------
# Filter compilation: predicate → packed-row bitmap → probe pushdown
# ---------------------------------------------------------------------------


def filter_bitmap(index: IVFIndex, flt: Filter) -> np.ndarray:
    """Compile a :class:`Filter` to this segment's *allowed* bitmap
    (bool [NB], packed-row order).

    Cached on the immutable segment index keyed by the (hashable) filter
    value, so re-serving the same predicate re-uses the bitmap — the
    filtered analogue of the ``dead_shard_mask`` cache. A corpus without
    metadata allows nothing (absent attributes can't satisfy a
    predicate), matching :meth:`Filter.evaluate` on a missing column."""
    cache = index.__dict__.setdefault("_filter_bitmaps", {})
    bm = cache.get(flt)
    if bm is None:
        if len(cache) >= 64:        # bound the per-segment bitmap cache
            cache.clear()
        if index.meta is None:
            bm = np.zeros(index.nb, bool)
        else:
            bm = flt.evaluate(index.meta.tags, index.meta.nums, index.nb)
        cache[flt] = bm
    return bm


def filter_excluded_rows(
    index: IVFIndex, flt: Optional[Filter],
    dead_rows: Optional[np.ndarray],
) -> Optional[np.ndarray]:
    """Merge a filter's allowed bitmap with the tombstones into one
    *excluded* mask (bool [NB]) — a filter is just a per-query tombstone
    set, so the whole dead-row masking path (oracle member mask, host
    engine's shard remap, executor's chunk table) applies verbatim.
    Returns None when nothing is excluded (the unfiltered fast path)."""
    if flt is None:
        return dead_rows if dead_rows is not None and dead_rows.any() else None
    excluded = ~filter_bitmap(index, flt)
    if dead_rows is not None:
        excluded = excluded | dead_rows
    return excluded


def filtered_assign_queries(
    index: IVFIndex,
    q: np.ndarray,
    excluded: Optional[np.ndarray],
    nprobe: Optional[int] = None,
) -> np.ndarray:
    """Probe selection with predicate pushdown: clusters whose every row
    is excluded (by the filter and/or tombstones) are dropped from the
    centroid ranking, so low-selectivity filters spend their probe budget
    on clusters that can actually produce candidates.

    Slots that would land on a fully-excluded cluster (fewer live
    clusters than ``nprobe``) are *duplicate-filled* with the query's
    best live cluster instead of a sentinel: every downstream consumer
    (member-set assignment, τ prewarm's per-cluster sampling, the visit
    schedule's ``np.unique``, the executor's chunk table) treats
    duplicates as one probe, while a negative sentinel would wrap or
    crash them. Row-level masking stays the source of truth, so this is
    pure work avoidance — never a correctness dependency.

    Selectivity-aware widening: when the allowed fraction of rows falls
    below ``cfg.filter_widen_threshold``, the effective ``nprobe`` scales
    by ~``threshold / selectivity`` (capped at ``filter_widen_cap`` ×,
    clamped to ``nlist``). Candidates thin out linearly with selectivity,
    so a fixed probe budget starves a sel=0.01 filter of candidates long
    before it hurts recall at sel=0.5 — widening spends probes exactly
    where the filter made them cheap. An explicitly passed ``nprobe`` is
    a caller override and is never widened."""
    explicit = nprobe is not None
    nprobe = nprobe or index.cfg.nprobe
    if excluded is None or not excluded.any():
        return assign_queries(index, q, nprobe)
    thr = getattr(index.cfg, "filter_widen_threshold", 0.0)
    sel = float((~excluded).mean())
    if not explicit and thr > 0.0 and 0.0 < sel < thr:
        cap = max(1.0, getattr(index.cfg, "filter_widen_cap", 1.0))
        nprobe = min(index.nlist,
                     int(np.ceil(nprobe * min(cap, thr / sel))))
    live_cluster = np.bincount(
        index.cluster_of[~excluded], minlength=index.nlist
    ) > 0
    qn = np.sum(q * q, axis=1)[:, None]
    cn = np.sum(index.centers * index.centers, axis=1)[None, :]
    d = qn - 2.0 * (q @ index.centers.T) + cn
    d = np.where(live_cluster[None, :], d, np.inf)
    probes = np.argsort(d, axis=1)[:, :nprobe].astype(np.int32)
    picked = np.take_along_axis(d, probes.astype(np.int64), axis=1)
    bad = ~np.isfinite(picked)
    if bad.any():
        probes = np.where(bad, probes[:, :1], probes)
    return probes


# ---------------------------------------------------------------------------
# Oracle (single-node Faiss-like)
# ---------------------------------------------------------------------------


def search_oracle(
    index: IVFIndex,
    q: np.ndarray,
    k: Optional[int] = None,
    nprobe: Optional[int] = None,
    chunk: int = 128,
    dead_rows: Optional[np.ndarray] = None,
    flt: Optional[Filter] = None,
) -> SearchResult:
    """Exact top-k over probed clusters (masked full scan, chunked).

    ``dead_rows`` (bool [NB], packed-row tombstones) excludes deleted /
    superseded rows from the candidate set — the sealed-segment masking
    of the mutable data plane. ``flt`` additionally restricts candidates
    to rows matching the metadata predicate (the filtered ground truth:
    at ``nprobe=nlist`` this is the exact brute-force filtered top-k)."""
    cfg = index.cfg
    k = k or cfg.topk
    if flt is not None:
        dead_rows = filter_excluded_rows(index, flt, dead_rows)
    probes = assign_queries(index, q, nprobe)
    nq = q.shape[0]
    out_s = np.full((nq, k), np.inf, np.float32)
    out_i = np.full((nq, k), -1, np.int64)
    t0 = time.perf_counter()
    # corpus norms are query-invariant: materialize once (cached on the
    # index), not inside every query chunk
    xn2 = index.xnorm2 if cfg.metric == "l2" else None
    for lo in range(0, nq, chunk):
        hi = min(nq, lo + chunk)
        member = np.zeros((hi - lo, index.nlist), bool)
        member[np.arange(hi - lo)[:, None], probes[lo:hi]] = True
        mask = member[:, index.cluster_of]                     # [m, NB]
        if dead_rows is not None:
            mask &= ~dead_rows[None, :]
        if cfg.metric == "l2":
            d = (
                np.sum(q[lo:hi] * q[lo:hi], axis=1)[:, None]
                - 2.0 * (q[lo:hi] @ index.x.T)
                + xn2[None, :]
            )
        else:
            d = -(q[lo:hi] @ index.x.T)
        d = np.where(mask, d, np.inf).astype(np.float32)
        part = np.argpartition(d, kth=k - 1, axis=1)[:, :k]
        sc = np.take_along_axis(d, part, axis=1)
        order = np.argsort(sc, axis=1, kind="stable")
        out_s[lo:hi] = np.take_along_axis(sc, order, axis=1)
        out_i[lo:hi] = index.ids[np.take_along_axis(part, order, axis=1)]
        out_i[lo:hi][out_s[lo:hi] == np.inf] = -1
    dt = time.perf_counter() - t0
    return SearchResult(ids=out_i, scores=out_s, stats={"wall_s": dt})


# ---------------------------------------------------------------------------
# Two-stage quantized search (int8 scan → exact fp32 re-rank), host engine
# ---------------------------------------------------------------------------


def two_stage_search(
    index: IVFIndex,
    q: np.ndarray,
    k: Optional[int] = None,
    nprobe: Optional[int] = None,
    probes: Optional[np.ndarray] = None,
    rerank_factor: Optional[int] = None,
    dead_rows: Optional[np.ndarray] = None,
    quant_blocks: Optional[int] = None,
    chunk: int = 128,
) -> SearchResult:
    """Stage 1 scores the probed, live candidate set with the segment's
    sealed int8 codes (quantized L2, int32 dot accumulation) and keeps the
    best ``k·rerank_factor`` rows per query; stage 2 gathers those rows'
    fp32 vectors and rescores them exactly, so every returned score is a
    true fp32 distance.

    Exactness: stage 2 returns the true top-k *of the stage-1 survivor
    set*. Quantization error can only demote a true top-k candidate out of
    the survivor set, never corrupt a returned score — and once
    ``k·rerank_factor`` covers the whole probed candidate set, the result
    is identical to :func:`search_oracle` (asserted in tests). L2 only:
    the shared-grid difference form has no inner-product analogue.
    """
    cfg = index.cfg
    assert cfg.metric == "l2", "int8 two-stage search supports l2 only"
    k = k or cfg.topk
    rerank_factor = rerank_factor or cfg.rerank_factor
    quant = index.int8_quant(quant_blocks or cfg.quant_blocks)
    if probes is None:
        probes = assign_queries(index, q, nprobe)
    nq = q.shape[0]
    kp = min(max(k, k * rerank_factor), index.nb)
    out_s = np.full((nq, k), np.inf, np.float32)
    out_i = np.full((nq, k), -1, np.int64)
    t0 = time.perf_counter()
    q_codes = quant.encode(q)
    xn2 = index.xnorm2
    survivors = 0
    for lo in range(0, nq, chunk):
        hi = min(nq, lo + chunk)
        m = hi - lo
        member = np.zeros((m, index.nlist), bool)
        if probes.shape[1]:
            member[np.arange(m)[:, None], probes[lo:hi]] = True
        mask = member[:, index.cluster_of]                     # [m, NB]
        if dead_rows is not None:
            mask &= ~dead_rows[None, :]
        # stage 1: quantized distances over the masked candidate set
        d8 = np.where(mask, quant.scores(q_codes[lo:hi]), np.inf)
        part = np.argpartition(d8, kth=kp - 1, axis=1)[:, :kp]  # packed rows
        valid = np.isfinite(np.take_along_axis(d8, part, axis=1))
        survivors += int(valid.sum())
        # stage 2: exact fp32 re-rank of the survivors
        qf = q[lo:hi]
        xg = index.x[part]                                     # [m, kp, D]
        d = (
            np.sum(qf * qf, axis=1)[:, None]
            - 2.0 * np.einsum("md,mkd->mk", qf, xg)
            + xn2[part]
        )
        d = np.where(valid, d, np.inf).astype(np.float32)
        if kp > k:
            sel = np.argpartition(d, kth=k - 1, axis=1)[:, :k]
        else:
            sel = np.broadcast_to(np.arange(kp), (m, kp))
        sc = np.take_along_axis(d, sel, axis=1)
        order = np.argsort(sc, axis=1, kind="stable")
        nk = min(k, kp)
        out_s[lo:hi, :nk] = np.take_along_axis(sc, order, axis=1)[:, :nk]
        rows = np.take_along_axis(part, np.take_along_axis(sel, order, axis=1),
                                  axis=1)[:, :nk]
        out_i[lo:hi, :nk] = index.ids[rows]
        out_i[lo:hi][out_s[lo:hi] == np.inf] = -1
    dt = time.perf_counter() - t0
    return SearchResult(
        ids=out_i,
        scores=out_s,
        stats={
            "wall_s": dt,
            "precision": "int8",
            "rerank_k": kp,
            "stage1_survivors": survivors,
        },
    )


# ---------------------------------------------------------------------------
# HARMONY staged engine
# ---------------------------------------------------------------------------


class SearchStats:
    """Structural + timing counters for benchmarks and the roofline model."""

    def __init__(self, d_blocks: int, v_shards: int):
        self.slice_total = np.zeros(d_blocks, np.int64)   # pairs reaching slot j
        self.slice_alive = np.zeros(d_blocks, np.int64)   # pairs computed at slot j
        self.pair_flops = 0                                # pair-level (pruned) flops
        self.row_flops = 0                                 # compacted-matmul flops
        self.dense_flops = 0                               # no-pruning flops
        self.shard_pair_flops = np.zeros(v_shards, np.int64)
        self.comm_bytes = defaultdict(int)
        self.visits = 0
        self.stages = 0
        self.wall_comp_s = 0.0
        self.wall_other_s = 0.0
        # per-(stage, machine) pair-flops — machine (v, b) of the V×B grid
        # owns dimension block b of shard v; the cluster's critical path is
        # max-over-machines per stage (dimension blocks pipeline across
        # machines in steady state, per Fig. 5)
        self.machine_flops = defaultdict(float)   # (stage, v*B+b) → flops
        self.d_blocks = d_blocks
        self.max_pair_buffer = 0         # peak acc elements in any visit

    def parallel_wall_s(self, flops_rate: float = 5e9,
                        net_bw: float = 12.5e9, latency: float = 15e-6) -> float:
        """Critical-path wall time of the modeled cluster: per stage the
        busiest machine's pair-flops / rate, plus the comm model. The
        benchmarks calibrate ``flops_rate`` from a measured single-node
        run so modes are compared on one consistent hardware model."""
        per_stage: Dict[int, float] = defaultdict(float)
        agg: Dict[int, Dict[int, float]] = defaultdict(lambda: defaultdict(float))
        for (stage, machine), fl in self.machine_flops.items():
            agg[stage][machine] += fl
        comp = sum(max(m.values()) for m in agg.values()) / flops_rate if agg else 0.0
        comm = sum(self.comm_bytes.values()) / net_bw + latency * max(self.visits, 1)
        return comp + comm

    def as_dict(self) -> Dict:
        tot = np.maximum(self.slice_total, 1)
        return {
            "slice_pruned_ratio": (1.0 - self.slice_alive / tot).tolist(),
            "pair_flops": int(self.pair_flops),
            "row_flops": int(self.row_flops),
            "dense_flops": int(self.dense_flops),
            "shard_pair_flops": self.shard_pair_flops.tolist(),
            "comm_bytes": dict(self.comm_bytes),
            "visits": self.visits,
            "stages": self.stages,
            "wall_comp_s": self.wall_comp_s,
            "wall_other_s": self.wall_other_s,
            "parallel_wall_s": self.parallel_wall_s(),
            "machine_flops": {f"{k[0]}:{k[1]}": float(v)
                              for k, v in self.machine_flops.items()},
            "max_pair_buffer": int(self.max_pair_buffer),
        }


def _visit_schedule(
    probes: np.ndarray, plan: PartitionPlan
) -> List[List[Tuple[int, np.ndarray]]]:
    """Ring visit order: query i's probed shards, starting from the shard of
    its top-1 probe and walking the ring. Returns per-stage lists of
    (shard, query_indices)."""
    nq = probes.shape[0]
    V = plan.v_shards
    shard_of = plan.cluster_to_shard[probes]               # [NQ, P]
    per_stage: List[Dict[int, List[int]]] = []
    max_stages = 0
    visit_lists: List[np.ndarray] = []
    for i in range(nq):
        shards = shard_of[i]
        start = shards[0]
        uniq = np.unique(shards)
        # ring order from start
        order = np.argsort((uniq - start) % V, kind="stable")
        visit_lists.append(uniq[order])
        max_stages = max(max_stages, len(uniq))
    schedule: List[List[Tuple[int, np.ndarray]]] = []
    for s in range(max_stages):
        by_shard: Dict[int, List[int]] = defaultdict(list)
        for i, visits in enumerate(visit_lists):
            if s < len(visits):
                by_shard[int(visits[s])].append(i)
        schedule.append(
            [(v, np.asarray(qs, np.int64)) for v, qs in sorted(by_shard.items())]
        )
    return schedule


def harmony_search(
    index: IVFIndex,
    corpus: ShardedCorpus,
    q: np.ndarray,
    k: Optional[int] = None,
    nprobe: Optional[int] = None,
    enable_pruning: Optional[bool] = None,
    pipeline: bool = True,
    collect_stats: bool = True,
    dead_rows: Optional[np.ndarray] = None,
    dead_key: Optional[tuple] = None,
    probes: Optional[np.ndarray] = None,
) -> SearchResult:
    """Distributed HARMONY search (host-scheduled reproduction engine).

    ``probes`` (int [nq, nprobe']) — precomputed probe table; skips the
    internal :func:`assign_queries` so a caller that already selected
    probes (filter-aware pushdown/widening in the serving engine) scans
    exactly those clusters.

    ``dead_rows`` (bool [NB] over *packed* index rows) applies the mutable
    data plane's tombstones exactly: dead rows are excluded from the τ
    prewarm sample and masked out of every candidate batch before it can
    enter a heap, so a deleted/superseded id can neither appear in results
    nor tighten pruning below the live kth-best.

    ``dead_key`` — the data plane's ``(generation, dead_version)`` at the
    snapshot this search runs against; lets the corpus cache the
    packed→shard tombstone remap across batches (see
    :meth:`ShardedCorpus.dead_shard_mask`)."""
    cfg = index.cfg
    plan = corpus.plan
    k = k or cfg.topk
    metric = cfg.metric
    if enable_pruning is None:
        enable_pruning = cfg.enable_pruning
    nq, D = q.shape
    V, B = plan.v_shards, plan.d_blocks
    bounds = dim_block_bounds(D, B)
    stats = SearchStats(B, V)

    t_host0 = time.perf_counter()
    if probes is None:
        probes = assign_queries(index, q, nprobe)
    tau0 = (
        prewarm_tau(index, q, probes, k, cfg.prewarm_samples, metric,
                    dead_rows=dead_rows)
        if enable_pruning
        else np.full((nq,), np.inf, np.float32)
    )
    heap = TopKHeap.empty(nq, k)
    schedule = (
        _visit_schedule(probes, plan)
        if pipeline
        else [_all_visits(probes, plan)]
    )
    # remap packed-row tombstones onto the shard layout via the corpus's
    # precomputed permutation (cached across batches when dead_key is the
    # snapshot's (generation, dead_version))
    dead_sh = None
    if dead_rows is not None and dead_rows.any():
        dead_sh = corpus.dead_shard_mask(dead_rows, key=dead_key)
    stats.wall_other_s += time.perf_counter() - t_host0

    for stage in schedule:
        stats.stages += 1
        pending: List[Tuple[np.ndarray, TopKHeap]] = []
        tau_stage = np.minimum(tau0, heap.tau) if enable_pruning else tau0
        for v, qidx in stage:
            local = _process_visit(
                corpus=corpus,
                probes=probes,
                q=q,
                qidx=qidx,
                v=v,
                plan=plan,
                bounds=bounds,
                tau_in=tau_stage[qidx],
                k=k,
                metric=metric,
                enable_pruning=enable_pruning,
                stats=stats,
                stage_idx=stats.stages - 1,
                dead_sh=dead_sh,
            )
            if local is not None:
                pending.append((qidx, local))
                stats.comm_bytes["result_return"] += len(qidx) * k * 12
        # stage barrier: merges become visible to the next stage
        t0 = time.perf_counter()
        for qidx, local in pending:
            heap.merge_rows(qidx, local.scores, local.ids)
        stats.wall_other_s += time.perf_counter() - t0

    # never report an id whose score is +inf (pruned-to-nothing or dead
    # slots) — matches the oracle's -1 convention
    heap.ids[~np.isfinite(heap.scores)] = -1
    res = SearchResult(ids=heap.ids, scores=heap.scores, stats=stats.as_dict())
    return res


def _process_visit(
    corpus: ShardedCorpus,
    probes: np.ndarray,
    q: np.ndarray,
    qidx: np.ndarray,
    v: int,
    plan: PartitionPlan,
    bounds: Sequence[Tuple[int, int]],
    tau_in: np.ndarray,
    k: int,
    metric: str,
    enable_pruning: bool,
    stats: "SearchStats",
    stage_idx: int,
    dead_sh: Optional[np.ndarray] = None,
) -> Optional[TopKHeap]:
    """One (shard, query-group) visit.

    Vector-level pipeline (Alg. 1 VectorPipeline): probed clusters on this
    shard are scanned sequentially in probe-rank order; after each cluster
    batch the *local* heap refines τ, so later batches prune harder.
    Dimension-level pipeline (Alg. 1 DimensionPipeline): within a batch,
    dimension blocks are processed in the shard's rotated ring order with
    monotone partial-sum pruning and dead-row compaction between slices.
    """
    V, B = plan.v_shards, plan.d_blocks
    D = q.shape[1]
    t0 = time.perf_counter()
    cl = probes[qidx]                                      # [m, P]
    on_shard = plan.cluster_to_shard[cl] == v              # [m, P]
    if not on_shard.any():
        stats.wall_other_s += time.perf_counter() - t0
        return None
    # probe-rank-ordered cluster scan: rank r = best rank among group queries
    best_rank: Dict[int, int] = {}
    m, P = cl.shape
    for r in range(P):
        for c in cl[:, r][on_shard[:, r]]:
            best_rank.setdefault(int(c), r)
    ordered = sorted(best_rank, key=lambda c: (best_rank[c], c))
    stats.visits += 1
    local = TopKHeap.empty(len(qidx), k)
    tau_local = tau_in.astype(np.float32).copy()
    qg = q[qidx]
    stats.comm_bytes["query_dispatch"] += qg.size * 4
    stats.wall_other_s += time.perf_counter() - t0

    # staggered ring: base rotation by shard and stage; on top of it, the
    # queries of a visit are split into B sub-groups whose ring starts are
    # rotated per group (Fig. 5(b): Q1 starts D1, Q2 starts D2, ...) — this
    # is what spreads the unprunable first-slot work across all machines.
    offset = (int(plan.ring_offsets[v % V]) + stage_idx) % B

    for c in ordered:
        cv, lo_r, hi_r = corpus.cluster_slices[c]
        assert cv == v
        nrows = hi_r - lo_r
        if nrows == 0:
            continue
        sub_all = np.nonzero((cl == c).any(axis=1) & on_shard.any(axis=1))[0]
        if sub_all.size == 0:
            continue
        for g in range(min(B, len(sub_all))):
            sub = sub_all[g::B]
            if sub.size == 0:
                continue
            order = np.roll(np.arange(B), -((offset + g) % B))
            t0 = time.perf_counter()
            ms = len(sub)
            acc = np.zeros((ms, nrows), np.float32)
            if dead_sh is not None:
                # tombstoned rows enter the visit already pruned: they are
                # compacted away with the other dead pairs and can never
                # reach a heap (exactly the sealed-segment delete mask)
                acc[:, dead_sh[v, lo_r:hi_r]] = np.inf
            live_rows = np.arange(lo_r, hi_r)
            tau_g = tau_local[sub]
            stats.slice_total += ms * nrows   # every pair reaches every slot
            for pos, b in enumerate(order):
                blo, bhi = bounds[b]
                alive_pair = np.isfinite(acc)
                n_alive = int(alive_pair.sum())
                stats.slice_alive[pos] += n_alive
                keep = alive_pair.any(axis=0)
                if not keep.all():
                    acc = acc[:, keep]
                    live_rows = live_rows[keep]
                    alive_pair = alive_pair[:, keep]
                if acc.shape[1] == 0:
                    break
                xr = corpus.x_shard[v, live_rows, blo:bhi]
                xn = corpus.xnorm2_blk[v, b, live_rows]
                part = partial_scores_block(xr, qg[sub][:, blo:bhi], xn, metric)
                acc = np.where(alive_pair, acc + part, np.inf)
                nflop = 2 * n_alive * (bhi - blo)
                stats.pair_flops += nflop
                stats.row_flops += 2 * acc.shape[1] * ms * (bhi - blo)
                stats.shard_pair_flops[v] += nflop
                stats.machine_flops[(stage_idx, v * B + int(b))] += nflop
                if enable_pruning and pos < B - 1:
                    acc = np.where(acc > tau_g[:, None], np.inf, acc)
                    stats.comm_bytes["partial_results"] += int(np.isfinite(acc).sum()) * 4
                stats.comm_bytes["threshold_sync"] += ms * 4
            stats.dense_flops += 2 * nrows * ms * D
            stats.wall_comp_s += time.perf_counter() - t0
            stats.max_pair_buffer = max(stats.max_pair_buffer, ms * nrows)

            t0 = time.perf_counter()
            if acc.shape[1]:
                ids = corpus.ids_shard[v, live_rows]
                local.merge_rows(sub, acc, np.broadcast_to(ids, acc.shape))
                if enable_pruning:
                    tau_local[sub] = np.minimum(tau_local[sub], local.tau[sub])
            stats.wall_other_s += time.perf_counter() - t0
    return local


def _all_visits(probes: np.ndarray, plan: PartitionPlan):
    """Non-pipelined dispatch: every (shard, probing queries) visit in one
    stage — the 'synchronous execution' ablation (Fig. 9)."""
    shard_of = plan.cluster_to_shard[probes]
    out = []
    for v in range(plan.v_shards):
        qs = np.nonzero((shard_of == v).any(axis=1))[0]
        if qs.size:
            out.append((v, qs.astype(np.int64)))
    return out


# ---------------------------------------------------------------------------
# Mutable data plane: delta scan + cross-segment merge
# ---------------------------------------------------------------------------


def delta_topk(
    delta_x: np.ndarray,
    delta_ids: np.ndarray,
    delta_live: np.ndarray,
    q: np.ndarray,
    k: int,
    metric: str = "l2",
) -> Tuple[np.ndarray, np.ndarray]:
    """Exact brute-force top-k over the live rows of a delta buffer.

    The delta is small by construction (the compactor seals it before it
    grows), so a dense scan is the right tool — no clustering, no
    pruning, no approximation. Returns (scores [NQ, k] ascending
    +inf-padded, ids [NQ, k] int64 -1-padded).
    """
    from repro.core.pruning import exact_scores

    nq = q.shape[0]
    live = np.nonzero(delta_live)[0]
    if live.size == 0:
        return (np.full((nq, k), np.inf, np.float32),
                np.full((nq, k), -1, np.int64))
    sc = exact_scores(delta_x[live], q, metric)            # [NQ, n_live]
    ids = delta_ids[live]
    heap = TopKHeap.empty(nq, k)
    heap.merge_rows(np.arange(nq), sc, np.broadcast_to(ids, sc.shape))
    heap.ids[~np.isfinite(heap.scores)] = -1
    return heap.scores, heap.ids


def merge_topk(
    parts: Sequence[Tuple[np.ndarray, np.ndarray]],
    k: int,
    fused: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """Merge per-segment (scores, ids) top-k lists into one global top-k.

    ``fused=True`` folds each part into a running top-K with the fused
    :func:`repro.kernels.ops.running_topk_update` kernel (the same
    VMEM-resident primitive the SPMD ring uses between chunks) — the
    device-backend path; the default is the host ``TopKHeap`` merge.
    Both return (scores [NQ, k] ascending, ids [NQ, k] int64, -1 where
    +inf).

    The kernel carries ids as int32 (like the whole device pipeline, whose
    resident ``row_ids`` are int32); external ids beyond the int32 range
    fall back to the host merge rather than silently wrapping.
    """
    assert parts
    nq = parts[0][0].shape[0]
    if fused and any(np.abs(ids).max(initial=0) > np.iinfo(np.int32).max
                     for _, ids in parts):
        fused = False
    if fused:
        import jax.numpy as jnp

        from repro.kernels import ops as kops

        run_s = jnp.full((nq, k), jnp.inf, jnp.float32)
        run_i = jnp.full((nq, k), -1, jnp.int32)
        for sc, ids in parts:
            run_s, run_i = kops.running_topk_update(
                jnp.asarray(np.asarray(sc, np.float32)),
                jnp.asarray(np.asarray(ids, np.int32)),
                run_s, run_i, k=k,
            )
        scores = np.asarray(run_s)
        out_i = np.asarray(run_i).astype(np.int64)
    else:
        heap = TopKHeap.empty(nq, k)
        rows = np.arange(nq)
        for sc, ids in parts:
            heap.merge_rows(rows, sc, ids)
        scores, out_i = heap.scores, heap.ids
    out_i = out_i.copy()
    out_i[~np.isfinite(scores)] = -1
    return scores, out_i
