"""Device-resident batched search executor — serving through the
Pallas/SPMD pipeline with static-shape bucketing.

This closes the gap between the scheduler's batch former (which used to
dispatch every batch into the host-side numpy engine) and the TPU-target
SPMD ring pipeline of :mod:`repro.core.pipeline`: served batches now run
the jit'd shard_map step — Pallas partial-distance with tile-granular
early-stop, ppermute dimension ring, fused running-top-K, τ tightening
between chunks — end to end on the device mesh.

Design:

* **Corpus residency** — the sharded corpus, per-block norms, cluster ids
  and row ids are packed once (:func:`repro.core.pipeline.build_corpus_arrays`)
  and ``device_put`` on the mesh at construction. Serving a batch moves
  only the query block, probe table, τ seeds, and a small int32 row-index
  table host→device; the corpus never re-crosses the PCIe/ICI boundary.
* **Cold tier** (``tier="host"``) — for host-resident (demoted) segments
  nothing stays on the mesh: per batch, only the probed clusters' rows
  are gathered host-side (:func:`repro.core.pipeline.gather_host_candidates`)
  into the same static (qb, cap) bucket shapes and streamed up through a
  double-buffered async upload — :meth:`SpmdExecutor.prefetch` stages
  batch i+1's transfer while batch i's ring kernels run. int8 codes
  stream 4× less PCIe traffic than fp32 rows, and the fp32 re-rank reads
  host memory anyway, so the cold tier prefers the PR 6 quantized path.
  Results are bit-identical to ``tier="device"``: same gathered
  candidate set, same kernels, same bucket ladder.
* **Candidate gather** — probed clusters are contiguous row ranges of the
  resident shards (the IVF pack is cluster-sorted), so the host computes a
  per-shard row-index union and the device gathers those rows into a
  padded static candidate buffer (:func:`gather_local_candidates`). The
  ring then scans ``cap_b`` gathered rows instead of the full shard.
* **Static-shape bucketing** — jit recompiles per shape, and the
  scheduler's adaptive batches vary in both query count and candidate
  volume. Both are padded up a small ladder of (qb, cap) buckets; the
  compiled step for each bucket is cached, so replaying mixed batch sizes
  compiles each bucket exactly once. Batches larger than the biggest qb
  bucket are split and merged host-side.

Exactness: identical guarantees to the host engine and the oracle —
padding adds rows whose cluster id is -1 (matches no probe) and queries
whose τ is -inf (everything prunes), neither of which can enter a top-K.
Pruning is auto-disabled for ``metric="ip"`` (partial -dot sums are not
monotone, so τ-pruning is only exact for L2).
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import shard_map_compat
from repro.core.index import IVFIndex, assign_queries, preassign
from repro.core.pipeline import (
    SpmdConfig,
    build_corpus_arrays,
    build_query_arrays,
    corpus_shardings,
    gather_host_candidates,
    gather_local_candidates,
    ring_chunk_search,
)
from repro.core.pruning import prewarm_tau
from repro.core.router import load_aware_assignment, ring_offsets
from repro.core.types import PartitionPlan, SearchResult


@dataclass(frozen=True)
class ExecutorConfig:
    """Knobs of the device-resident executor.

    ``qb_buckets`` is the query-count ladder (each entry is rounded up to a
    multiple of the mesh's dimension-block count); the candidate-capacity
    ladder is derived as chunk·2^i up to the full shard capacity.
    """

    d_blocks: int = 1               # model-axis size; data axis gets the rest
    chunk: int = 256                # candidate rows scored per ring pass
    qb_buckets: Tuple[int, ...] = (8, 32, 128)
    use_pallas: Optional[bool] = None   # None → Pallas on TPU, jnp elsewhere
    x_dtype: str = "float32"
    precision: str = "fp32"         # "int8" → quantized stage-1 + fp32 re-rank
    rerank_factor: int = 4          # int8: stage-1 keeps k·rerank_factor rows
    tile_m: int = 128
    tile_n: int = 128
    tile_k: int = 128
    prune: Optional[bool] = None    # None → index.cfg.enable_pruning (L2 only)


def _default_mesh(d_blocks: int) -> Mesh:
    devs = jax.devices()
    n = len(devs)
    assert n % d_blocks == 0, (n, d_blocks)
    return Mesh(
        np.asarray(devs).reshape(n // d_blocks, d_blocks), ("data", "model")
    )


class SpmdExecutor:
    """Batched search over the device-resident SPMD pipeline.

    Self-contained: builds its own cluster→shard packing for the mesh
    geometry (independent of the host engine's cost-model plan, which may
    be rebuilt under it by replans — results are plan-invariant, so the
    two paths stay interchangeable oracles for each other).
    """

    def __init__(
        self,
        index: IVFIndex,
        cfg: Optional[ExecutorConfig] = None,
        mesh: Optional[Mesh] = None,
        tier: str = "device",
    ):
        assert tier in ("device", "host"), tier
        self.tier = tier
        self.index = index
        self.cfg = cfg or ExecutorConfig()
        self.mesh = mesh if mesh is not None else _default_mesh(self.cfg.d_blocks)
        V, B = self.mesh.devices.shape
        self.k = index.cfg.topk
        self.metric = index.cfg.metric
        self.precision = self.cfg.precision
        assert self.precision in ("fp32", "int8"), self.precision
        if self.precision == "int8":
            assert self.metric == "l2", "int8 tier is L2-only"
            assert self.cfg.rerank_factor >= 1, self.cfg.rerank_factor
        prune = self.cfg.prune
        if prune is None:
            prune = index.cfg.enable_pruning
        self.prune = bool(prune and self.metric == "l2")
        use_pallas = self.cfg.use_pallas
        if use_pallas is None:
            use_pallas = jax.default_backend() == "tpu"
        self.use_pallas = use_pallas

        plan = PartitionPlan(
            v_shards=V,
            d_blocks=B,
            cluster_to_shard=load_aware_assignment(index.sizes, None, V),
            ring_offsets=ring_offsets(V, B),
        )
        # pad_to=chunk keeps the full capacity (the top of the cap ladder)
        # chunk-aligned
        self.corpus = preassign(index, plan, pad_to=self.cfg.chunk)
        self.cap_full = self.corpus.cap
        dim_pad = -(-index.dim // B) * B
        self._base_scfg = SpmdConfig(
            v_shards=V,
            d_blocks=B,
            qb=8 * B,                   # placeholder; buckets override
            cap=self.cap_full,
            dim=dim_pad,
            nprobe=index.cfg.nprobe,
            k=self.k,
            chunk=self.cfg.chunk,
            metric=self.metric,
            prune=self.prune,
            x_dtype=self.cfg.x_dtype,
            precision=self.precision,
            use_pallas=self.use_pallas,
            tile_m=self.cfg.tile_m,
            tile_n=self.cfg.tile_n,
            tile_k=self.cfg.tile_k,
        )

        # bucket ladders (static shapes the step may compile for)
        self.qb_buckets = tuple(sorted({-(-b // B) * B for b in self.cfg.qb_buckets}))
        caps, c = [], self.cfg.chunk
        while c < self.cap_full:
            caps.append(c)
            c *= 2
        caps.append(self.cap_full)
        self.cap_buckets = tuple(caps)

        # corpus residency is tier-dependent: "device" uploads the packed
        # arrays to the mesh once at construction (the hot tier);
        # "host" keeps them in host RAM and streams only the probed
        # clusters' rows per batch through a double-buffered upload
        # (the cold tier — int8 codes preferred, 4× less PCIe traffic)
        quant = index.int8_quant() if self.precision == "int8" else None
        arrays = build_corpus_arrays(self.corpus, self._base_scfg, quant=quant)
        self._quant_grid = arrays.pop("quant_grid", None)
        sh = corpus_shardings(self._base_scfg, self.mesh)
        names = ("x_blocks", "xn2_blocks", "cluster_ids", "row_ids")
        if self.precision == "int8":
            names = names + ("scale2",)
        if tier == "device":
            self._resident = tuple(
                jax.device_put(arrays[name], sh[name]) for name in names
            )
            self._host_arrays = None
        else:
            self._resident = None
            self._host_arrays = {name: arrays[name] for name in names}
            # scale2 is B floats — park it on the mesh even for the cold
            # tier rather than re-streaming it per batch
            self._scale2_dev = (
                jax.device_put(arrays["scale2"], sh["scale2"])
                if self.precision == "int8" else None
            )
            ad, am = self._base_scfg.axis_data, self._base_scfg.axis_model
            from jax.sharding import NamedSharding
            self._stream_sh = (
                NamedSharding(self.mesh, P(ad, None, am)),   # x_c
                NamedSharding(self.mesh, P(am, ad, None)),   # xn2_c
                NamedSharding(self.mesh, P(ad, None)),       # cl_c
                NamedSharding(self.mesh, P(ad, None)),       # id_c
            )
            # double-buffered prefetch queue: candidate uploads staged by
            # the scheduler's formed-batch lookahead, keyed on the gather
            # table so the later dispatch recognizes its own rows. Two
            # slots = the upload of batch i+1 in flight while batch i
            # computes; device_put is async, so the transfer genuinely
            # overlaps the ring kernels.
            self._prefetched: Dict[tuple, tuple] = {}
        # stage-2 re-rank lookup (ext id → packed row), built lazily
        self._id_order: Optional[np.ndarray] = None
        self._sorted_ids: Optional[np.ndarray] = None

        # compile cache: (qb, cap, k, nprobe) → jit'd step
        self._steps: Dict[Tuple[int, int, int, int], object] = {}
        self.trace_counts: Dict[Tuple[int, int, int, int], int] = {}
        # probe-table widths a compiled step exists for (see search_batch)
        self._probe_widths: set = set()
        self.dispatches = 0
        self.queries = 0
        self.wall_s = 0.0
        self.tile_skipped = 0
        self.tile_total = 0
        # cold-tier counters (always 0 for a device-tier executor)
        self.cold_dispatches = 0
        self.bytes_streamed = 0
        self.prefetch_hits = 0
        self.prefetch_misses = 0
        self.prefetch_staged = 0

    def warmup(self, k: Optional[int] = None, nprobe=None):
        """Pre-compile the whole (qb, cap) bucket ladder.

        Serving paths that charge measured walls to a clock (the
        scheduler's virtual-clock replay) call this once up front so no
        in-trace dispatch ever pays a jit compile.

        ``nprobe`` may be an int or an iterable of probe-table widths;
        each width gets its own compiled steps (the compile cache keys on
        ``probes.shape[1]``, not on the config's nprobe — warming only the
        config default used to leave every explicit-probe dispatch cold).
        :meth:`search_batch` pads narrower probe tables up to the nearest
        warmed width, so a single warmed width also covers anything below
        it."""
        k = k or self.k
        k_step = min(k * self.cfg.rerank_factor, self.index.nb) \
            if self.precision == "int8" else k
        if nprobe is None:
            widths = (self.index.cfg.nprobe,)
        elif np.ndim(nprobe) == 0:
            widths = (int(nprobe),)
        else:
            widths = tuple(int(w) for w in nprobe)
        for w in widths:
            for qb in self.qb_buckets:
                for cap in self.cap_buckets:
                    key = (qb, cap, k_step, w)
                    self._get_step(self._bucket_cfg(*key))(
                        *self._warmup_args(*key))

    def _bucket_cfg(self, qb: int, cap: int, k: int, nprobe: int) -> SpmdConfig:
        return dataclasses.replace(self._base_scfg, qb=qb, cap=cap, k=k,
                                   nprobe=nprobe)

    def _warmup_args(self, qb: int, cap: int, k: int, nprobe: int) -> tuple:
        """Inputs of the (qb, cap, k, nprobe) bucket step for one dummy
        query over one candidate row."""
        bscfg = self._bucket_cfg(qb, cap, k, nprobe)
        rows = np.full((bscfg.v_shards, cap), -1, np.int32)
        rows[:, 0] = 0
        qarr = build_query_arrays(
            np.zeros((1, self.index.dim), np.float32), bscfg,
            np.zeros((1, nprobe), np.int32),
            np.full((1,), np.inf, np.float32),
            quant_grid=self._quant_grid,
        )
        cand = (self._upload_candidates(rows, cap)[0] if self.tier == "host"
                else (*self._resident, rows))
        return (*cand, qarr["queries"], qarr["probes"], qarr["tau0"])

    # ----------------------------------------------------------- bucketing
    def _pick_bucket(self, ladder: Tuple[int, ...], need: int) -> int:
        for b in ladder:
            if b >= need:
                return b
        return ladder[-1]

    def _gather_rows(self, probes: np.ndarray,
                     dead_rows: Optional[np.ndarray] = None):
        """Per-shard union of probed clusters' resident row ranges, padded
        to the smallest cap bucket. Returns (rows [V, cap_b] i32, cap_b);
        (None, 0) when the batch probes no resident rows.

        ``dead_rows`` (bool [NB] over *packed* index rows — the mutable
        data plane's tombstones) drops dead rows from the gather table, so
        deletes cost zero device work and never inflate K: masking happens
        in the host-side row union, the compiled step is untouched."""
        V = self._base_scfg.v_shards
        uniq = np.unique(probes) if probes.size else np.zeros(0, np.int64)
        uniq = uniq[uniq >= 0]
        per_shard = [[] for _ in range(V)]
        counts = np.zeros(V, np.int64)
        for c in uniq:
            v, lo, hi = self.corpus.cluster_slices[int(c)]
            if hi > lo:
                r = np.arange(lo, hi, dtype=np.int32)
                if dead_rows is not None:
                    # shard row lo+j of cluster c is packed row plo+j
                    plo, phi = self.index.cluster_rows(int(c))
                    r = r[~dead_rows[plo:phi]]
                if r.size:
                    per_shard[v].append(r)
                    counts[v] += r.size
        need = int(counts.max()) if len(uniq) else 0
        if need == 0:
            return None, 0
        cap_b = self._pick_bucket(self.cap_buckets, need)
        rows = np.full((V, cap_b), -1, np.int32)
        for v in range(V):
            if per_shard[v]:
                r = np.concatenate(per_shard[v])
                rows[v, : len(r)] = r
        return rows, cap_b

    # --------------------------------------------------------- compilation
    def _get_step(self, bscfg: SpmdConfig):
        key = (bscfg.qb, bscfg.cap, bscfg.k, bscfg.nprobe)
        step = self._steps.get(key)
        if step is None:
            step = (self._make_stream_step(bscfg, key)
                    if self.tier == "host" else self._make_step(bscfg, key))
            self._steps[key] = step
        self._probe_widths.add(bscfg.nprobe)
        return step

    def _make_step(self, bscfg: SpmdConfig, key):
        cap_full, db, counts = self.cap_full, bscfg.db, self.trace_counts
        int8 = self.precision == "int8"

        def device_fn(x_res, xn2_res, cl_res, id_res, *rest):
            # this Python body runs only while jit traces → counts compiles
            counts[key] = counts.get(key, 0) + 1
            if int8:
                scale2, rows, q_blk, probes, tau0 = rest
            else:
                scale2, (rows, q_blk, probes, tau0) = None, rest
            x_res = x_res.reshape(cap_full, db)
            xn2_res = xn2_res.reshape(cap_full)
            cl_res = cl_res.reshape(cap_full)
            id_res = id_res.reshape(cap_full)
            rows = rows.reshape(bscfg.cap)
            q_blk = q_blk.reshape(bscfg.qb, db)
            x_c, xn2_c, cl_c, id_c = gather_local_candidates(
                rows, x_res, xn2_res, cl_res, id_res
            )
            return ring_chunk_search(
                bscfg, x_c, xn2_c, cl_c, id_c, q_blk, probes, tau0,
                scale2=scale2,
            )

        ad, am = bscfg.axis_data, bscfg.axis_model
        resident_specs = (
            P(ad, None, am),        # x_blocks  (resident)
            P(am, ad, None),        # xn2_blocks (resident)
            P(ad, None),            # cluster_ids (resident)
            P(ad, None),            # row_ids (resident)
        )
        if int8:
            resident_specs = resident_specs + (P(am),)   # scale2 (resident)
        in_specs = resident_specs + (
            P(ad, None),            # rows (per-batch gather table)
            P(None, am),            # queries
            P(None, None),          # probes
            P(None),                # tau0
        )
        fn = shard_map_compat(
            device_fn, mesh=self.mesh, in_specs=in_specs,
            out_specs=(P(), P(), P()),
        )
        return jax.jit(fn)

    def _make_stream_step(self, bscfg: SpmdConfig, key):
        """Cold-tier step: the candidate arrays arrive *already gathered*
        (host-side, :func:`gather_host_candidates`) and streamed to the
        mesh, so the device body skips the resident gather and runs the
        identical ring kernels over the same (qb, cap) bucket shapes —
        one compile cache, bit-identical results to the resident path."""
        db, counts = bscfg.db, self.trace_counts
        int8 = self.precision == "int8"

        def device_fn(x_c, xn2_c, cl_c, id_c, *rest):
            counts[key] = counts.get(key, 0) + 1
            if int8:
                scale2, q_blk, probes, tau0 = rest
            else:
                scale2, (q_blk, probes, tau0) = None, rest
            x_c = x_c.reshape(bscfg.cap, db)
            xn2_c = xn2_c.reshape(bscfg.cap)
            cl_c = cl_c.reshape(bscfg.cap)
            id_c = id_c.reshape(bscfg.cap)
            q_blk = q_blk.reshape(bscfg.qb, db)
            return ring_chunk_search(
                bscfg, x_c, xn2_c, cl_c, id_c, q_blk, probes, tau0,
                scale2=scale2,
            )

        ad, am = bscfg.axis_data, bscfg.axis_model
        cand_specs = (
            P(ad, None, am),        # x_c  (streamed per batch)
            P(am, ad, None),        # xn2_c
            P(ad, None),            # cl_c
            P(ad, None),            # id_c
        )
        if int8:
            cand_specs = cand_specs + (P(am),)   # scale2 (resident)
        in_specs = cand_specs + (
            P(None, am),            # queries
            P(None, None),          # probes
            P(None),                # tau0
        )
        fn = shard_map_compat(
            device_fn, mesh=self.mesh, in_specs=in_specs,
            out_specs=(P(), P(), P()),
        )
        return jax.jit(fn)

    # ---------------------------------------------------- cold-tier stream
    def _upload_candidates(self, rows: np.ndarray, cap_b: int):
        """Gather the probed rows host-side and start their (async)
        upload. Returns ``(device_arrays, nbytes)`` — the arrays are
        valid step inputs immediately; the actual transfer overlaps
        whatever the device is computing when this is called."""
        cand = gather_host_candidates(self._host_arrays, rows)
        nbytes = sum(a.nbytes for a in cand.values())
        xs, ns, cs, is_ = self._stream_sh
        dev = (
            jax.device_put(cand["x_c"], xs),
            jax.device_put(cand["xn2_c"], ns),
            jax.device_put(cand["cl_c"], cs),
            jax.device_put(cand["id_c"], is_),
        )
        if self.precision == "int8":
            dev = dev + (self._scale2_dev,)
        return dev, nbytes

    def prefetch(
        self,
        queries: Optional[np.ndarray] = None,
        probes: Optional[np.ndarray] = None,
        dead_rows: Optional[np.ndarray] = None,
        nprobe: Optional[int] = None,
    ) -> None:
        """Stage the *next* batch's cold-candidate upload while the
        current batch computes (the scheduler calls this with its
        formed-batch lookahead). No-op on a device-tier executor.

        The staged upload is keyed on the gather table itself, so the
        later :meth:`search_batch` recognizes its own candidate set no
        matter how the batch was predicted; a wrong prediction is just a
        miss (the dispatch uploads synchronously), never a wrong answer.
        The queue is bounded to two slots — classic double buffering."""
        if self.tier != "host":
            return
        if probes is None:
            if queries is None:
                return
            queries = np.asarray(queries, np.float32)
            if queries.ndim == 1:
                queries = queries[None]
            probes = assign_queries(self.index, queries, nprobe)
        max_qb = self.qb_buckets[-1]
        for lo in range(0, probes.shape[0], max_qb):
            rows, cap_b = self._gather_rows(probes[lo:lo + max_qb], dead_rows)
            if cap_b == 0:
                continue
            key = (rows.tobytes(), cap_b)
            if key in self._prefetched:
                continue
            self._prefetched[key] = self._upload_candidates(rows, cap_b)
            self.prefetch_staged += 1
            while len(self._prefetched) > 2:    # double buffer: 2 slots
                self._prefetched.pop(next(iter(self._prefetched)))

    # ------------------------------------------------------------- serving
    def search_batch(
        self,
        queries: np.ndarray,
        k: Optional[int] = None,
        nprobe: Optional[int] = None,
        probes: Optional[np.ndarray] = None,
        dead_rows: Optional[np.ndarray] = None,
    ) -> SearchResult:
        """Top-K for one batch through the device-resident pipeline.

        ``dead_rows`` applies the segmented data plane's tombstones (see
        :meth:`_gather_rows`); the τ prewarm excludes the same rows so
        pruning stays exact over the live set."""
        k = k or self.k
        queries = np.asarray(queries, np.float32)
        if queries.ndim == 1:
            queries = queries[None]
        nq = queries.shape[0]
        max_qb = self.qb_buckets[-1]
        if nq > max_qb:
            # batch exceeds the biggest bucket: split, serve, merge
            parts = [
                self.search_batch(
                    queries[lo : lo + max_qb], k=k, nprobe=nprobe,
                    probes=None if probes is None else probes[lo : lo + max_qb],
                    dead_rows=dead_rows,
                )
                for lo in range(0, nq, max_qb)
            ]
            return SearchResult(
                ids=np.concatenate([p.ids for p in parts]),
                scores=np.concatenate([p.scores for p in parts]),
                stats={
                    "backend": "spmd",
                    "wall_s": sum(p.stats["wall_s"] for p in parts),
                    "buckets": [b for p in parts for b in p.stats["buckets"]],
                    "tile_skipped": sum(p.stats["tile_skipped"] for p in parts),
                    "tile_total": sum(p.stats["tile_total"] for p in parts),
                    "pad_queries": sum(p.stats["pad_queries"] for p in parts),
                    "compiled": any(p.stats["compiled"] for p in parts),
                    "splits": len(parts),
                    "precision": self.precision,
                    "rerank_k": max(p.stats.get("rerank_k", 0) for p in parts),
                    "cold": max(p.stats.get("cold", 0) for p in parts),
                    "bytes_streamed": sum(p.stats.get("bytes_streamed", 0)
                                          for p in parts),
                    "prefetch_hits": sum(p.stats.get("prefetch_hits", 0)
                                         for p in parts),
                },
            )

        t0 = time.perf_counter()
        if probes is None:
            if nprobe is not None and nprobe <= 0:
                # assign_queries treats 0 as "use the config default"; an
                # explicit empty probe set means "no candidates"
                probes = np.zeros((nq, 0), np.int32)
            else:
                probes = assign_queries(self.index, queries, nprobe)
        rows, cap_b = self._gather_rows(probes, dead_rows)
        if cap_b == 0:
            dt = time.perf_counter() - t0
            self.dispatches += 1
            self.queries += nq
            self.wall_s += dt
            return SearchResult(
                ids=np.full((nq, k), -1, np.int64),
                scores=np.full((nq, k), np.inf, np.float32),
                stats={
                    "backend": "spmd", "wall_s": dt, "buckets": [],
                    "tile_skipped": 0, "tile_total": 0, "pad_queries": 0,
                    "compiled": False, "splits": 1,
                    "precision": self.precision, "rerank_k": 0,
                    "cold": int(self.tier == "host"),
                    "bytes_streamed": 0, "prefetch_hits": 0,
                },
            )
        int8 = self.precision == "int8"
        # τ prewarm runs over the *original* probe table: prewarm_tau
        # indexes per-cluster sample rows, so pad columns (-2) must never
        # reach it. int8 stage 1 scores in the quantized metric, where an
        # fp32-space τ seed is not a valid upper bound — start at +inf and
        # let the travelling τ tighten within the quantized metric instead.
        tau0 = (
            prewarm_tau(self.index, queries, probes, k,
                        self.index.cfg.prewarm_samples, self.metric,
                        dead_rows=dead_rows)
            if self.prune and not int8
            else np.full((nq,), np.inf, np.float32)
        )
        # compile-cache alignment: the step keys on probes.shape[1]; pad a
        # narrower probe table (-2 columns match no cluster) up to the
        # smallest already-compiled width so explicit-probe dispatches hit
        # warmed steps instead of recompiling per width.
        w = probes.shape[1]
        if w not in self._probe_widths:
            wider = sorted(pw for pw in self._probe_widths if pw > w)
            if wider:
                pad = np.full((nq, wider[0] - w), -2, np.int32)
                probes = np.concatenate([probes.astype(np.int32), pad], axis=1)
        k_step = min(k * self.cfg.rerank_factor, self.index.nb) if int8 else k
        qb_b = self._pick_bucket(self.qb_buckets, nq)
        bscfg = self._bucket_cfg(qb_b, cap_b, k_step, probes.shape[1])
        qarr = build_query_arrays(queries, bscfg, probes, tau0,
                                  quant_grid=self._quant_grid)
        compiles_before = self.compiles
        step = self._get_step(bscfg)
        cold_bytes, pf_hit = 0, 0
        if self.tier == "host":
            pkey = (rows.tobytes(), cap_b)
            staged = self._prefetched.pop(pkey, None)
            if staged is not None:
                cand, cold_bytes = staged
                pf_hit = 1
                self.prefetch_hits += 1
            else:
                cand, cold_bytes = self._upload_candidates(rows, cap_b)
                self.prefetch_misses += 1
            self.cold_dispatches += 1
            self.bytes_streamed += cold_bytes
            gs, gi, st = step(
                *cand, qarr["queries"], qarr["probes"], qarr["tau0"],
            )
        else:
            gs, gi, st = step(
                *self._resident, rows,
                qarr["queries"], qarr["probes"], qarr["tau0"],
            )
        scores = np.asarray(gs)[:nq]
        ids = np.asarray(gi)[:nq].astype(np.int64)
        ids[~np.isfinite(scores)] = -1
        if int8:
            scores, ids = self._rerank(queries, scores, ids, k)
        st = np.asarray(st)
        dt = time.perf_counter() - t0
        self.dispatches += 1
        self.queries += nq
        self.wall_s += dt
        self.tile_skipped += int(st[0])
        self.tile_total += int(st[1])
        return SearchResult(
            ids=ids,
            scores=scores,
            stats={
                "backend": "spmd",
                "wall_s": dt,
                "buckets": [(qb_b, cap_b)],
                "tile_skipped": int(st[0]),
                "tile_total": int(st[1]),
                "pad_queries": qb_b - nq,
                "compiled": self.compiles > compiles_before,
                "splits": 1,
                "precision": self.precision,
                "rerank_k": k_step if int8 else 0,
                "cold": int(self.tier == "host"),
                "bytes_streamed": cold_bytes,
                "prefetch_hits": pf_hit,
            },
        )

    # -------------------------------------------------------------- rerank
    def _rerank(self, queries: np.ndarray, s1_scores: np.ndarray,
                s1_ids: np.ndarray, k: int):
        """Exact fp32 re-rank of int8 stage-1 survivors.

        Stage 1 returns the quantized-metric top ``K' = k·rerank_factor``
        external ids; this gathers their original fp32 vectors and returns
        the *exact* L2 top-k of that survivor set — identical scores to
        the fp32 path whenever the true top-k survive stage 1."""
        nq, kp = s1_ids.shape
        if self._id_order is None:
            self._id_order = np.argsort(self.index.ids, kind="stable")
            self._sorted_ids = self.index.ids[self._id_order]
        valid = np.isfinite(s1_scores) & (s1_ids >= 0)
        safe = np.where(valid, s1_ids, self._sorted_ids[0])
        pos = np.searchsorted(self._sorted_ids, safe)
        rows = self._id_order[np.clip(pos, 0, self.index.nb - 1)]
        xg = self.index.x[rows]                      # [nq, kp, D] fp32 gather
        d = (
            np.sum(queries * queries, axis=1)[:, None]
            - 2.0 * np.einsum("md,mkd->mk", queries, xg)
            + self.index.xnorm2[rows]
        ).astype(np.float32)
        d = np.where(valid, d, np.inf)
        if kp > k:
            sel = np.argpartition(d, kth=k - 1, axis=1)[:, :k]
        else:
            sel = np.broadcast_to(np.arange(kp)[None, :], (nq, kp))
        sc = np.take_along_axis(d, sel, axis=1)
        order = np.argsort(sc, axis=1, kind="stable")
        sel = np.take_along_axis(sel, order, axis=1)
        sc = np.take_along_axis(sc, order, axis=1)
        out_ids = np.take_along_axis(s1_ids, sel, axis=1)
        out_ids[~np.isfinite(sc)] = -1
        if sc.shape[1] < k:                          # tiny corpus: pad to k
            pad = k - sc.shape[1]
            sc = np.pad(sc, ((0, 0), (0, pad)), constant_values=np.inf)
            out_ids = np.pad(out_ids, ((0, 0), (0, pad)), constant_values=-1)
        return sc, out_ids

    # ----------------------------------------------------------- reporting
    @property
    def compiles(self) -> int:
        return sum(self.trace_counts.values())

    def stats_summary(self) -> dict:
        """JSON-friendly digest (the benchmark harness folds this into the
        serving results blob)."""
        return {
            "precision": self.precision,
            "tier": self.tier,
            "cold_dispatches": self.cold_dispatches,
            "bytes_streamed": self.bytes_streamed,
            "prefetch_hits": self.prefetch_hits,
            "prefetch_misses": self.prefetch_misses,
            "prefetch_staged": self.prefetch_staged,
            "dispatches": self.dispatches,
            "queries": self.queries,
            "wall_s": self.wall_s,
            "compiles": self.compiles,
            "buckets_compiled": {
                f"qb{qb}_cap{cap}_k{k}_p{p}": n
                for (qb, cap, k, p), n in sorted(self.trace_counts.items())
            },
            "qb_buckets": list(self.qb_buckets),
            "cap_buckets": list(self.cap_buckets),
            "tile_skipped": self.tile_skipped,
            "tile_total": self.tile_total,
            "tile_skip_frac": self.tile_skipped / max(self.tile_total, 1),
        }
