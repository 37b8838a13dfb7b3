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
  and row ids are packed once (:func:`repro.core.pipeline.build_corpus_arrays`),
  each dimension block at a multiple of the distance kernel's ``tile_k``
  columns, and ``device_put`` on the mesh at construction. Serving a batch moves
  only the query block, probe table, τ seeds, and a small chunk table
  host→device; the corpus never re-crosses the PCIe/ICI boundary.
* **Cold tier** (``tier="host"``) — for host-resident (demoted) segments
  nothing stays on the mesh: per batch, only the probed clusters' rows
  are gathered host-side (:func:`repro.core.pipeline.gather_host_candidates`)
  into the same static (qb, cap) bucket shapes and streamed up through a
  double-buffered async upload — :meth:`SpmdExecutor.prefetch` stages
  batch i+1's transfer while batch i's ring kernels run. int8 codes
  stream 4× less PCIe traffic than fp32 rows, and the fp32 re-rank reads
  host memory anyway, so the cold tier prefers the PR 6 quantized path.
  Results are bit-identical to ``tier="device"``: the rows of the same
  chunk table, slot for slot, the same kernels, the same bucket ladder.
* **Chunk table** — probed clusters are contiguous row ranges of the
  resident shards (the IVF pack is cluster-sorted), so the host cuts the
  probed ranges into ``chunk``-row windows of the resident shard
  (:class:`ChunkTable`: each window's first row and its live rows) and
  the ring reads those windows in place, one per pass, for as many
  passes as the table has chunks — no per-batch copy of the candidates.
* **Static-shape bucketing** — jit recompiles per shape, and the
  scheduler's adaptive batches vary in both query count and candidate
  volume. Both are padded up a small ladder of (qb, cap) buckets; the
  compiled step for each bucket is cached, so replaying mixed batch sizes
  compiles each bucket exactly once. Batches larger than the biggest qb
  bucket are split and merged host-side.

Exactness: identical guarantees to the host engine and the oracle —
each live row of a probed list lies in exactly one chunk's live rows; a
row that is not live, or whose cluster the query did not probe, starts
at +inf, and padded queries carry τ = -inf (everything prunes), so
neither can enter a top-K.
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
from numpy.lib.stride_tricks import sliding_window_view

from repro.compat import shard_map_compat
from repro.core.index import (
    IVFIndex,
    assign_queries,
    padded_block_width,
    preassign,
)
from repro.core.pipeline import (
    SpmdConfig,
    build_corpus_arrays,
    build_query_arrays,
    corpus_shardings,
    gather_host_candidates,
    identity_chunk_table,
    ring_chunk_search,
)
from repro.core.pruning import prewarm_tau
from repro.core.router import load_aware_assignment, ring_offsets
from repro.core.types import PartitionPlan, SearchResult
from repro.serve import spans


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


@dataclass(frozen=True)
class ChunkTable:
    """The rows one batch scans, per shard, as ``chunk``-row windows of
    the resident shard: ``starts[v, c]`` is window c's first row,
    ``live[v, c]`` marks the rows in it to score (inside a probed list,
    not dead, and not owned by another window). Every shard runs
    ``n_iter`` windows (the most any shard needs); the table is padded
    to ``n_b`` windows on the cap ladder, and windows past a shard's own
    are all dead."""

    starts: np.ndarray      # [V, n_b] int32
    live: np.ndarray        # [V, n_b, chunk] bool
    n_iter: int
    rows: int               # live rows of the table (probed, not dead)

    @property
    def cap(self) -> int:
        """Rows of the table's bucket (n_b × chunk)."""
        return self.live.shape[1] * self.live.shape[2]

    @property
    def chunks(self) -> int:
        """Chunks the step scans, summed over the shards."""
        return self.live.shape[0] * self.n_iter

    def row_table(self) -> np.ndarray:
        """[V, cap] resident row of each table slot, -1 where not live —
        the host tier's gather list."""
        rows = self.starts[:, :, None] + np.arange(self.live.shape[2],
                                                   dtype=np.int32)
        return np.where(self.live, rows, -1).reshape(self.live.shape[0], -1)


def _pass_stats(passes: int, slots: int) -> dict:
    """The top-K merge's pass counters and the share of slots it ran."""
    return {"topk_passes": passes, "topk_pass_slots": slots,
            "topk_pass_frac": passes / max(slots, 1)}


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
        # for the chunk table: each list's (shard, first row, end row) in
        # the shard layout, and views [V, row, chunk] of the cluster and
        # the packed row (-1: padding) of the chunk starting at each row
        self._lists = np.asarray(
            [self.corpus.cluster_slices[c] for c in range(index.nlist)],
            np.int64).reshape(-1, 3).T
        packed_of = np.full((V, self.cap_full), -1, np.int32)
        packed_of[self.corpus.packed_shard, self.corpus.packed_row] = (
            np.arange(index.nb, dtype=np.int32))
        self._cluster_windows, self._packed_windows = (
            sliding_window_view(a, self.cfg.chunk, axis=1)
            for a in (self.corpus.cluster_shard, packed_of))
        # each dimension block is packed once at a multiple of the
        # kernel's contraction tile, so no chunk is padded in the step
        dim_pad = B * padded_block_width(index.dim, B, self.cfg.tile_k)
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
        # chunk starts are aligned down to the corpus dtype's row tile on
        # TPU (8 rows of 32-bit data, 32 of int8), so a chunk read in
        # place never cuts a packed tile
        self.row_align = 32 // arrays["x_blocks"].dtype.itemsize
        assert self.cfg.chunk % self.row_align == 0, (self.cfg.chunk,
                                                      self.row_align)
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
            # the scheduler's formed-batch lookahead, keyed on the chunk
            # table's rows so the later dispatch recognizes its own. Two
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
        # top-K insertion passes run, and the most the chunks could take
        # (chunks × query tiles × K): their ratio is the merge's work share
        self.topk_passes = 0
        self.topk_pass_slots = 0
        # live rows of the chunk tables, and the rows of the chunks the
        # step scans: their ratio is how full the chunks are
        self.rows_gathered = 0
        self.rows_scanned = 0
        self.chunks_scanned = 0
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
        V, chunk = bscfg.v_shards, bscfg.chunk
        live = np.zeros((V, cap // chunk, chunk), bool)
        live[:, 0, 0] = True
        table = ChunkTable(np.zeros((V, cap // chunk), np.int32), live, 1, V)
        qarr = build_query_arrays(
            np.zeros((1, self.index.dim), np.float32), bscfg,
            np.zeros((1, nprobe), np.int32),
            np.full((1,), np.inf, np.float32),
            quant_grid=self._quant_grid,
        )
        return (*self._table_args(table)[0],
                qarr["queries"], qarr["probes"], qarr["tau0"])

    # ----------------------------------------------------------- bucketing
    def _pick_bucket(self, ladder: Tuple[int, ...], need: int) -> int:
        for b in ladder:
            if b >= need:
                return b
        return ladder[-1]

    def _chunk_table(self, probes: np.ndarray,
                     dead_rows: Optional[np.ndarray] = None
                     ) -> Optional[ChunkTable]:
        """The batch's :class:`ChunkTable`; None when the batch probes no
        live resident row.

        The probed lists of each shard, in packed order, merge into runs
        where less than a chunk separates them; each run is cut into
        ``chunk``-row windows from its first row aligned down to
        ``row_align``; a window that would run past the shard's padded
        rows reads from ``cap_full - chunk`` instead. A window owns the
        rows from its nominal start on, so no row is scored twice, and
        windows number at most ``cap_full / chunk`` a shard: the top of
        the cap ladder holds any batch. A row is live where its cluster
        is probed and it is not in ``dead_rows`` (bool [NB] over *packed*
        index rows: the data plane's tombstones, or a filter's excluded
        rows). Dead rows never inflate K and leave the compiled step as
        it is; they do not split a run, and only a window with no live
        row is dropped, so scattered dead rows still cost their windows'
        device work."""
        V, C = self._base_scfg.v_shards, self.cfg.chunk
        uniq = np.unique(probes) if probes.size else np.zeros(0, np.int64)
        uniq = uniq[uniq >= 0]
        lv, lo, hi = self._lists[:, uniq]
        order = np.lexsort((lo, lv))
        order = order[hi[order] > lo[order]]
        lv, lo, hi = lv[order], lo[order], hi[order]
        if lo.size == 0:
            return None
        # runs: lists of one shard closer than a chunk share windows
        new = np.ones(lo.size, bool)
        new[1:] = (lv[1:] != lv[:-1]) | (lo[1:] - hi[:-1] >= C)
        r_at = np.flatnonzero(new)
        r_a = lo[r_at] - lo[r_at] % self.row_align
        r_n = -(-(np.maximum.reduceat(hi, r_at) - r_a) // C)
        # windows: the nominal start (the rows it owns begin there) and
        # the start it reads from, which stays inside the shard
        c_run = np.repeat(np.arange(r_at.size), r_n)
        c_nom = r_a[c_run] + C * (np.arange(c_run.size)
                                  - (np.cumsum(r_n) - r_n)[c_run])
        c_start = np.minimum(c_nom, self.cap_full - C)
        c_v = lv[r_at][c_run]
        probed = np.zeros(self.index.nlist + 1, bool)    # [-1]: padding
        probed[uniq] = True
        c_live = np.take(probed, self._cluster_windows[c_v, c_start])
        for w in np.flatnonzero(c_start < c_nom):       # pulled back
            c_live[w, : c_nom[w] - c_start[w]] = False
        if dead_rows is not None:
            dead = np.append(dead_rows, False)            # [-1]: padding
            c_live &= ~np.take(dead, self._packed_windows[c_v, c_start])
            keep = c_live.any(axis=1)
            c_v, c_start, c_live = c_v[keep], c_start[keep], c_live[keep]
        live_rows = int(np.count_nonzero(c_live))
        if live_rows == 0:
            return None
        per_shard = np.bincount(c_v, minlength=V)
        c_k = np.arange(c_v.size) - (np.cumsum(per_shard) - per_shard)[c_v]
        n_iter = int(per_shard.max())
        n_b = self._pick_bucket(self.cap_buckets, n_iter * C) // C
        starts = np.zeros((V, n_b), np.int32)
        starts[c_v, c_k] = c_start
        live = np.zeros((V, n_b, C), bool)
        live[c_v, c_k] = c_live
        return ChunkTable(starts, live, n_iter, live_rows)

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
                scale2, starts, live, n_iter, q_blk, probes, tau0 = rest
            else:
                scale2, (starts, live, n_iter, q_blk, probes, tau0) = None, rest
            n_b = bscfg.n_chunks
            return ring_chunk_search(
                bscfg, x_res.reshape(cap_full, db), xn2_res.reshape(cap_full),
                cl_res.reshape(cap_full), id_res.reshape(cap_full),
                q_blk.reshape(bscfg.qb, db), probes, tau0,
                starts.reshape(n_b), live.reshape(n_b, bscfg.chunk), n_iter,
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
            P(ad, None),            # starts (per-batch chunk table)
            P(ad, None, None),      # live
            P(),                    # n_iter
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
        (host-side, :func:`gather_host_candidates`, slot for slot as the
        chunk table lays them out) and streamed to the mesh, so the device
        body scans them through the identity chunk table with the
        identical ring kernels over the same (qb, cap) bucket shapes —
        one compile cache, bit-identical results to the resident path."""
        db, counts = bscfg.db, self.trace_counts
        int8 = self.precision == "int8"

        def device_fn(x_c, xn2_c, cl_c, id_c, *rest):
            counts[key] = counts.get(key, 0) + 1
            if int8:
                scale2, n_iter, q_blk, probes, tau0 = rest
            else:
                scale2, (n_iter, q_blk, probes, tau0) = None, rest
            cl_c = cl_c.reshape(bscfg.cap)
            starts, live = identity_chunk_table(cl_c, bscfg.chunk)
            return ring_chunk_search(
                bscfg, x_c.reshape(bscfg.cap, db), xn2_c.reshape(bscfg.cap),
                cl_c, id_c.reshape(bscfg.cap), q_blk.reshape(bscfg.qb, db),
                probes, tau0, starts, live, n_iter, scale2=scale2,
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
            P(),                    # n_iter
            P(None, am),            # queries
            P(None, None),          # probes
            P(None),                # tau0
        )
        fn = shard_map_compat(
            device_fn, mesh=self.mesh, in_specs=in_specs,
            out_specs=(P(), P(), P()),
        )
        return jax.jit(fn)

    def _table_args(self, table: ChunkTable):
        """The step's candidate operands for ``table``: the resident
        arrays and the table, or (host tier) the streamed candidates.
        Returns ``(operands, streamed bytes, prefetch hit)``."""
        n_iter = np.int32(table.n_iter)
        if self.tier != "host":
            return (*self._resident, table.starts, table.live, n_iter), 0, 0
        rows = table.row_table()
        staged = self._prefetched.pop((rows.tobytes(), table.cap), None)
        hit = int(staged is not None)
        cand, nbytes = staged if hit else self._upload_candidates(rows)
        return (*cand, n_iter), nbytes, hit

    # ---------------------------------------------------- cold-tier stream
    def _upload_candidates(self, rows: np.ndarray):
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

        The staged upload is keyed on the chunk table's rows, so the
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
            table = self._chunk_table(probes[lo:lo + max_qb], dead_rows)
            if table is None:
                continue
            rows = table.row_table()
            key = (rows.tobytes(), table.cap)
            if key in self._prefetched:
                continue
            self._prefetched[key] = self._upload_candidates(rows)
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
        :meth:`_chunk_table`); the τ prewarm excludes the same rows so
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
                    **_pass_stats(sum(p.stats["topk_passes"] for p in parts),
                                  sum(p.stats["topk_pass_slots"]
                                      for p in parts)),
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

        with spans.span("executor") as span:
            return self._search_bucket(queries, k, nprobe, probes, dead_rows,
                                       span)

    def _search_bucket(self, queries, k, nprobe, probes, dead_rows,
                       span) -> SearchResult:
        """One dispatch of at most the largest qb bucket, inside the
        ``executor`` span; ``span`` gets the bucket, the live rows and
        the chunks the step scans."""
        nq = queries.shape[0]
        t0 = time.perf_counter()
        if probes is None:
            if nprobe is not None and nprobe <= 0:
                # assign_queries treats 0 as "use the config default"; an
                # explicit empty probe set means "no candidates"
                probes = np.zeros((nq, 0), np.int32)
            else:
                probes = assign_queries(self.index, queries, nprobe)
        with spans.span("executor.gather_table"):
            table = self._chunk_table(probes, dead_rows)
        if table is None:
            dt = time.perf_counter() - t0
            self.dispatches += 1
            self.queries += nq
            self.wall_s += dt
            return SearchResult(
                ids=np.full((nq, k), -1, np.int64),
                scores=np.full((nq, k), np.inf, np.float32),
                stats={
                    "backend": "spmd", "wall_s": dt, "buckets": [],
                    "tile_skipped": 0, "tile_total": 0, **_pass_stats(0, 0),
                    "pad_queries": 0,
                    "compiled": False, "splits": 1,
                    "precision": self.precision, "rerank_k": 0,
                    "cold": int(self.tier == "host"),
                    "bytes_streamed": 0, "prefetch_hits": 0,
                },
            )
        qb_b = self._pick_bucket(self.qb_buckets, nq)
        cap_b = table.cap
        span.set_metadata(qb=qb_b, cap=cap_b, rows=table.rows,
                          chunks=table.chunks)
        int8 = self.precision == "int8"
        # τ prewarm runs over the *original* probe table: prewarm_tau
        # indexes per-cluster sample rows, so pad columns (-2) must never
        # reach it. int8 stage 1 scores in the quantized metric, where an
        # fp32-space τ seed is not a valid upper bound — start at +inf and
        # let the travelling τ tighten within the quantized metric instead.
        if self.prune and not int8:
            with spans.span("executor.prewarm"):
                tau0 = prewarm_tau(self.index, queries, probes, k,
                                   self.index.cfg.prewarm_samples,
                                   self.metric, dead_rows=dead_rows)
        else:
            tau0 = np.full((nq,), np.inf, np.float32)
        k_step = min(k * self.cfg.rerank_factor, self.index.nb) if int8 else k
        compiles_before = self.compiles
        with spans.span("executor.launch"):
            # compile-cache alignment: the step keys on probes.shape[1];
            # pad a narrower probe table (-2 columns match no cluster) up
            # to the smallest already-compiled width so explicit-probe
            # dispatches hit warmed steps instead of recompiling per width.
            w = probes.shape[1]
            if w not in self._probe_widths:
                wider = sorted(pw for pw in self._probe_widths if pw > w)
                if wider:
                    pad = np.full((nq, wider[0] - w), -2, np.int32)
                    probes = np.concatenate([probes.astype(np.int32), pad],
                                            axis=1)
            bscfg = self._bucket_cfg(qb_b, cap_b, k_step, probes.shape[1])
            qarr = build_query_arrays(queries, bscfg, probes, tau0,
                                      quant_grid=self._quant_grid)
            step = self._get_step(bscfg)
            cand, cold_bytes, pf_hit = self._table_args(table)
            if self.tier == "host":
                self.prefetch_hits += pf_hit
                self.prefetch_misses += 1 - pf_hit
                self.cold_dispatches += 1
                self.bytes_streamed += cold_bytes
            gs, gi, st = step(
                *cand, qarr["queries"], qarr["probes"], qarr["tau0"],
            )
        with spans.span("executor.wait"):
            scores = np.asarray(gs)[:nq]
            ids = np.asarray(gi)[:nq].astype(np.int64)
            st = np.asarray(st)
        ids[~np.isfinite(scores)] = -1
        if int8:
            with spans.span("executor.rerank"):
                scores, ids = self._rerank(queries, scores, ids, k)
        dt = time.perf_counter() - t0
        self.dispatches += 1
        self.queries += nq
        self.wall_s += dt
        self.tile_skipped += int(st[0])
        self.tile_total += int(st[1])
        self.topk_passes += int(st[2])
        self.topk_pass_slots += int(st[3])
        self.rows_gathered += table.rows
        self.chunks_scanned += table.chunks
        self.rows_scanned += table.chunks * self.cfg.chunk
        return SearchResult(
            ids=ids,
            scores=scores,
            stats={
                "backend": "spmd",
                "wall_s": dt,
                "buckets": [(qb_b, cap_b)],
                "tile_skipped": int(st[0]),
                "tile_total": int(st[1]),
                **_pass_stats(int(st[2]), int(st[3])),
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
            "dim": self.index.dim,
            "dim_padded": self._base_scfg.db,
            "contraction_steps": self._base_scfg.db // self.cfg.tile_k,
            "qb_buckets": list(self.qb_buckets),
            "cap_buckets": list(self.cap_buckets),
            "tile_skipped": self.tile_skipped,
            "tile_total": self.tile_total,
            "tile_skip_frac": self.tile_skipped / max(self.tile_total, 1),
            **_pass_stats(self.topk_passes, self.topk_pass_slots),
            "rows_gathered": self.rows_gathered,
            "rows_scanned": self.rows_scanned,
            "chunks_scanned": self.chunks_scanned,
        }
