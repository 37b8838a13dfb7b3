"""Flexible pipelined execution engine — TPU-target SPMD path (§4.3).

The paper's MPI pipeline (Fig. 5(b)) maps onto the device mesh as a
**dimension ring**: the mesh is (``pod`` ×) ``data`` × ``model``; device
(v, b) owns dimension block b of vector shard v. Query groups' partial
accumulators rotate around the ``model`` axis with ``lax.ppermute`` — at
ring stage t, device (v, b) scores dimension block b for query group
(b − t − offset_v) mod B, adds into the received accumulator, prunes
against the group's travelling τ, and forwards. After B stages every
group has visited every dimension block. ``offset_v`` staggers ring
starts across shards (the paper's load-aware deferred-block schedule).

Billion-scale feasibility: a shard's rows are streamed in chunks read in
place through a chunk table (``lax.fori_loop`` over the table's live
chunks), each chunk running one full dimension ring; a per-group
running top-K (and its τ = kth best) tightens between chunks — the
vector-level pipeline of Fig. 5(a). Accumulator memory is O(QG × chunk),
not O(QG × cap).

Exactness: identical guarantees to the host engine — pruning uses monotone
partial sums against a valid upper bound τ; results equal the oracle's
top-k over probed clusters.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map_compat
from repro.core.index import (
    IVFIndex,
    ShardedCorpus,
    dim_block_bounds,
    encode_blocks,
    pack_dim_blocks,
)
from repro.kernels import ops as kops


@dataclass(frozen=True)
class SpmdConfig:
    """Static geometry of the SPMD search step."""

    v_shards: int          # data-axis size (vector shards per pod)
    d_blocks: int          # model-axis size (dimension blocks)
    n_pods: int = 1        # pod-axis size (corpus super-shards)
    qb: int = 64           # queries per step (per pod; replicated over pods)
    cap: int = 1024        # padded rows per shard
    dim: int = 128         # d_blocks * db; each block padded to db columns
    nprobe: int = 8
    k: int = 10
    chunk: int = 512       # candidate rows scored per ring pass
    metric: str = "l2"
    prune: bool = True
    x_dtype: str = "float32"    # bf16 halves corpus HBM traffic (accum stays f32)
    precision: str = "fp32"     # "int8" → quantized stage-1 scoring tier
    use_pallas: bool = True     # False → pure-jnp scoring (dry-run / CPU bench)
    tile_m: int = 128
    tile_n: int = 128
    tile_k: int = 128
    axis_pod: str = "pod"
    axis_data: str = "data"
    axis_model: str = "model"

    @property
    def qg(self) -> int:
        assert self.qb % self.d_blocks == 0, (self.qb, self.d_blocks)
        return self.qb // self.d_blocks

    @property
    def db(self) -> int:
        assert self.dim % self.d_blocks == 0, (self.dim, self.d_blocks)
        return self.dim // self.d_blocks

    @property
    def n_chunks(self) -> int:
        assert self.cap % self.chunk == 0, (self.cap, self.chunk)
        return self.cap // self.chunk

    def __post_init__(self):
        assert self.precision in ("fp32", "int8"), self.precision
        if self.precision == "int8":
            # the shared-grid quantized difference form is L2-only
            assert self.metric == "l2", (self.precision, self.metric)


# ---------------------------------------------------------------------------
# Host-side input packaging
# ---------------------------------------------------------------------------


def build_corpus_arrays(corpus: ShardedCorpus, scfg: SpmdConfig,
                        quant: Optional["Int8Quant"] = None):
    """Pack the sharded corpus into the step's device-resident arrays.

    These are the batch-invariant inputs — the serving executor uploads
    them to the mesh ONCE and reuses them across every served batch.

    Shapes (global, to be sharded by the step's in_shardings):
      x_blocks   [V, cap, D_pad]      f32 | int8 codes  (rows→data, dims→model)
      xn2_blocks [B, V, cap]          f32   (block norms; B→model, V→data)
      cluster_ids[V, cap]             i32
      row_ids    [V, cap]             i32
      scale2     [B]                  f32   (int8 only: s² per dim block)

    With ``precision="int8"`` the resident corpus is the 1-byte codes of a
    per-dimension-block affine grid (4× smaller than fp32), ``xn2_blocks``
    carries the pre-scaled s²·Σcode² norms, and the grid's (scale, zero)
    come from ``quant`` — the segment's seal-time :class:`Int8Quant` —
    when its blocking matches this mesh, else are fit to this layout.
    Each dimension block is laid out once here at ``scfg.db`` columns
    (:func:`repro.core.index.pack_dim_blocks`): the block's true columns,
    then zeros — code 0 for int8, as for queries — so padding contributes
    exactly 0 to every norm and product, and a ``db`` that is a multiple of
    the kernel's ``tile_k`` leaves the kernel nothing to pad per chunk.
    """
    V, B = scfg.v_shards, scfg.d_blocks
    cap, D = scfg.cap, scfg.dim
    assert corpus.plan.v_shards == V
    xs = corpus.x_shard
    assert xs.shape[1] <= cap, (xs.shape, cap)

    cluster_ids = np.full((V, cap), -1, np.int32)
    cluster_ids[:, : xs.shape[1]] = corpus.cluster_shard
    row_ids = np.full((V, cap), -1, np.int32)
    row_ids[:, : xs.shape[1]] = corpus.ids_shard.astype(np.int32)

    if scfg.precision == "int8":
        scale, zero = _mesh_quant_grid(xs, corpus.valid, scfg, quant)
        true_codes = encode_blocks(xs, scale, zero)
        codes = pack_dim_blocks(np.zeros((V, cap, D), np.int8), true_codes, B)
        xn2_blocks = np.zeros((B, V, cap), np.float32)
        for b, (lo, hi) in enumerate(dim_block_bounds(xs.shape[2], B)):
            c32 = true_codes[:, :, lo:hi].astype(np.int32)
            xn2_blocks[b, :, : xs.shape[1]] = (scale[b] ** 2) * np.sum(c32 * c32, axis=2)
        return dict(
            x_blocks=codes,
            xn2_blocks=xn2_blocks,
            cluster_ids=cluster_ids,
            row_ids=row_ids,
            scale2=(scale.astype(np.float32) ** 2),
            # host-only: the grid queries must be encoded on (callers pop
            # this before uploading the dict to the mesh)
            quant_grid=(scale, zero),
        )

    import ml_dtypes

    xdt = np.float32 if scfg.x_dtype == "float32" else ml_dtypes.bfloat16
    x_blocks = pack_dim_blocks(np.zeros((V, cap, D), xdt), xs.astype(xdt, copy=False), B)

    xn2_blocks = np.zeros((B, V, cap), np.float32)
    if xdt is np.float32 and corpus.xnorm2_blk.shape[1] == B:
        # reuse the per-block norms preassign already materialized (over
        # the true columns; zero padding of rows or dims adds nothing)
        xn2_blocks[:, :, : xs.shape[1]] = np.moveaxis(corpus.xnorm2_blk, 0, 1)
    else:
        # dtype cast (or a different block split) changes the norms
        db = scfg.db
        for b in range(B):
            seg = x_blocks[:, :, b * db:(b + 1) * db]
            xn2_blocks[b] = np.sum(seg * seg, axis=2)
    return dict(
        x_blocks=x_blocks,
        xn2_blocks=xn2_blocks,
        cluster_ids=cluster_ids,
        row_ids=row_ids,
    )


def _mesh_quant_grid(xs: np.ndarray, valid: np.ndarray, scfg: SpmdConfig,
                     quant: Optional["Int8Quant"]):
    """(scale [B], zero [B]) for this mesh's dimension blocking.

    Reuses the seal-time grid when its per-block dim ranges coincide with
    the mesh blocking (``quant_blocks == d_blocks``); otherwise fits a
    fresh grid to the shard layout's valid rows, block by block over the
    true columns — a deterministic function of the corpus, so every
    replica derives identical codes."""
    B, D = scfg.d_blocks, xs.shape[2]
    if (quant is not None and quant.d_blocks == B
            and quant.codes.shape[1] == D):
        return quant.scale.copy(), quant.zero.copy()
    scale = np.ones(B, np.float32)
    zero = np.zeros(B, np.float32)
    rows = xs[valid[:, : xs.shape[1]]] if valid.size else xs.reshape(-1, D)
    for b, (lo, hi) in enumerate(dim_block_bounds(D, B)):
        blk = rows[:, lo:hi]
        mn = float(blk.min()) if blk.size else 0.0
        mx = float(blk.max()) if blk.size else 0.0
        zero[b] = 0.5 * (mn + mx)
        scale[b] = max((mx - mn) / 254.0, 1e-8)
    return scale, zero


def build_query_arrays(
    q: np.ndarray, scfg: SpmdConfig, probes: np.ndarray, tau0: np.ndarray,
    quant_grid: Optional[Tuple[np.ndarray, np.ndarray]] = None,
):
    """Pack one query batch into the step's per-batch arrays, padded to the
    static ``scfg.qb`` shape, each dimension block laid out at ``scfg.db``
    columns as the corpus is (:func:`build_corpus_arrays`).

      queries    [QB, D_pad]          f32 | int8 codes   (dims→model)
      probes     [QB, P]              i32   (replicated)
      tau0       [QB]                 f32   (replicated)

    With ``precision="int8"``, queries are encoded on the corpus's grid
    (``quant_grid`` = (scale [B], zero [B]) of the resident codes) —
    out-of-range query values clip; padded rows and dims are code 0, as
    in the corpus, so padding adds nothing to the quantized distance."""
    qb, D = scfg.qb, scfg.dim
    nq = min(q.shape[0], qb)
    q = np.asarray(q[:nq], np.float32)
    if scfg.precision == "int8":
        assert quant_grid is not None, "int8 queries need the corpus grid"
        queries = pack_dim_blocks(np.zeros((qb, D), np.int8),
                                  encode_blocks(q, *quant_grid), scfg.d_blocks)
    else:
        queries = pack_dim_blocks(np.zeros((qb, D), np.float32), q, scfg.d_blocks)
    probes_pad = np.zeros((qb, probes.shape[1]), np.int32)
    probes_pad[:nq] = probes[:nq]
    probes_pad[nq:] = -2                      # match nothing
    tau_pad = np.full((qb,), -np.inf, np.float32)
    tau_pad[:nq] = tau0[:nq]
    return dict(queries=queries, probes=probes_pad, tau0=tau_pad)


def build_spmd_inputs(
    index: IVFIndex, corpus: ShardedCorpus, q: np.ndarray, scfg: SpmdConfig,
    probes: np.ndarray, tau0: np.ndarray,
):
    """Corpus + query-batch packing in one call (one-shot example path)."""
    quant = (index.int8_quant(scfg.d_blocks)
             if scfg.precision == "int8" else None)
    corpus_arrays = build_corpus_arrays(corpus, scfg, quant=quant)
    grid = corpus_arrays.pop("quant_grid", None)
    return {
        **corpus_arrays,
        **build_query_arrays(q, scfg, probes, tau0, quant_grid=grid),
    }


def corpus_shardings(scfg: SpmdConfig, mesh: Mesh):
    """NamedShardings of the batch-invariant (device-resident) arrays."""
    ap = scfg.axis_pod if scfg.n_pods > 1 else None
    ad, am = scfg.axis_data, scfg.axis_model
    # the pod axis shards extra vector shards: x arrays carry a leading pod dim
    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    if scfg.n_pods > 1:
        out = dict(
            x_blocks=ns(ap, ad, None, am),
            xn2_blocks=ns(ap, am, ad, None),
            cluster_ids=ns(ap, ad, None),
            row_ids=ns(ap, ad, None),
        )
    else:
        out = dict(
            x_blocks=ns(ad, None, am),
            xn2_blocks=ns(am, ad, None),
            cluster_ids=ns(ad, None),
            row_ids=ns(ad, None),
        )
    if scfg.precision == "int8":
        out["scale2"] = ns(am)      # one s² per dimension block
    return out


def query_shardings(scfg: SpmdConfig, mesh: Mesh):
    """NamedShardings of the per-batch arrays."""
    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    return dict(
        queries=ns(None, scfg.axis_model),
        probes=ns(None, None),
        tau0=ns(None),
    )


def input_shardings(scfg: SpmdConfig, mesh: Mesh):
    return {**corpus_shardings(scfg, mesh), **query_shardings(scfg, mesh)}


def input_specs(scfg: SpmdConfig):
    """ShapeDtypeStructs for dry-run lowering (no allocation)."""
    V, B, cap, D = scfg.v_shards, scfg.d_blocks, scfg.cap, scfg.dim
    lead = (scfg.n_pods,) if scfg.n_pods > 1 else ()
    f32, i32 = jnp.float32, jnp.int32
    int8 = scfg.precision == "int8"
    xdt = jnp.int8 if int8 else jnp.dtype(scfg.x_dtype)
    out = dict(
        x_blocks=jax.ShapeDtypeStruct(lead + (V, cap, D), xdt),
        xn2_blocks=jax.ShapeDtypeStruct(lead + (B, V, cap), f32),
        cluster_ids=jax.ShapeDtypeStruct(lead + (V, cap), i32),
        row_ids=jax.ShapeDtypeStruct(lead + (V, cap), i32),
        queries=jax.ShapeDtypeStruct((scfg.qb, D), jnp.int8 if int8 else f32),
        probes=jax.ShapeDtypeStruct((scfg.qb, scfg.nprobe), i32),
        tau0=jax.ShapeDtypeStruct((scfg.qb,), f32),
    )
    if int8:
        out["scale2"] = jax.ShapeDtypeStruct((B,), f32)
    return out


# ---------------------------------------------------------------------------
# The SPMD step
# ---------------------------------------------------------------------------


def _score_chunk_update(scfg: SpmdConfig, x_c, xn2_c, qrows, qn2, acc, tau):
    """One (group, chunk, block) partial update — Pallas or jnp ref."""
    out, skip = kops.partial_distance_update(
        x_c, xn2_c, qrows, qn2, acc, tau,
        prune=scfg.prune, metric=scfg.metric,
        tile_m=scfg.tile_m, tile_n=scfg.tile_n, tile_k=scfg.tile_k,
        use_pallas=scfg.use_pallas,
    )
    return out, skip.sum(), skip.size


def _score_chunk_update_int8(scfg: SpmdConfig, x_c, xn2_c, qrows, qn2, s2,
                             acc, tau):
    """int8 variant: codes in, int32 MXU accumulation, f32 combine."""
    out, skip = kops.int8_partial_distance_update(
        x_c, xn2_c, qrows, qn2, s2, acc, tau,
        prune=scfg.prune,
        tile_m=scfg.tile_m, tile_n=scfg.tile_n, tile_k=scfg.tile_k,
        use_pallas=scfg.use_pallas,
    )
    return out, skip.sum(), skip.size


def identity_chunk_table(cluster_ids, chunk: int):
    """The chunk table of an already-packed candidate buffer: chunk c
    starts at row ``c * chunk``, and a row is live unless it is padding
    (cluster id -1). Returns ``(starts [n], live [n, chunk])``."""
    n = cluster_ids.shape[0] // chunk
    starts = jnp.arange(n, dtype=jnp.int32) * chunk
    return starts, (cluster_ids >= 0).reshape(n, chunk)


def gather_host_candidates(arrays: dict, rows: np.ndarray) -> dict:
    """Host-tier (cold) segments: gather the probed clusters' rows out of
    the host-resident packed corpus into per-batch candidate arrays
    ready to stream to the mesh.

    ``arrays`` is :func:`build_corpus_arrays`'s dict kept host-side
    (int8 codes stream 4× less PCIe traffic than fp32 rows — the cold
    tier's preferred precision); ``rows`` [V, cap_b] int32 indexes each
    shard's packed rows, -1 = pad. Pad slots re-read row 0 but get
    cluster id -1 and zero norms, so they match no probe and never enter
    a top-K.

    Returns ``dict(x_c [V, cap_b, D], xn2_c [B, V, cap_b],
    cl_c [V, cap_b], id_c [V, cap_b])`` with the same dtypes, block
    grids and axis layout the resident path uses, so the streamed step
    runs the identical ring kernels over them.
    """
    x_blocks, xn2_blocks = arrays["x_blocks"], arrays["xn2_blocks"]
    cl, rid = arrays["cluster_ids"], arrays["row_ids"]
    V = cl.shape[0]
    keep = rows >= 0
    safe = np.where(keep, rows, 0)
    vi = np.arange(V)[:, None]
    x_c = np.ascontiguousarray(x_blocks[vi, safe])
    xn2_c = np.where(keep[None], xn2_blocks[:, vi, safe], 0.0).astype(np.float32)
    cl_c = np.where(keep, cl[vi, safe], -1).astype(np.int32)
    id_c = np.where(keep, rid[vi, safe], -1).astype(np.int32)
    return dict(x_c=x_c, xn2_c=xn2_c, cl_c=cl_c, id_c=id_c)


def ring_chunk_search(scfg: SpmdConfig, x_blk, xn2_blk, cluster_ids, row_ids,
                      q_blk, probes, tau0, starts, live, n_iter, scale2=None):
    """Per-device ring search core (call under shard_map).

    Inputs are this device's local, already-squeezed arrays:
      x_blk [rows, db], xn2_blk [rows], cluster_ids/row_ids [rows],
      q_blk [qb, db], probes [qb, P], tau0 [qb],
    and the chunk table that says which of those rows to scan:
      starts [n_b] i32 (chunk c is rows ``starts[c] : starts[c] + chunk``),
      live [n_b, chunk] bool (the rows of chunk c to score), and
      n_iter, the chunks to run (≤ n_b; may be traced).
    The rows are read in place: a chunk is a slice of ``x_blk``, so the
    resident corpus is scanned without a per-batch copy, and chunks past
    ``n_iter`` cost nothing. A row scores where it is live and its
    cluster is among the query's probes; every other row stays +inf.
    Runs the chunked dimension-ring scan (Pallas partial-distance with
    tile-granular early-stop, ppermute rotation, running top-K with τ
    tightening between chunks) and merges results across the mesh axes.
    ``n_iter`` must agree across the mesh, so the ring's ppermutes stay
    in step.
    Returns replicated (scores [qb, K], ids [qb, K], stats [4]): the
    distance tiles skipped and scored, the top-K insertion passes run
    (``kops.topk_pass_counts``, summed) and their most (chunks × query
    tiles × K), each summed over the mesh.

    ``precision="int8"``: x_blk/q_blk carry int8 codes, xn2_blk the
    pre-scaled s²·Σcode² norms, and ``scale2`` this device's scalar s².
    The ring then computes *quantized* L2 — still monotone over dimension
    blocks, so the travelling-τ pruning and running top-K stay exact
    within the quantized metric (the fp32 re-rank happens host-side in
    the executor).
    """
    B, QG, K = scfg.d_blocks, scfg.qg, scfg.k
    chunk = scfg.chunk

    b_idx = jax.lax.axis_index(scfg.axis_model)
    v_idx = jax.lax.axis_index(scfg.axis_data)
    offset = v_idx % B
    g_home = (b_idx - offset) % B          # resident group of this device

    # per-group local state: this device accumulates results for g_home
    probes_home = jax.lax.dynamic_slice_in_dim(probes, g_home * QG, QG, 0)
    tau_home0 = jax.lax.dynamic_slice_in_dim(tau0, g_home * QG, QG, 0)

    run_scores0 = jnp.full((QG, K), jnp.inf, jnp.float32)
    run_ids0 = jnp.full((QG, K), -1, jnp.int32)

    perm = [(i, (i + 1) % B) for i in range(B)]

    def outer(c, carry):
        run_scores, run_ids, skip_cnt, tile_cnt, pass_cnt, slot_cnt = carry
        row0 = starts[c]
        x_c = jax.lax.dynamic_slice_in_dim(x_blk, row0, chunk, 0)
        xn2_c = jax.lax.dynamic_slice_in_dim(xn2_blk, row0, chunk, 0)
        cl_c = jax.lax.dynamic_slice_in_dim(cluster_ids, row0, chunk, 0)
        id_c = jax.lax.dynamic_slice_in_dim(row_ids, row0, chunk, 0)
        live_c = jax.lax.dynamic_index_in_dim(live, c, 0, keepdims=False)

        # init acc for home group: 0 where live and probed, +inf otherwise
        mask = ((probes_home[:, :, None] == cl_c[None, None, :]).any(axis=1)
                & live_c[None, :])
        tau_home = jnp.minimum(tau_home0, run_scores[:, -1])
        acc0 = jnp.where(mask, 0.0, jnp.inf).astype(jnp.float32)

        def ring(rc, t):
            acc, tau_g, sk, tc = rc
            g = (b_idx - t - offset) % B
            qrows = jax.lax.dynamic_slice_in_dim(q_blk, g * QG, QG, 0)
            if scfg.precision == "int8":
                s2 = scale2.reshape(())
                # int32 code norms are exact; one f32 scale at the end
                qn2 = s2 * jnp.sum(
                    qrows.astype(jnp.int32) ** 2, axis=1
                ).astype(jnp.float32)
                acc, s_cnt, t_cnt = _score_chunk_update_int8(
                    scfg, x_c, xn2_c, qrows, qn2, s2, acc, tau_g
                )
            else:
                qn2 = jnp.sum(qrows.astype(jnp.float32) ** 2, axis=1)
                acc, s_cnt, t_cnt = _score_chunk_update(
                    scfg, x_c, xn2_c, qrows, qn2, acc, tau_g
                )
            if B > 1:
                acc = jax.lax.ppermute(acc, scfg.axis_model, perm)
                tau_g = jax.lax.ppermute(tau_g, scfg.axis_model, perm)
            return (acc, tau_g, sk + s_cnt, tc + t_cnt), None

        (acc, _, skip_cnt, tile_cnt), _ = jax.lax.scan(
            ring, (acc0, tau_home, skip_cnt, tile_cnt), jnp.arange(B)
        )
        # after B stages (and B ppermutes) the accumulator is home again;
        # merge the chunk into the running top-K (fused VMEM-resident kernel
        # on the Pallas path, concat+sort on the jnp path). Both paths count
        # the kernel's insertion passes, so the counter reads the same.
        id_b = jnp.broadcast_to(id_c[None, :], acc.shape)
        passes = kops.topk_pass_counts(acc, run_scores, k=K)
        pass_cnt = pass_cnt + passes.sum()
        slot_cnt = slot_cnt + passes.size * K
        if scfg.use_pallas:
            run_scores, run_ids = kops.running_topk_update(
                acc, id_b, run_scores, run_ids, passes, k=K
            )
        else:
            cat_s = jnp.concatenate([run_scores, acc], axis=1)
            cat_i = jnp.concatenate([run_ids, id_b], axis=1)
            neg, pos = jax.lax.top_k(-cat_s, K)
            run_scores = -neg
            run_ids = jnp.take_along_axis(cat_i, pos, axis=1)
        return (run_scores, run_ids, skip_cnt, tile_cnt, pass_cnt,
                slot_cnt)

    zero = jnp.int32(0)
    run_scores, run_ids, skip_cnt, tile_cnt, pass_cnt, slot_cnt = (
        jax.lax.fori_loop(
            0, n_iter, outer,
            (run_scores0, run_ids0, zero, zero, zero, zero),
        )
    )

    # ---- gather groups across the model axis and restore group order
    gs = jax.lax.all_gather(run_scores, scfg.axis_model)   # [B, QG, K]
    gi = jax.lax.all_gather(run_ids, scfg.axis_model)
    src = (jnp.arange(B) + offset) % B                     # group g ← device g+offset
    gs = jnp.take(gs, src, axis=0).reshape(scfg.qb, K)
    gi = jnp.take(gi, src, axis=0).reshape(scfg.qb, K)

    # ---- merge across vector shards (data axis)
    if scfg.v_shards > 1:
        as_ = jax.lax.all_gather(gs, scfg.axis_data)       # [V, QB, K]
        ai = jax.lax.all_gather(gi, scfg.axis_data)
        as_ = jnp.moveaxis(as_, 0, 1).reshape(scfg.qb, -1)
        ai = jnp.moveaxis(ai, 0, 1).reshape(scfg.qb, -1)
        neg, pos = jax.lax.top_k(-as_, K)
        gs = -neg
        gi = jnp.take_along_axis(ai, pos, axis=1)

    # ---- merge across pods (corpus super-shards)
    if scfg.n_pods > 1:
        ps = jax.lax.all_gather(gs, scfg.axis_pod)
        pi = jax.lax.all_gather(gi, scfg.axis_pod)
        ps = jnp.moveaxis(ps, 0, 1).reshape(scfg.qb, -1)
        pi = jnp.moveaxis(pi, 0, 1).reshape(scfg.qb, -1)
        neg, pos = jax.lax.top_k(-ps, K)
        gs = -neg
        gi = jnp.take_along_axis(pi, pos, axis=1)

    stats = jax.lax.psum(
        jnp.stack([skip_cnt, tile_cnt, pass_cnt, slot_cnt]), scfg.axis_model
    )
    stats = jax.lax.psum(stats, scfg.axis_data)
    if scfg.n_pods > 1:
        stats = jax.lax.psum(stats, scfg.axis_pod)
    return gs, gi, stats


def make_device_fn(scfg: SpmdConfig):
    """The per-device body, to be wrapped in shard_map: squeeze the leading
    sharded axes and run the ring search core over the full resident shard
    (its identity chunk table: every chunk, in order)."""

    def device_fn(x_blk, xn2_blk, cluster_ids, row_ids, *rest):
        # shapes (per device):
        #   x_blk [1(,1), cap, db]  xn2_blk [1(,1)?, ...] — squeeze leading axes
        if scfg.precision == "int8":
            scale2, q_blk, probes, tau0 = rest
        else:
            scale2, (q_blk, probes, tau0) = None, rest
        x_blk = x_blk.reshape(scfg.cap, scfg.db)
        xn2_blk = xn2_blk.reshape(scfg.cap)
        cluster_ids = cluster_ids.reshape(scfg.cap)
        row_ids = row_ids.reshape(scfg.cap)
        q_blk = q_blk.reshape(scfg.qb, scfg.db)
        starts, live = identity_chunk_table(cluster_ids, scfg.chunk)
        return ring_chunk_search(
            scfg, x_blk, xn2_blk, cluster_ids, row_ids, q_blk, probes, tau0,
            starts, live, scfg.n_chunks, scale2=scale2,
        )

    return device_fn


def make_spmd_search(scfg: SpmdConfig, mesh: Mesh):
    """jit(shard_map(...)) search step over the mesh. Returns a callable
    (and the in_shardings dict for dry-run lowering)."""
    dev = make_device_fn(scfg)
    if scfg.n_pods > 1:
        corpus_specs = (
            P(scfg.axis_pod, scfg.axis_data, None, scfg.axis_model),
            P(scfg.axis_pod, scfg.axis_model, scfg.axis_data, None),
            P(scfg.axis_pod, scfg.axis_data, None),
            P(scfg.axis_pod, scfg.axis_data, None),
        )
    else:
        corpus_specs = (
            P(scfg.axis_data, None, scfg.axis_model),
            P(scfg.axis_model, scfg.axis_data, None),
            P(scfg.axis_data, None),
            P(scfg.axis_data, None),
        )
    if scfg.precision == "int8":
        corpus_specs = corpus_specs + (P(scfg.axis_model),)   # scale2 [B]
    in_specs = corpus_specs + (
        P(None, scfg.axis_model),
        P(None, None),
        P(None),
    )
    out_specs = (P(), P(), P())

    fn = shard_map_compat(
        dev, mesh=mesh, in_specs=in_specs, out_specs=out_specs
    )
    return jax.jit(fn)
