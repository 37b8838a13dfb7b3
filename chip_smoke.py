#!/usr/bin/env python3
"""Bring-up smoke test: the served vector-search path on a TPU.

Drives the normal serving entry points once at the shape of the
ann-benchmarks ``sift-128-euclidean`` deployment — 1,000,000 float32
vectors of 128 dimensions, L2, k=10, an IVF index with 1024 lists built on
the chip — through

    SearchRequest -> ServingFrontend -> HarmonyServer(backend="spmd")
      -> SpmdExecutor -> ring step (core/pipeline.py) -> Pallas kernels

and checks every answer against the repository's own references
(``search_oracle`` at the same probes, brute force for recall).

    python chip_smoke.py             # one chip: fp32 and int8 servers
    python chip_smoke.py --chips 4   # four chips: only the V=2 x B=2 mesh
                                     # path (ppermute ring + all_gather
                                     # merge) and its oracle comparison

It needs a TPU and has no CPU branch: without one it exits non-zero
before doing any work. Any failed check exits non-zero too. Only when
every check passed is the last line of standard output
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, or,
when that is unset, at ``.jax_cache`` next to this script.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.config import HarmonyConfig  # noqa: E402
from repro.core import (  # noqa: E402
    SearchRequest, SegmentedIndex, build_ivf, search_oracle,
)
from repro.data import (  # noqa: E402
    brute_force_topk, make_dataset, make_queries, recall_at_k,
)
from repro.kernels import ops as kops  # noqa: E402
from repro.serve import HarmonyServer, SchedulerConfig, ServingFrontend  # noqa: E402
from repro.serve.executor import ExecutorConfig  # noqa: E402

# ann-benchmarks sift-128-euclidean shape, IVF1024 (Faiss's IVF1024,Flat)
N_ROWS, DIM, NLIST, K = 1_000_000, 128, 1024, 10
NPROBE = 16          # probed lists per query
BATCH = 32           # requests per served batch (= the one qb bucket)
FP32_BATCHES, INT8_BATCHES = 8, 4
N_UPSERTS = 8        # half replace sealed rows, half add new ids
RECALL_SAMPLE = 100  # queries whose recall@10 is measured by brute force
SEED = 0


class SmokeError(RuntimeError):
    """A check of the smoke test failed."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def report(name: str, **fields) -> None:
    print(f"{name}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def cache_dir() -> str:
    """Where the persistent compile cache lives: ``JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it itself), else one fixed path in the checkout."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")


class CompileCounter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events."""

    def __init__(self):
        self.compiles, self.compile_s, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ---------------------------------------------------------------- phases


def build_index(n_rows: int, dim: int, nlist: int, nprobe: int, n_queries: int,
                seed: int = SEED):
    """Seeded Gaussian-mixture corpus and queries, and the IVF index built
    over them (k-means runs on the default device)."""
    t0 = time.perf_counter()
    ds = make_dataset(nb=n_rows, dim=dim, n_components=nlist, seed=seed)
    queries = make_queries(ds, nq=n_queries, seed=seed + 1)
    data_s = time.perf_counter() - t0
    cfg = HarmonyConfig(dim=dim, nlist=nlist, nprobe=nprobe, topk=K)
    t0 = time.perf_counter()
    index = build_ivf(ds.x, cfg)
    build_s = time.perf_counter() - t0
    report("build", rows=n_rows, dim=dim, nlist=nlist, nprobe=nprobe,
           data_s=data_s, build_s=build_s,
           train_s=index.build_times["train"], add_s=index.build_times["add"])
    return SegmentedIndex.from_static(index), queries


def upsert_batch(data: SegmentedIndex, queries: np.ndarray, n: int,
                 seed: int = SEED):
    """``n`` writes, each placed next to one of the first ``n`` queries so
    the delta rows show up in their answers: the first half replace sealed
    rows (tombstoning them), the rest add new ids."""
    rng = np.random.default_rng(seed + 2)
    nb = data.segments[0].nb
    ids = np.concatenate([
        rng.choice(nb, n // 2, replace=False),
        nb + np.arange(n - n // 2),
    ]).astype(np.int64)
    noise = rng.standard_normal((n, queries.shape[1])).astype(np.float32)
    vecs = queries[:n] + 0.05 * noise / np.sqrt(queries.shape[1])
    return ids, vecs


def serve(server: HarmonyServer, queries: np.ndarray, batch: int,
          upserts=None):
    """Serve ``queries`` on the wall clock through a ``ServingFrontend``
    in full batches of ``batch`` requests (``upserts`` are written through
    the front-end first). Returns (ids [n, K], scores [n, K]).
    """
    sched = SchedulerConfig(max_batch=batch, max_wait_s=0.5, max_retries=0)
    t0 = time.perf_counter()
    with ServingFrontend(server, sched, k=K) as fe:   # warms up the buckets
        warmup_s = time.perf_counter() - t0
        if upserts is not None:
            fe.upsert(*upserts)
        results = []
        t0 = time.perf_counter()
        for lo in range(0, len(queries), batch):
            futs = fe.submit_many(
                [SearchRequest(vector=v) for v in queries[lo:lo + batch]]
            )
            results += [f.result(timeout=900) for f in futs]
        serve_s = time.perf_counter() - t0
        lat = np.asarray(fe.stats.request_latency_ms)
    ex = server.executor
    report(f"serve[{server.precision}]", requests=len(results),
           batches=server.stats.batches, warmup_s=warmup_s, serve_s=serve_s,
           step_compiles=ex.compiles, dispatches=ex.dispatches,
           latency_ms_p50=float(np.percentile(lat, 50)),
           latency_ms_p99=float(np.percentile(lat, 99)),
           latency_ms_max=float(lat.max()))
    return (np.stack([r.ids for r in results]),
            np.stack([r.scores for r in results]))


def check_served(server: HarmonyServer, ids: np.ndarray) -> None:
    """No batch fell back to the host engine, failed, or degraded."""
    st = server.stats
    require(st.batches > 0 and st.spmd_batches == st.batches,
            f"{st.batches - st.spmd_batches} of {st.batches} batches left "
            "the device path")
    require(server.executor.dispatches >= st.batches,
            "fewer executor dispatches than served batches")
    require(st.failed_batches == 0, f"{st.failed_batches} batches failed")
    require((ids >= 0).all(), f"{int((ids < 0).sum())} sentinel ids (-1)")


def reference(data: SegmentedIndex, queries: np.ndarray, k: int = K):
    """``search_oracle`` over the sealed segment (tombstones applied) at
    the probes the server uses, merged with an exact scan of the live
    delta rows. Returns (ids, scores)."""
    snap = data.snapshot()
    (seg,) = snap.segments
    orc = search_oracle(seg.index, queries, k,
                        dead_rows=snap.dead_rows[seg.seg_id])
    live = np.nonzero(snap.delta_live)[0]
    d = ((queries[:, None, :] - snap.delta_x[live][None]) ** 2).sum(-1)
    s = np.concatenate([orc.scores, d.astype(np.float32)], axis=1)
    i = np.concatenate(
        [orc.ids, np.broadcast_to(snap.delta_ids[live], d.shape)], axis=1)
    top = np.argsort(s, axis=1, kind="stable")[:, :k]
    return np.take_along_axis(i, top, 1), np.take_along_axis(s, top, 1)


def true_scores(data: SegmentedIndex, queries: np.ndarray, ids: np.ndarray):
    """Exact squared L2 distance of each returned id's live vector."""
    live_ids, live_x = data.live_vectors()
    pos = np.searchsorted(live_ids, ids)
    require((live_ids[pos] == ids).all(), "an answer names a dead id")
    return ((live_x[pos] - queries[:, None, :]) ** 2).sum(-1)


def check_exact(name, data, queries, ids, scores, want_ids, want_scores,
                rtol=1e-3, atol=1e-5) -> None:
    """fp32 answers equal the oracle's: scores within ``rtol`` at every
    rank, each score the true distance of its id, so ids can differ only
    across tied scores."""
    close = np.isclose(scores, want_scores, rtol=rtol, atol=atol)
    require(close.all(), f"{name}: {int((~close).sum())} scores differ "
            f"from the oracle beyond rtol {rtol}")
    true = true_scores(data, queries, ids)
    own = np.isclose(scores, true, rtol=rtol, atol=atol)
    require(own.all(), f"{name}: {int((~own).sum())} scores are not the "
            "distance of their id")
    report(f"exact[{name}]", queries=len(ids),
           ids_equal=float((ids == want_ids).mean()),
           tie_swaps=int((ids != want_ids).sum()),
           max_rel_err=float(np.max(np.abs(scores - want_scores)
                                    / np.maximum(np.abs(want_scores), atol))))


def check_rerank(name, data, queries, ids, scores, want_ids,
                 rtol=1e-3, atol=1e-5, min_recall=0.98) -> None:
    """int8 answers after the fp32 re-rank: every score is the exact fp32
    distance of its id, ascending, and the ids recall the fp32 oracle's."""
    true = true_scores(data, queries, ids)
    own = np.isclose(scores, true, rtol=rtol, atol=atol)
    require(own.all(), f"{name}: {int((~own).sum())} re-ranked scores are "
            "not the fp32 distance of their id")
    require((np.diff(scores, axis=1) >= 0).all(), f"{name}: not ascending")
    rec = recall_at_k(ids, want_ids)
    require(rec >= min_recall, f"{name}: recall {rec} of the fp32 oracle "
            f"below {min_recall}")
    report(f"rerank[{name}]", queries=len(ids), recall_vs_fp32_oracle=rec)


def measure_recall(data: SegmentedIndex, queries: np.ndarray, answers: dict):
    """recall@K of each server's answers against exact brute force over
    the live corpus (computed on the default device at full f32
    precision)."""
    live_ids, live_x = data.live_vectors()
    with jax.default_matmul_precision("highest"):
        pos, _ = brute_force_topk(live_x, queries, K)
    truth = live_ids[pos]
    report("recall", queries=len(queries), k=K,
           **{f"recall_{n}": recall_at_k(a[: len(queries)], truth)
              for n, a in answers.items()})


def check_device_path(server: HarmonyServer) -> str:
    """The executor chose the Pallas path and its compiled step runs
    Mosaic kernels (``tpu_custom_call``), not interpreted ones. Returns
    the compiled HLO text."""
    ex = server.executor
    require(ex.use_pallas, "SpmdExecutor took the jnp path (use_pallas False)")
    key, step = next(iter(ex._steps.items()))      # one compiled bucket
    hlo = step.lower(*ex._warmup_args(*key)).compile().as_text()
    require("tpu_custom_call" in hlo,
            "the compiled step has no Mosaic kernel (tpu_custom_call)")
    return hlo


def check_kernels_compiled(m: int = 128, n: int = 256, d: int = 128) -> None:
    """Each kernel wrapper, left to pick interpret mode by itself, lowers
    to a Mosaic kernel at serving shapes."""
    f32, i32, i8 = jnp.float32, jnp.int32, jnp.int8
    S = jax.ShapeDtypeStruct
    calls = {
        "partial_distance_update": (kops.partial_distance_update, (
            S((n, d), f32), S((n,), f32), S((m, d), f32), S((m,), f32),
            S((m, n), f32), S((m,), f32))),
        "int8_partial_distance_update": (kops.int8_partial_distance_update, (
            S((n, d), i8), S((n,), f32), S((m, d), i8), S((m,), f32),
            S((), f32), S((m, n), f32), S((m,), f32))),
        "running_topk_update": (
            lambda *a: kops.running_topk_update(*a, k=K), (
                S((m, n), f32), S((m, n), i32), S((m, K), f32),
                S((m, K), i32))),
    }
    for name, (fn, shapes) in calls.items():
        text = jax.jit(fn).lower(*shapes).as_text()
        require("tpu_custom_call" in text, f"{name} is not a Mosaic kernel")


def peak_memory() -> None:
    for i, dev in enumerate(jax.devices()):
        st = dev.memory_stats() or {}
        report(f"memory[{i}]", peak_bytes_in_use=st.get("peak_bytes_in_use"),
               bytes_in_use=st.get("bytes_in_use"),
               bytes_limit=st.get("bytes_limit"))


# ------------------------------------------------------------------ runs


def run_one_chip(n_rows: int = N_ROWS, nlist: int = NLIST,
                 nprobe: int = NPROBE, batch: int = BATCH,
                 ecfg: ExecutorConfig = None,
                 recall_sample: int = RECALL_SAMPLE) -> None:
    """fp32 and int8 servers over one index on one chip, every answer
    checked; ``ecfg`` defaults to the one qb bucket ``batch``."""
    ecfg = ecfg or ExecutorConfig(qb_buckets=(batch,))
    n_int8 = INT8_BATCHES * batch
    data, queries = build_index(n_rows, DIM, nlist, nprobe,
                                FP32_BATCHES * batch)
    upserts = upsert_batch(data, queries, N_UPSERTS)

    fp32 = HarmonyServer(data, n_nodes=1, backend="spmd", executor_cfg=ecfg)
    ids32, sc32 = serve(fp32, queries, batch, upserts=upserts)
    check_served(fp32, ids32)
    check_device_path(fp32)
    want_ids, want_sc = reference(data, queries)
    require(all(i in want_ids[j] for j, i in enumerate(upserts[0])),
            "an upserted row is missing from its query's oracle answer")
    check_exact("fp32", data, queries, ids32, sc32, want_ids, want_sc)

    int8 = HarmonyServer(data, n_nodes=1, backend="spmd", executor_cfg=ecfg,
                         precision="int8")
    ids8, sc8 = serve(int8, queries[:n_int8], batch)
    check_served(int8, ids8)
    check_device_path(int8)
    check_rerank("int8", data, queries[:n_int8], ids8, sc8, want_ids[:n_int8])

    check_kernels_compiled()
    measure_recall(data, queries[:recall_sample],
                   {"fp32": ids32, "int8": ids8})


def run_four_chips(n_rows: int = N_ROWS, nlist: int = NLIST,
                   nprobe: int = NPROBE, batch: int = BATCH) -> None:
    """Only the V=2 x B=2 mesh path (dimension ring over ``model``,
    all_gather merge over ``data``) and its oracle comparison."""
    require(len(jax.devices()) == 4,
            f"--chips 4 needs 4 devices, JAX reports {len(jax.devices())}")
    data, queries = build_index(n_rows, DIM, nlist, nprobe,
                                FP32_BATCHES * batch)
    upserts = upsert_batch(data, queries, N_UPSERTS)
    srv = HarmonyServer(data, n_nodes=1, backend="spmd",
                        executor_cfg=ExecutorConfig(d_blocks=2,
                                                    qb_buckets=(batch,)))
    ids, sc = serve(srv, queries, batch, upserts=upserts)
    check_served(srv, ids)
    ex = srv.executor
    require(ex.mesh.devices.shape == (2, 2), f"mesh {ex.mesh.devices.shape}")
    # the resident corpus must be spread over all four chips
    corpus = ex._resident[0]
    per_dev = {s.device.id: s.data.nbytes for s in corpus.addressable_shards}
    require(len(per_dev) == 4 and min(per_dev.values()) > 0,
            f"corpus shards by device: {per_dev}")
    report("mesh", shape=ex.mesh.devices.shape, corpus_bytes_by_device=per_dev)
    hlo = check_device_path(srv)
    for op in ("collective-permute", "all-gather"):
        require(op in hlo, f"the V=2 x B=2 step has no {op}")
    want_ids, want_sc = reference(data, queries)
    check_exact("fp32_2x2", data, queries, ids, sc, want_ids, want_sc)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX reports platform "
              f"{devs[0].platform!r}); this smoke test runs only on a TPU",
              file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    counter = CompileCounter()
    t0 = time.perf_counter()
    report("device", platform=devs[0].platform, kind=repr(devs[0].device_kind),
           count=len(devs), jax=jax.__version__, cache=cache_dir())
    if args.chips == 4:
        run_four_chips()
    else:
        run_one_chip()
    peak_memory()
    report("compile", backend_compiles=counter.compiles,
           backend_compile_s=counter.compile_s,
           cache_hits=counter.cache_hits)
    report("total", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
