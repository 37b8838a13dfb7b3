"""The served path's profiler spans and row counters, captured on the CPU.

A few batches go through ``ServingFrontend`` → ``HarmonyServer`` (spmd) →
``SpmdExecutor`` under ``jax.profiler``; the trace is read back with the
benchmark's span loader, ``chipbench.spantrace.events``."""

import glob
from collections import defaultdict

import jax
import numpy as np
import pytest

from chipbench import spantrace
from repro.config import HarmonyConfig
from repro.core import SearchRequest, assign_queries, build_ivf
from repro.serve import HarmonyServer, SchedulerConfig, ServingFrontend
from repro.serve.executor import ExecutorConfig
from repro.serve.spans import PREFIX

NQ, BATCH, CHUNK = 24, 8, 128
EXECUTOR_PARTS = {"executor.gather_table", "executor.launch", "executor.wait"}


def _served_trace(precision, tmp_path):
    rng = np.random.default_rng(3)
    x = rng.standard_normal((2048, 16)).astype(np.float32)
    index = build_ivf(x, HarmonyConfig(dim=16, nlist=16, nprobe=4, topk=5,
                                       kmeans_iters=2))
    srv = HarmonyServer(index, n_nodes=1, backend="spmd", precision=precision,
                        executor_cfg=ExecutorConfig(qb_buckets=(BATCH,), chunk=CHUNK,
                                                    precision=precision))
    ex = srv.executor
    q = rng.standard_normal((NQ, 16)).astype(np.float32)
    with ServingFrontend(srv, SchedulerConfig(max_batch=BATCH, max_wait_s=0.05),
                         k=5) as fe:
        fe.submit_many([SearchRequest(vector=v) for v in q[:BATCH]])
        fe.drain(timeout=120)                       # compiles outside the trace
        before = (ex.rows_gathered, ex.rows_scanned)
        jax.profiler.start_trace(str(tmp_path))
        try:
            answers = [f.result(timeout=120) for f in
                       fe.submit_many([SearchRequest(vector=v) for v in q])]
            fe.drain(timeout=120)
        finally:
            jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    from jax.profiler import ProfileData

    spans = spantrace.events(ProfileData.from_file(path))["spans"]
    counted = (ex.rows_gathered - before[0], ex.rows_scanned - before[1])
    return index, q, answers, spans, counted, ex.stats_summary()


def _inside(inner, outer):
    return (inner[4] == outer[4] and outer[2] <= inner[2]
            and inner[2] + inner[3] <= outer[2] + outer[3])


@pytest.mark.parametrize("precision", ["fp32", "int8"])
def test_each_batch_carries_the_span_tree_and_the_row_counters(precision, tmp_path):
    index, q, answers, spans, (gathered, scanned), summary = \
        _served_trace(precision, tmp_path)
    by_name = defaultdict(list)
    for sp in spans:
        by_name[sp[0]].append(sp)
    batches = by_name["frontend.batch"]
    served = sorted({a.batch_id for a in answers})
    assert sorted(int(b[1]["batch"]) for b in batches) == served
    assert sorted(int(d[1]["batch"]) for d in by_name["frontend.dispatch"]) == served
    assert all(int(d[1]["queued"]) >= 0 for d in by_name["frontend.dispatch"])
    own = EXECUTOR_PARTS | ({"executor.prewarm"} if precision == "fp32"
                            else {"executor.rerank"})
    live_rows = scanned_chunks = 0
    for fb in batches:
        bid = int(fb[1]["batch"])
        rows = [a.req_id for a in answers if a.batch_id == bid]
        assert int(fb[1]["size"]) == len(rows)
        (engine,) = [s for s in by_name["engine"] if _inside(s, fb)]
        (probe,) = [s for s in by_name["engine.probe"] if _inside(s, engine)]
        (complete,) = [s for s in by_name["frontend.complete"] if _inside(s, fb)]
        assert complete[2] >= engine[2] + engine[3]
        (ex,) = [s for s in by_name["executor"] if _inside(s, engine)]
        assert probe[2] + probe[3] <= ex[2]
        children = {s[0] for s in spans if s[0].startswith("executor.")
                    and _inside(s, ex)}
        assert children == own
        # the executor's live rows are the probed lists' rows, counted apart
        probes = assign_queries(index, q[np.array(rows) - min(a.req_id for a in answers)])
        assert int(ex[1]["rows"]) == int(index.sizes[np.unique(probes)].sum())
        assert int(ex[1]["rows"]) <= int(ex[1]["cap"]) and int(ex[1]["qb"]) == BATCH
        live_rows += int(ex[1]["rows"])
        # the step scans whole chunks of the table, at most the bucket
        chunks = int(ex[1]["chunks"])
        assert int(ex[1]["rows"]) <= chunks * CHUNK <= int(ex[1]["cap"])
        scanned_chunks += chunks
    assert gathered == live_rows <= scanned
    assert scanned == scanned_chunks * CHUNK
    assert summary["rows_gathered"] >= gathered and summary["rows_scanned"] >= scanned
    assert summary["rows_scanned"] == summary["chunks_scanned"] * CHUNK


def test_the_benchmark_reads_the_program_prefix():
    assert PREFIX == "harmony." == spantrace.PREFIX


def test_index_build_carries_the_kmeans_span(tmp_path):
    """``harmony.index.kmeans`` wraps training, with the rows, the lists
    and the row blocks of each Lloyd pass, as ``build_times`` records."""
    x = np.random.default_rng(5).standard_normal((1500, 16)).astype(np.float32)
    jax.profiler.start_trace(str(tmp_path))
    try:
        index = build_ivf(x, HarmonyConfig(dim=16, nlist=12, nprobe=4,
                                           kmeans_iters=2))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    from jax.profiler import ProfileData

    spans = [s for s in spantrace.events(ProfileData.from_file(path))["spans"]
             if s[0] == "index.kmeans"]
    assert len(spans) == 1
    args = {k: int(v) for k, v in spans[0][1].items()}
    assert args == {"rows": 1500, "nlist": 12, "blocks": 1}
    assert index.build_times["kmeans_blocks"] == 1
    assert spans[0][3] / 1e9 <= index.build_times["train"]
