"""Device-resident batched executor: oracle parity (pruning on/off, both
metrics), static-shape bucketing edges, compile-count bounds, and the
scheduler/serve integration (backend="spmd", arrival-timestamp streams).

Everything runs on CPU — the jnp scoring path (use_pallas=False) plus one
interpret-mode Pallas case keep the BlockSpec logic covered without a TPU.
"""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import chunk_table_cases

from repro.config import HarmonyConfig
from repro.core import build_ivf, search_oracle
from repro.data import make_dataset, make_queries
from repro.serve import (
    ExecutorConfig,
    HarmonyServer,
    SchedulerConfig,
    ServingScheduler,
    SpmdExecutor,
)


@pytest.fixture(scope="module")
def anns():
    ds = make_dataset(nb=4000, dim=32, n_components=8, spread=0.6, seed=0)
    cfg = HarmonyConfig(dim=32, nlist=32, nprobe=6, topk=5, kmeans_iters=4)
    index = build_ivf(ds.x, cfg)
    q = make_queries(ds, nq=64, skew=0.3, noise=0.2, seed=1)
    return ds, cfg, index, q


def _executor(index, **kw):
    kw.setdefault("chunk", 128)
    kw.setdefault("qb_buckets", (8, 32))
    return SpmdExecutor(index, ExecutorConfig(**kw))


def assert_matches_oracle(res, oracle):
    """Scores equal (tie order may permute ids); inf/valid pattern equal."""
    finite = np.isfinite(oracle.scores)
    assert np.array_equal(np.isfinite(res.scores), finite)
    np.testing.assert_allclose(
        res.scores[finite], oracle.scores[finite], rtol=1e-3, atol=1e-3
    )
    # ids may differ only across equal-score ties
    diff = (res.ids != oracle.ids) & finite
    for r in np.unique(np.nonzero(diff)[0]):
        assert np.allclose(
            np.sort(res.scores[r]), np.sort(oracle.scores[r]),
            rtol=1e-3, atol=1e-3,
        ), (res.ids[r], oracle.ids[r])


# ----------------------------------------------------------------- parity


@pytest.mark.parametrize("prune", [True, False])
def test_parity_vs_oracle(anns, prune):
    ds, cfg, index, q = anns
    ex = _executor(index, prune=prune)
    res = ex.search_batch(q[:32])
    assert_matches_oracle(res, search_oracle(index, q[:32]))


def test_parity_pallas_interpret(anns):
    """Interpret-mode Pallas kernels under the executor (tile-skip map and
    BlockSpec logic validated end to end on CPU)."""
    ds, cfg, index, q = anns
    ex = _executor(index, use_pallas=True, tile_m=32, tile_n=64, tile_k=32)
    res = ex.search_batch(q[:8])
    assert_matches_oracle(res, search_oracle(index, q[:8]))
    assert res.stats["tile_total"] > 0


def test_topk_pass_counter_same_on_pallas_and_jnp_paths(anns):
    """The step counts the top-K merge's insertion passes the same way on
    the interpret-mode Pallas path and the jnp path: at most one per slot
    (chunks × query tiles × K), the same count, the same answers."""
    ds, cfg, index, q = anns
    runs = {}
    for use_pallas in (False, True):
        ex = _executor(index, use_pallas=use_pallas, tile_m=32, tile_n=64,
                       tile_k=32)
        res = ex.search_batch(q[:8])
        runs[use_pallas] = (res, ex.stats_summary())
        assert_matches_oracle(res, search_oracle(index, q[:8]))
        summary = runs[use_pallas][1]
        assert 0 < summary["topk_passes"] <= summary["topk_pass_slots"]
        assert summary["topk_pass_frac"] == (summary["topk_passes"]
                                             / summary["topk_pass_slots"])
        for key in ("topk_passes", "topk_pass_slots", "topk_pass_frac"):
            assert res.stats[key] == summary[key]
    (res_j, sum_j), (res_p, sum_p) = runs[False], runs[True]
    assert sum_p["topk_passes"] == sum_j["topk_passes"]
    assert sum_p["topk_pass_slots"] == sum_j["topk_pass_slots"]
    np.testing.assert_array_equal(res_p.ids, res_j.ids)


@pytest.fixture(scope="module", params=[960, 96], ids=lambda d: f"d{d}")
def unaligned(request):
    """A corpus whose width is no multiple of the 128-column contraction
    tile (GIST's 960, and one narrower than a tile)."""
    dim = request.param
    ds = make_dataset(nb=4096, dim=dim, n_components=8, spread=0.6, seed=4)
    cfg = HarmonyConfig(dim=dim, nlist=16, nprobe=4, topk=5, kmeans_iters=3)
    return build_ivf(ds.x, cfg), make_queries(ds, nq=8, seed=5)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_unaligned_width_is_padded_once_and_matches_oracle(unaligned, use_pallas,
                                                           monkeypatch):
    """Each dimension block is packed once at a multiple of tile_k: the
    resident corpus and the queries arrive tile-aligned, the kernel pads no
    column of any chunk, and the answers are the oracle's."""
    from repro.kernels import distance

    index, q = unaligned
    column_pads, padded = [], []
    pad_to = distance.pad_to

    def recording_pad_to(a, mult, value):
        padded.append(a.shape)
        if a.ndim == 2 and a.shape[1] % mult[1]:
            column_pads.append(a.shape)
        return pad_to(a, mult, value)

    monkeypatch.setattr(distance, "pad_to", recording_pad_to)
    # tiles of this test alone, so the kernel is traced here, with the
    # recording pad_to in place
    ex = _executor(index, use_pallas=use_pallas, tile_m=16, tile_n=128)
    res = ex.search_batch(q)
    assert_matches_oracle(res, search_oracle(index, q))
    steps = -(-index.dim // 128)
    summary = ex.stats_summary()
    assert (summary["dim"], summary["dim_padded"], summary["contraction_steps"]) \
        == (index.dim, 128 * steps, steps)
    assert ex._resident[0].shape[-1] == 128 * steps
    assert padded and column_pads == []
    assert res.stats["tile_total"] > 0


def test_parity_metric_ip():
    ds = make_dataset(nb=3000, dim=24, n_components=6, spread=0.6, seed=2)
    cfg = HarmonyConfig(dim=24, nlist=24, nprobe=5, topk=5, kmeans_iters=4,
                        metric="ip")
    index = build_ivf(ds.x, cfg)
    q = make_queries(ds, nq=24, seed=3)
    ex = _executor(index)
    # -dot partial sums are not monotone → executor must not prune for ip
    assert ex.prune is False
    assert_matches_oracle(ex.search_batch(q), search_oracle(index, q))


@pytest.mark.parametrize("mesh", ["1x1", "2x2"])
@pytest.mark.parametrize("precision", ["fp32", "int8"])
def test_chunk_table_scores_each_probed_row_once(precision, mesh):
    """The step reads the probed lists in place through the chunk table:
    merged runs, long lists, unaligned starts, a window pulled back at
    the shard's end, tombstones, a filter and empty probe sets all give
    the oracle's answers with no id twice (``chunk_table_cases``). The
    2 × 2 mesh runs in a subprocess with four host devices."""
    if mesh == "1x1":
        chunk_table_cases.run(precision)
        return
    root = Path(__file__).resolve().parent.parent
    env = dict(os.environ, PYTHONPATH=str(root / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, str(root / "tests" / "chunk_table_cases.py"),
         precision, "2"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "CHUNK_TABLE_OK" in proc.stdout, proc.stdout + proc.stderr


# ------------------------------------------------------- bucketing edges


def test_batch_larger_than_biggest_bucket_splits(anns):
    ds, cfg, index, q = anns
    ex = _executor(index)            # biggest qb bucket = 32 < 64 queries
    res = ex.search_batch(q)
    assert res.ids.shape == (64, 5)
    assert res.stats["splits"] == 2
    assert_matches_oracle(res, search_oracle(index, q))


def test_singleton_batch(anns):
    ds, cfg, index, q = anns
    ex = _executor(index)
    res = ex.search_batch(q[:1])
    assert res.ids.shape == (1, 5)
    assert res.stats["pad_queries"] == ex.qb_buckets[0] - 1
    assert_matches_oracle(res, search_oracle(index, q[:1]))


def test_empty_probe_set(anns):
    ds, cfg, index, q = anns
    ex = _executor(index)
    res = ex.search_batch(q[:4], nprobe=0)
    assert (res.ids == -1).all()
    assert np.isinf(res.scores).all()
    assert ex.compiles == 0          # no candidates → no device dispatch


# ------------------------------------------------------ compile bounds


def test_mixed_batch_sizes_compile_each_bucket_at_most_once(anns):
    ds, cfg, index, q = anns
    ex = _executor(index)
    sizes = [3, 8, 20, 32, 1, 17, 32, 8]
    off = 0
    for n in sizes:
        ex.search_batch(q[off % 32 : off % 32 + n])
        off += 7
    assert all(n == 1 for n in ex.trace_counts.values()), ex.trace_counts
    compiled = ex.compiles
    # replaying the same mix must be served entirely from the compile cache
    off = 0
    for n in sizes:
        ex.search_batch(q[off % 32 : off % 32 + n])
        off += 7
    assert ex.compiles == compiled
    assert set(ex.trace_counts) == set(ex._steps)


# ------------------------------------------------- scheduler integration


def test_scheduled_spmd_backend_matches_oracle(anns):
    ds, cfg, index, q = anns
    srv = HarmonyServer(index, n_nodes=4,
                        executor_cfg=ExecutorConfig(chunk=128, qb_buckets=(16,)))
    sched = ServingScheduler(
        srv, SchedulerConfig(max_batch=16, backend="spmd"), k=5
    )
    results = sched.run_trace([(0.0, q[i]) for i in range(len(q))])
    assert len(results) == len(q)
    assert srv.stats.spmd_batches == len(q) // 16
    res_scores = np.stack([r.scores for r in results])
    oracle = search_oracle(index, q, k=5)
    finite = np.isfinite(oracle.scores)
    np.testing.assert_allclose(
        res_scores[finite], oracle.scores[finite], rtol=1e-3, atol=1e-3
    )


def test_host_fallback_is_not_counted_as_spmd(anns):
    """A precision override the executor was not built for is served by
    the host engine even under backend="spmd": that batch must not count
    as device-served."""
    ds, cfg, index, q = anns
    srv = HarmonyServer(index, n_nodes=4, backend="spmd",
                        executor_cfg=ExecutorConfig(chunk=128, qb_buckets=(16,)))
    dev = srv.search_batch(q[:8], k=5)
    host = srv.search_batch(q[:8], k=5, precision="int8")
    assert (dev.stats["backend"], host.stats["backend"]) == ("spmd", "host")
    assert (srv.stats.batches, srv.stats.spmd_batches) == (2, 1)


def test_serve_arrival_stream_drives_batch_formation(anns):
    """Per-batch arrival timestamps must reach the scheduler: far-apart
    arrivals form one deadline batch each instead of one merged batch, and
    queue-wait percentiles stop degenerating to the all-at-t0 answer."""
    ds, cfg, index, q = anns
    batches = [q[0:4], q[4:8], q[8:12]]

    srv0 = HarmonyServer(index, n_nodes=4)
    srv0.serve(batches, k=5)                       # legacy: all arrive at t=0
    assert srv0.stats.batches == 1

    srv = HarmonyServer(index, n_nodes=4)
    outs = srv.serve(batches, k=5, arrivals=[0.0, 10.0, 20.0])
    assert srv.stats.batches == 3
    assert srv.stats.deadline_batches == 3
    oracle = search_oracle(index, q[:12], k=5)
    np.testing.assert_allclose(
        np.concatenate([o.scores for o in outs]), oracle.scores,
        rtol=1e-3, atol=1e-3,
    )


def test_serve_per_row_arrivals(anns):
    ds, cfg, index, q = anns
    srv = HarmonyServer(index, n_nodes=4)
    outs = srv.serve(
        [q[0:4]], k=5, arrivals=[np.array([0.0, 0.1, 0.2, 0.3])],
    )
    assert outs[0].ids.shape == (4, 5)
    # spaced arrivals + 2ms deadline → multiple batches, nonzero makespan
    assert srv.stats.batches >= 2
    assert outs[0].stats["wall_s"] > 0.0
