"""The harness end to end on the CPU at a tiny size: the run command refuses
a machine without a TPU; past that look, a run drives the served path,
prints the contract's keys, and its output check passes a sound program
and fails a broken one."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import run as harness

ROOT = Path(__file__).resolve().parents[2]
# the CPU's float32 dot products round differently from the chip's, so the
# score limit here is the CPU's own
TINY = {"rows": 4096, "nlist": 16, "nprobe": 4, "kmeans_iters": 3,
        "executor": {"qb_buckets": [32], "chunk": 1024},
        "check": {"unanswered": 0, "lists_cover": 0, "rank_gap": 1e-3, "score_gap": 1e-4}}


CELLS = {"flat-sat": ("sift1m-ivfflat", "uniform-sat"),
         "sq8-sat": ("sift1m-ivfsq8", "uniform-sat")}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout whose configurations are cut to a size the CPU serves in
    seconds, one cell per configuration and committed mix."""
    root = tmp_path_factory.mktemp("tiny")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1}
                          for n, (c, t) in CELLS.items()]
    bench["end_to_end"] = [
        {"name": "qps", "workloads": ["flat-sat", "sq8-sat"]},
        {"name": "setup_s"}]
    bench["per_layer"] = []
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for d in ("traffic", "metrics"):
        shutil.copytree(ROOT / "chipbench" / d, root / "chipbench" / d)
    (root / "chipbench" / "configs").mkdir()
    for f in (ROOT / "chipbench" / "configs").glob("*.json"):
        cfg = {**json.loads(f.read_text()), **TINY}
        cfg["generator"] = {**cfg["generator"], "components": 8}
        (root / "chipbench" / "configs" / f.name).write_text(json.dumps(cfg))
    return root


@pytest.fixture
def on_cpu(monkeypatch):
    monkeypatch.setattr(harness, "use_compile_cache", lambda jax: "off")


def test_run_command_refuses_a_machine_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         "sift1m-ivfflat-uniform-sat", "--seed", "0", "--seconds", "10", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "no TPU" in p.stderr


def test_main_prints_the_contract_line_last(monkeypatch, capsys):
    out = {"correct": True, "attempted": 3, "failed": 0, "metrics": {},
           "device": {}, "checks": {"score_gap": {"value": 1e-6, "limit": 1e-5}}}
    monkeypatch.setattr(harness, "run", lambda *a, **k: dict(out))
    assert harness.main(["--workload", "w", "--seed", "1", "--seconds", "1"]) == 0
    o, e = capsys.readouterr()
    line = json.loads(o.strip().splitlines()[-1])
    assert list(line)[-1] == "checks" and {"correct", "attempted", "failed",
                                           "metrics", "device"} <= set(line)
    assert e.strip().splitlines()[-1].startswith("check score_gap: 1e-06 limit 1e-05")


@pytest.mark.parametrize("workload", ["flat-sat", "sq8-sat"])
def test_tiny_run_is_correct(tiny_root, on_cpu, workload):
    out = harness.run(workload, 2**31 + 7, 1.0, False, root=tiny_root, need_chip=False)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    names = {m["name"] for m in harness.spec.metrics(
        harness.spec.benchmark(tiny_root), workload, per_layer=False)}
    assert set(out["metrics"]) == names
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"


def _alter_one_answer(execute):
    def broken(self, queries, *a, **kw):
        res = execute(self, queries, *a, **kw)
        res.ids = res.ids.copy()
        res.ids[0, 0] = (res.ids[0, 0] + 1) % self.index.nb
        return res
    return broken


def _drop_half_the_batch(execute):
    def broken(self, queries, *a, **kw):
        res = execute(self, queries, *a, **kw)
        half = len(queries) // 2
        res.ids, res.scores = res.ids.copy(), res.scores.copy()
        res.ids[half:] = -1
        res.scores[half:] = np.inf
        return res
    return broken


def _misassign_some_rows(monkeypatch):
    """k-means that puts every 50th row in the next list over."""
    from repro.core import index as ivf

    fit = ivf.kmeans_fit_np

    def broken(x, k, **kw):
        c, a = fit(x, k, **kw)
        a = a.copy()
        a[::50] = (a[::50] + 1) % k
        return c, a
    monkeypatch.setattr(ivf, "kmeans_fit_np", broken)


@pytest.mark.parametrize("fault,number", [(_alter_one_answer, "rank_gap"),
                                          (_drop_half_the_batch, "rank_gap"),
                                          (_misassign_some_rows, "lists_cover")])
def test_a_broken_path_is_not_correct(tiny_root, on_cpu, monkeypatch, fault, number):
    from repro.serve.executor import SpmdExecutor

    if number == "lists_cover":
        fault(monkeypatch)
    else:
        monkeypatch.setattr(SpmdExecutor, "search_batch", fault(SpmdExecutor.search_batch))
    out = harness.run("flat-sat", 11, 1.0, False,
                      root=tiny_root, need_chip=False)
    assert not out["correct"] and out["failed"] == 0
    assert out["checks"][number]["value"] > out["checks"][number]["limit"]


@pytest.mark.parametrize("workload", ["flat-sat", "sq8-sat"])
def test_traced_run_marks_its_window_and_batches(tiny_root, on_cpu, monkeypatch, workload):
    """On the CPU the trace has no TPU plane, so the reduction is replaced
    by one that keeps what the harness annotated; the rest of a traced run
    goes through."""
    seen = {}

    def keep(events):
        seen.update(events)
        return {"window_s": 1.0, "busy_s": 0.5, "batches": [], "device_ops": [],
                "idle_gaps": [], "idle_by_host": {}, "n_ops": 0,
                "kernel_total_s": {"topk": 0.0}, "overlap_s": 0.0}

    monkeypatch.setattr(harness.tracing, "reduce", keep)
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["per_layer"] = [{"name": "engine_self_ms.sat", "unit": "ms"},
                          {"name": "idle_pct.sat", "unit": "%"}]
    root = tiny_root / f"traced-{workload}"
    shutil.copytree(tiny_root / "chipbench", root / "chipbench")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = harness.run(workload, 3, 2.5, True, root=root, need_chip=False)
    assert out["correct"] and out["device"]["busy_s"] == 0.5
    assert set(out["metrics"]) == {"engine_self_ms.sat", "idle_pct.sat"}
    (window,) = [h for h in seen["host"] if h[0] == "window"]
    assert window[3] == pytest.approx(harness.TRACE_SECONDS * 1e9, rel=0.2)
    batches = [h[1]["batch"] for h in seen["host"] if h[0] == "engine"]
    assert batches and batches == sorted(batches)
