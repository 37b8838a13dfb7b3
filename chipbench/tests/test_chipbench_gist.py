"""The gist1m-ivfflat configuration end to end on the CPU: cut to a few
thousand rows but kept at GIST's 960 dimensions, one run of its cell
through the served path is correct, and the executor packs each row at
1,024 columns."""

import json
import shutil
from pathlib import Path

import pytest

from chipbench import run as harness

ROOT = Path(__file__).resolve().parents[2]
CELL = "gist1m-ivfflat-uniform-sat"
# the CPU's float32 dot products round differently from the chip's, so the
# score limit here is the CPU's own, as for the SIFT cells' tiny runs
TINY = {"rows": 4096, "nlist": 16, "nprobe": 4, "kmeans_iters": 3,
        "executor": {"qb_buckets": [32], "chunk": 1024},
        "check": {"unanswered": 0, "lists_cover": 0, "rank_gap": 1e-3, "score_gap": 1e-4}}


@pytest.fixture(scope="module")
def tiny_gist(tmp_path_factory):
    root = tmp_path_factory.mktemp("gist")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    (conf,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    bench["workloads"], bench["configs"], bench["per_layer"] = [cell], [conf], []
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(ROOT / "chipbench" / "traffic", root / "chipbench" / "traffic")
    cfg = {**json.loads((ROOT / conf["file"]).read_text()), **TINY}
    cfg["generator"] = {**cfg["generator"], "components": 8}
    path = root / conf["file"]
    path.parent.mkdir(parents=True)
    path.write_text(json.dumps(cfg))
    return root, cfg


def test_tiny_gist_run_is_correct(tiny_gist, monkeypatch):
    root, cfg = tiny_gist
    assert cfg["dim"] == 960
    monkeypatch.setattr(harness, "use_compile_cache", lambda jax: "off")
    summaries = []
    build = harness.build

    def keep_executor(*a, **kw):
        x, index, server = build(*a, **kw)
        summaries.append(server.executor.stats_summary)
        return x, index, server

    monkeypatch.setattr(harness, "build", keep_executor)
    out = harness.run(CELL, 2**33 + 9, 1.0, False, root=root, need_chip=False)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"qps", "setup_s"}
    summary = summaries[0]()
    assert (summary["dim"], summary["dim_padded"], summary["contraction_steps"]) \
        == (960, 1024, 8)
