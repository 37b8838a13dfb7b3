"""Configurations, mixes and metrics are files found by name, and the
committed BENCHMARK.json keeps to the shape the harness reads."""

import json
import re
from pathlib import Path

from chipbench import spec

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_a_new_config_mix_and_metric_are_found_by_name(tmp_path):
    (tmp_path / "chipbench" / "configs").mkdir(parents=True)
    (tmp_path / "chipbench" / "traffic").mkdir()
    (tmp_path / "chipbench" / "metrics").mkdir()
    (tmp_path / "chipbench" / "configs" / "tmpcfg.json").write_text('{"rows": 7}')
    (tmp_path / "chipbench" / "traffic" / "tmpmix.json").write_text('{"loop": "closed"}')
    (tmp_path / "chipbench" / "metrics" / "tmp_metric.x.py").write_text(
        "def read(run):\n    return run * 2\n")
    bench = {
        "configs": [{"name": "tmpcfg", "file": "chipbench/configs/tmpcfg.json"}],
        "workloads": [{"name": "tmpcell", "config": "tmpcfg", "traffic": "tmpmix"}],
        "end_to_end": [{"name": "setup_s"}, {"name": "qps", "workloads": ["other"]}],
        "per_layer": [{"name": "tmp_metric.x", "workloads": ["tmpcell"]},
                      {"name": "elsewhere", "workloads": ["other"]}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    b = spec.benchmark(tmp_path)
    cell = spec.cell(b, "tmpcell")
    assert spec.config(b, cell["config"], tmp_path) == {"rows": 7}
    assert spec.traffic(cell["traffic"], tmp_path) == {"loop": "closed"}
    assert [m["name"] for m in spec.metrics(b, "tmpcell", per_layer=True)] == ["tmp_metric.x"]
    assert [m["name"] for m in spec.metrics(b, "tmpcell", per_layer=False)] == ["setup_s"]
    assert spec.reader("tmp_metric.x", tmp_path)(21) == 42


def test_benchmark_json_is_complete():
    b = spec.benchmark(ROOT)
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in b[k]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for c in b["configs"]:
        cfg = spec.config(b, c["name"], ROOT)
        assert cfg["reduced"] == c["reduced"] and c["file"].startswith("chipbench/")
    for w in b["workloads"]:
        spec.traffic(w["traffic"], ROOT)
        reported = [m["name"] for m in spec.metrics(b, w["name"], per_layer=False)]
        assert "setup_s" in reported and len(reported) >= 2
        assert spec.metrics(b, w["name"], per_layer=True)
    for m in b["per_layer"]:
        assert callable(spec.reader(m["name"], ROOT))
        for w in m["workloads"]:
            assert m["moves"] in [x["name"] for x in spec.metrics(b, w, per_layer=False)]
    layers = {m["layer"] for m in b["per_layer"]}
    perf = (ROOT / "PERF.md").read_text()
    assert all(f"`{layer}`" in perf for layer in layers)
