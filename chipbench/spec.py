"""Find a cell's configuration, traffic mix and per-layer metrics by name.

Everything that belongs to one configuration, one mix or one metric is a
file of its own, so a later change adds files and ``BENCHMARK.json``
entries and edits none:

* ``BENCHMARK.json`` ``configs[].file``: the configuration's JSON;
* ``chipbench/traffic/<traffic>.json``: the traffic mix;
* ``chipbench/metrics/<metric>.py``: one per-layer metric, a module with
  ``read(run) -> float | None``.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((Path(root) / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "chipbench" / "traffic" / f"{name}.json").read_text())


def metrics(bench: dict, cell_name: str, per_layer: bool) -> list:
    """The cell's end-to-end (``per_layer=False``) or per-layer metric
    entries: those that list the cell, or list no cells."""
    key = "per_layer" if per_layer else "end_to_end"
    return [m for m in bench[key]
            if "workloads" not in m or cell_name in m["workloads"]]


def reader(name: str, root: Path = ROOT):
    """The ``read`` function of ``chipbench/metrics/<name>.py``."""
    path = Path(root) / "chipbench" / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
