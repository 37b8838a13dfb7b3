"""The output check's control, at a size a CPU test holds: the reference one
precision lower (bfloat16x3 products for IVF-Flat, an int4 grid for
IVF-SQ8) and the planted faults all come out not correct under the
committed limits, by the harness's own verdict; the reference itself comes
out correct."""

import json
from pathlib import Path

import pytest

from chipbench import control, spec

ROOT = Path(__file__).resolve().parents[2]
SMALL = {"rows": 8192, "nlist": 32, "nprobe": 4, "kmeans_iters": 4}


def _cell(name):
    bench = spec.benchmark(ROOT)
    cell = spec.cell(bench, name)
    cfg = {**spec.config(bench, cell["config"], ROOT), **SMALL}
    cfg["generator"] = {**cfg["generator"], "components": 10}
    return cfg, spec.traffic(cell["traffic"], ROOT)


@pytest.mark.parametrize("workload", ["sift1m-ivfflat-uniform-sat",
                                      "sift1m-ivfsq8-uniform-sat"])
def test_control_and_faults_fail_and_the_reference_passes(workload):
    cfg, mix = _cell(workload)
    rows = control.control_gaps(cfg, mix, 5, 512, ["reference", "control", "answer", "half"])
    assert rows[0]["correct"], json.dumps(rows[0])
    assert not any(r["correct"] for r in rows[1:]), json.dumps(rows)
