"""Work counts and the least time, on shapes worked out by hand."""

import json

import numpy as np
import pytest

from chipbench import work


def test_batch_work_counts_pairs_and_distinct_rows():
    sizes = np.array([10, 20, 30, 40])
    probes = [np.array([0, 1]), np.array([1, 3])]     # 30 and 60 rows scanned
    flops, nbytes = work.batch_work(sizes, probes, dim=8, precision="fp32", k_out=2)
    assert flops == 2 * 8 * (30 + 60)
    # distinct lists 0, 1, 3: 70 rows of 8*4 + 4 + 4 bytes, 2 queries, 2x2 results
    assert nbytes == 70 * 40 + 2 * 8 * 4 + 2 * 2 * 8
    _, nb8 = work.batch_work(sizes, probes, dim=8, precision="int8", k_out=2)
    assert nb8 == 70 * 16 + 2 * 8 + 2 * 2 * 8


def test_least_time_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "int8_ops_per_s": 200.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time(1000.0, 50.0, peak, "fp32") == (10.0, "compute")
    assert work.least_time(1000.0, 60.0, peak, "int8") == (6.0, "memory")


def test_peaks_table(tmp_path):
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    p = tmp_path / "peaks.json"
    p.write_text(json.dumps({"devices": {}}))
    with pytest.raises(KeyError):
        work.peaks("TPU v5 lite", p)
