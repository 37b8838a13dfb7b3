"""The trace reduction on a small trace recorded on a TPU v5e: two served
batches of a 65,536-row IVF-Flat cell (the window span cut to them)."""

import gzip
from pathlib import Path

import numpy as np
import pytest

from chipbench import trace

DATA = Path(__file__).resolve().parents[1] / "testdata" / "v5e_ivfflat_two_batches.pbtxt.gz"


@pytest.fixture(scope="module")
def events():
    from jax.profiler import ProfileData

    return trace.events(ProfileData.from_text_proto(gzip.open(DATA, "rt").read()))


def _union(intervals):
    total, end = 0.0, -np.inf
    for s, e in sorted(intervals):
        total += max(0.0, e - max(s, end))
        end = max(end, e)
    return total


def test_events_keep_device_ops_modules_and_harness_spans(events):
    (dev,) = events["devices"].values()
    assert len(dev["modules"]) == 2 and len(dev["ops"]) == 11850
    engines = [h for h in events["host"] if h[0] == "engine"]
    assert [h[1]["batch"] for h in engines] == [20, 21]
    assert trace.kernel_of("%partial_distance_update.11") == "distance"
    assert trace.kernel_of("%int8_partial_distance_update.3") == "distance"
    assert trace.kernel_of("%running_topk_update.10") == "topk"
    assert trace.kernel_of("%fusion.4") is None


def test_reduce_matches_the_events(events):
    r = trace.reduce(events)
    (dev,) = events["devices"].values()
    (win,) = [h for h in events["host"] if h[0] == "window"]
    t0, t1 = win[2], win[2] + win[3]
    busy = _union([(max(s, t0), min(s + d, t1)) for _, s, d in dev["ops"]
                   if s + d > t0 and s < t1]) * 1e-9
    assert r["window_s"] == pytest.approx(win[3] * 1e-9)
    assert r["busy_s"] == pytest.approx(busy) and 0 < r["busy_s"] < r["window_s"]
    assert [b["batch"] for b in r["batches"]] == [20, 21]
    for b, (_, ms, md) in zip(r["batches"], sorted(dev["modules"], key=lambda m: m[1])):
        assert b["step_s"] == pytest.approx(md * 1e-9)
        assert 0 < b["step_s"] < b["executor_s"] < b["engine_s"]
        topk = sum(d for n, s, d in dev["ops"] if trace.kernel_of(n) == "topk"
                   and ms <= s <= ms + md) * 1e-9
        assert b["kernel_s"]["topk"] == pytest.approx(topk)
        assert 0 < b["kernel_s"]["distance"] < b["step_s"]
    names = [n for n, _ in r["device_ops"]]
    assert names[0] == "running_topk_update" and "partial_distance_update" in names
    assert sum(t for _, t in r["device_ops"]) <= r["busy_s"] * (1 + 1e-9)
    labels = {"idle host", "between ops", "engine", "executor", "submit", "callback", "wait"}
    assert {g[0] for g in r["idle_gaps"]} <= labels
    assert sum(r["idle_by_host"].values()) == pytest.approx(r["window_s"] - r["busy_s"])
    both = sum(b["kernel_s"]["topk"] for b in r["batches"])
    assert r["kernel_total_s"]["topk"] == pytest.approx(both)
    assert r["overlap_s"] >= 0


def test_self_time_takes_nested_ops_out_of_their_parent():
    # a loop op [0, 10) holding [1, 4) and [5, 6); a lone op [12, 13)
    got = dict(trace._self_times([0, 1, 5, 12], [10, 4, 6, 13]))
    assert got == {0: 6, 1: 3, 2: 1, 3: 1}
