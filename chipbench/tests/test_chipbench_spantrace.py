"""The reduction of the program's own spans (``chipbench/spantrace.py``):
on a synthetic trace, where every number can be worked out by hand, and
on the recorded v5e trace, which holds no program span."""

import gzip
from pathlib import Path

import pytest

from chipbench import spantrace, trace

MS = 1_000_000      # ns


def _synthetic():
    """A 10 ms window: one batch whose device step runs 3–4 ms; thread 0
    serves it, thread 1 finishes an earlier batch and dispatches the next."""
    def sp(name, a, b, thread=0, **args):
        return [name, args, a * MS, (b - a) * MS, thread]

    return {
        "devices": {"/device:TPU:0": {
            "modules": [["jit_device_fn", 3 * MS, 1 * MS]],
            "ops": [["%running_topk_update.1", 3 * MS, 1 * MS]],
        }},
        "host": [["window", {}, 0, 10 * MS],
                 ["engine", {"batch": 7}, 0.9 * MS, 5.2 * MS],
                 ["executor", {}, 1.4 * MS, 4.2 * MS]],
        "spans": [
            sp("frontend.batch", 0.5, 9, batch=7, size=32),
            sp("engine", 1, 6),
            sp("engine.probe", 1.2, 1.5),
            sp("executor", 1.5, 5.5, qb=32, cap=512, rows=300),
            sp("executor.gather_table", 1.6, 2.4),
            sp("executor.launch", 2.4, 3),
            sp("executor.wait", 3, 4),
            sp("executor.rerank", 4, 5),
            sp("frontend.complete", 6.5, 8.5),
            sp("frontend.complete", 1, 1.1, thread=1),
            sp("frontend.dispatch", 8, 8.2, thread=1, batch=8, queued=3),
        ],
    }


def test_idle_goes_to_the_innermost_covering_span():
    r = spantrace.reduce(_synthetic())
    want = {"unspanned": 1.5, "frontend.batch": 1.5, "engine": 0.6,
            "engine.probe": 0.3, "executor": 0.6, "executor.gather_table": 0.8,
            "executor.launch": 0.6, "executor.rerank": 1.0,
            "frontend.complete": 1.9, "frontend.dispatch": 0.2}
    assert set(r["idle_by_span"]) == set(want)
    for name, ms in want.items():
        assert r["idle_by_span"][name] == pytest.approx(ms * 1e-3), name
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # each gap takes the span that holds most of it
    assert r["idle_gaps"] == [["frontend.complete", pytest.approx(6e-3)],
                              ["executor.gather_table", pytest.approx(3e-3)]]


def test_span_seconds_sum_per_batch_on_the_batch_thread():
    r = spantrace.reduce(_synthetic())
    (row,) = r["batches"]
    want = {"engine": 5.0, "engine.probe": 0.3, "executor": 4.0,
            "executor.gather_table": 0.8, "executor.launch": 0.6,
            "executor.wait": 1.0, "executor.rerank": 1.0}
    assert set(row["span_s"]) == set(want)      # thread 1's span is not the batch's
    for name, ms in want.items():
        assert row["span_s"][name] == pytest.approx(ms * 1e-3), name


def test_window_table_counts_totals_and_self_times():
    t = spantrace.reduce(_synthetic())["spans"]
    assert t["frontend.complete"]["count"] == 2
    assert t["frontend.complete"]["total_s"] == pytest.approx(2.1e-3)
    assert t["frontend.batch"]["self_s"] == pytest.approx(1.5e-3)
    assert t["engine"]["self_s"] == pytest.approx(0.7e-3)
    assert t["executor"]["self_s"] == pytest.approx(0.6e-3)
    assert t["executor.wait"] == {"count": 1, "total_s": pytest.approx(1e-3),
                                  "self_s": pytest.approx(1e-3)}


def test_readings_on_the_synthetic_trace():
    r = spantrace.reduce(_synthetic())
    assert spantrace.span_ms(r, "executor.gather_table") == pytest.approx(0.8)
    assert spantrace.span_ms(r, "engine.probe") == pytest.approx(0.3)
    assert spantrace.span_ms(r, "executor.prewarm") is None
    # harness executor 4.2 ms less the children's 3.4 ms
    assert spantrace.executor_uncovered_ms(r) == pytest.approx(0.8)
    # frontend.batch 8.5 ms less engine 5 ms, plus thread 1's 0.1 ms
    assert spantrace.frontend_ms(r) == pytest.approx(3.6)
    assert spantrace.idle_unspanned_pct(r) == pytest.approx(15.0)
    assert spantrace.gather_fill_pct({"rows_gathered": 100, "rows_scanned": 200},
                                     {"rows_gathered": 400, "rows_scanned": 600}) \
        == pytest.approx(75.0)


@pytest.mark.parametrize("reading", ["span_ms", "executor_uncovered_ms",
                                     "frontend_ms", "idle_unspanned_pct"])
def test_readings_are_none_without_program_spans(reading, recorded):
    fn = getattr(spantrace, reading)
    args = ("executor.launch",) if reading == "span_ms" else ()
    assert fn(None, *args) is None
    assert fn(spantrace.reduce(recorded), *args) is None


def test_gather_fill_is_none_without_the_counters():
    assert spantrace.gather_fill_pct({"batches": 1}, {"batches": 2}) is None
    assert spantrace.gather_fill_pct({"rows_gathered": 5, "rows_scanned": 9},
                                     {"rows_gathered": 5, "rows_scanned": 9}) is None


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    path = Path(__file__).resolve().parents[1] / "testdata" / "v5e_ivfflat_two_batches.pbtxt.gz"
    return spantrace.events(ProfileData.from_text_proto(gzip.open(path, "rt").read()))


def test_every_key_of_the_harness_reduction_is_unchanged(recorded):
    assert recorded["spans"] == []
    before = trace.reduce({k: v for k, v in recorded.items() if k != "spans"})
    after = spantrace.reduce(recorded)
    for key, value in before.items():
        if key == "batches":
            assert [{k: v for k, v in b.items() if k != "span_s"}
                    for b in after[key]] == value
        else:
            assert after[key] == value, key
    assert after["spans"] == {}
    assert after["idle_by_span"] == {"unspanned": pytest.approx(
        after["window_s"] - after["busy_s"])}


# ------------------------------------------------ the recorded span trace

SPANS_DATA = Path(__file__).resolve().parents[1] / "testdata" / "v5e_ivfflat_spans_two_batches.pbtxt.gz"
PROGRAM = {"frontend.dispatch", "frontend.batch", "frontend.complete", "engine",
           "engine.probe", "executor", "executor.gather_table", "executor.prewarm",
           "executor.launch", "executor.wait", "executor.rerank"}


@pytest.fixture(scope="module")
def recorded_spans():
    """Two served batches of a 65,536-row IVF-Flat deployment on a v5e,
    with the program's spans (the window cut to the two batches)."""
    from jax.profiler import ProfileData

    return spantrace.events(ProfileData.from_text_proto(gzip.open(SPANS_DATA, "rt").read()))


def test_recorded_executor_children_lie_inside_executor(recorded_spans):
    spans = recorded_spans["spans"]
    assert {s[0] for s in spans} == PROGRAM - {"executor.rerank"}
    execs = [s for s in spans if s[0] == "executor"]
    assert len(execs) == 2 and all(int(s[1]["rows"]) <= int(s[1]["cap"]) for s in execs)
    for child in (s for s in spans if s[0].startswith("executor.")):
        assert len([e for e in execs if e[4] == child[4] and e[2] <= child[2]
                    and child[2] + child[3] <= e[2] + e[3]]) == 1
    (dev,) = recorded_spans["devices"].values()
    for ex, (_, ms, md) in zip(sorted(execs, key=lambda s: s[2]), sorted(dev["modules"], key=lambda m: m[1])):
        (wait,) = [s for s in spans if s[0] == "executor.wait" and ex[2] <= s[2] <= ex[2] + ex[3]]
        # the step starts inside its executor span; the host waits past its end
        assert ex[2] < ms and ms + md < wait[2] + wait[3] <= ex[2] + ex[3]


def test_recorded_longest_idle_gaps_carry_program_span_labels(recorded_spans):
    r = spantrace.reduce(recorded_spans)
    assert [g[0] for g in r["idle_gaps"][:3]] == ["executor.wait", "executor.prewarm",
                                                  "executor.wait"]
    assert set(r["idle_by_span"]) <= PROGRAM | {spantrace.UNSPANNED}
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert spantrace.idle_unspanned_pct(r) < 1.0
    for row in r["batches"]:
        assert set(row["span_s"]) == PROGRAM - {"frontend.dispatch", "frontend.batch",
                                                "frontend.complete", "executor.rerank"}
        assert row["span_s"]["executor"] < row["executor_s"] < row["engine_s"]
    # the harness's own numbers are those trace.reduce gives; only labels differ
    before = trace.reduce({k: v for k, v in recorded_spans.items() if k != "spans"})
    for key, value in before.items():
        if key == "batches":
            assert [{k: v for k, v in b.items() if k != "span_s"} for b in r[key]] == value
        elif key == "idle_gaps":
            assert [g[1] for g in r[key]] == [g[1] for g in value]
        else:
            assert r[key] == value, key
