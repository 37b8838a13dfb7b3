"""The served path's own profiler spans (``harmony.*``) in a trace, beside
the reduction of ``trace.py``.

``trace.events`` keeps the device planes and the harness's spans
(``chipbench.*``); ``events`` here returns the same dict with one more key,
``"spans"``: each program span as ``[name, args, start_ns, dur_ns, thread]``
(``name`` without the ``harmony.`` prefix, ``thread`` the index of its
host-plane line). ``reduce`` returns every key of ``trace.reduce`` as that
gives it, except that an idle gap covered by a program span takes that
span's name as its label, and adds:

* ``batches[i]["span_s"]``: seconds of each program span nested in that
  row's harness ``engine`` span, on its thread;
* ``spans``: per span name, over the spans wholly inside the window, their
  ``count``, ``total_s`` and ``self_s`` (less the program spans nested in
  them on their thread);
* ``idle_by_span``: device idle seconds under the innermost program span
  that covers them (the shortest, over all threads), else ``"unspanned"``.

The ``*_ms`` helpers below are what per-layer metrics of the program
spans read; each returns None where the trace holds nothing to read, as a
trace of a program without these spans does.
"""

from __future__ import annotations

import numpy as np

from chipbench import trace

PREFIX = "harmony."
UNSPANNED = "unspanned"


def load(path: str) -> dict:
    """``events`` of an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    return events(ProfileData.from_file(path))


def events(data) -> dict:
    """``trace.events(data)`` plus the program spans under ``"spans"``."""
    out = trace.events(data)
    out["spans"] = []
    thread = 0
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out["spans"].append([e.name[len(PREFIX):],
                                         {k: v for k, v in e.stats},
                                         e.start_ns, e.duration_ns, thread])
            thread += 1
    return out


def _nested_self(spans: list) -> list:
    """Self time of each span: its length less the spans nested in it on
    its thread."""
    self_ns = [0.0] * len(spans)
    by_thread: dict = {}
    for i, sp in enumerate(spans):
        by_thread.setdefault(sp[4], []).append(i)
    for idx in by_thread.values():
        idx.sort(key=lambda i: (spans[i][2], -spans[i][3]))
        starts = [spans[i][2] for i in idx]
        ends = [spans[i][2] + spans[i][3] for i in idx]
        for j, s in trace._self_times(starts, ends):
            self_ns[idx[j]] = s
    return self_ns


class _Timeline:
    """The window cut at every program span's edges, each piece labelled
    with the shortest span covering it (``UNSPANNED`` where none does)."""

    def __init__(self, spans: list, t0: float, t1: float):
        live = [sp for sp in spans if sp[2] < t1 and sp[2] + sp[3] > t0]
        cuts = {t0, t1}
        for sp in live:
            cuts.update((max(sp[2], t0), min(sp[2] + sp[3], t1)))
        self.edges = np.array(sorted(cuts), dtype=np.float64)
        self.labels = [UNSPANNED] * (len(self.edges) - 1)
        for sp in sorted(live, key=lambda sp: -sp[3]):      # shortest last
            lo, hi = np.searchsorted(self.edges, [max(sp[2], t0),
                                                  min(sp[2] + sp[3], t1)])
            self.labels[lo:hi] = [sp[0]] * (hi - lo)

    def split(self, a: float, b: float) -> dict:
        """Nanoseconds of [a, b) under each label."""
        out: dict = {}
        lo = max(int(np.searchsorted(self.edges, a, side="right")) - 1, 0)
        for j in range(lo, len(self.labels)):
            s, e = max(a, self.edges[j]), min(b, self.edges[j + 1])
            if s >= b:
                break
            if e > s:
                out[self.labels[j]] = out.get(self.labels[j], 0.0) + (e - s)
        return out


def _gaps(events: dict, t0: float, t1: float) -> list:
    """Each device plane's idle gaps in the window as (length, start,
    end, harness label), in the order ``trace.reduce`` finds them."""
    out = []
    for dev in events["devices"].values():
        d = trace._device_arrays(dev)
        s2, e2 = np.maximum(d["start"], t0), np.minimum(d["end"], t1)
        live = np.nonzero(e2 > s2)[0]
        merged = trace._merge([[s2[i], e2[i]] for i in live])
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a >= trace.SHORT_GAP_NS:
                out.append((b - a, a, b, trace._label(events["host"], 0.5 * (a + b))))
            elif b > a:
                out.append((b - a, a, b, "between ops"))
    return out


def reduce(events: dict, top: int = 10) -> dict:
    """``trace.reduce`` with the program spans' keys (see the module)."""
    out = trace.reduce(events, top)
    spans = events.get("spans", [])
    win = next(h for h in events["host"] if h[0] == "window")
    t0, t1 = win[2], win[2] + win[3]
    n_dev = len(events["devices"])

    for row in out["batches"]:
        row["span_s"] = {}
    harness = {int(h[1].get("batch", -1)): h for h in events["host"]
               if h[0] == "engine"}
    for row in out["batches"]:
        _, _, s, d = harness[row["batch"]]
        inside = [sp for sp in spans if s <= sp[2] and sp[2] + sp[3] <= s + d]
        threads = {sp[4] for sp in inside if sp[0] == "engine"}
        for name, _, _, dur, th in inside:
            if th in threads:
                row["span_s"][name] = row["span_s"].get(name, 0.0) + dur * 1e-9

    whole = [sp for sp in spans if t0 <= sp[2] and sp[2] + sp[3] <= t1]
    table: dict = {}
    for sp, self_ns in zip(whole, _nested_self(whole)):
        t = table.setdefault(sp[0], {"count": 0, "total_s": 0.0, "self_s": 0.0})
        t["count"] += 1
        t["total_s"] += sp[3] * 1e-9
        t["self_s"] += self_ns * 1e-9
    out["spans"] = table

    timeline = _Timeline(spans, t0, t1)
    idle: dict = {}
    gaps = []
    for length, a, b, label in _gaps(events, t0, t1):
        parts = timeline.split(a, b)
        for name, ns in parts.items():
            idle[name] = idle.get(name, 0.0) + ns * 1e-9 / n_dev
        best = max(parts, key=parts.get) if parts else UNSPANNED
        gaps.append((length, label if best == UNSPANNED else best))
    out["idle_by_span"] = idle
    out["idle_gaps"] = [[label, length * 1e-9] for length, label
                        in sorted(gaps, key=lambda g: -g[0])[:top]]
    return out


# ------------------------------------------------------------- readings


def _step_rows(reduced):
    """The traced batches that ran a step program (those
    ``executor_host_ms.sat`` averages over)."""
    rows = reduced["batches"] if reduced else []
    return [r for r in rows if r["step_s"] > 0 and "span_s" in r]


def span_ms(reduced, name: str):
    """ms of program span ``name`` per traced batch with a step program;
    None where no such batch holds the span."""
    rows = _step_rows(reduced)
    if not any(name in r["span_s"] for r in rows):
        return None
    return sum(r["span_s"].get(name, 0.0) for r in rows) / len(rows) * 1e3


EXECUTOR_CHILDREN = ("executor.gather_table", "executor.prewarm",
                     "executor.launch", "executor.wait", "executor.rerank")


def executor_uncovered_ms(reduced):
    """The part of ``executor_host_ms.sat`` no executor child span covers,
    per batch: the harness's executor span less every child span, the wait
    included (its device part is the step that metric subtracts)."""
    rows = _step_rows(reduced)
    if not rows or not any("executor" in r["span_s"] for r in rows):
        return None
    return sum(r["executor_s"] - sum(r["span_s"].get(c, 0.0)
                                     for c in EXECUTOR_CHILDREN)
               for r in rows) / len(rows) * 1e3


def frontend_ms(reduced):
    """The front-end's own ms per batch in the window: ``frontend.batch``
    less the ``engine`` span nested in it (its self time plus
    ``frontend.complete``)."""
    table = reduced["spans"] if reduced else {}
    fb = table.get("frontend.batch")
    if not fb or not fb["count"]:
        return None
    own = fb["self_s"] + table.get("frontend.complete", {}).get("self_s", 0.0)
    return own / fb["count"] * 1e3


def idle_unspanned_pct(reduced):
    """Device idle under no program span, as a share of the window (%)."""
    if not reduced or "idle_by_span" not in reduced or not reduced["spans"]:
        return None
    return reduced["idle_by_span"].get(UNSPANNED, 0.0) / reduced["window_s"] * 100.0


def gather_fill_pct(start: dict, end: dict):
    """Live rows of the window's gather tables over the padded rows the
    step scanned (%), from the executor's ``rows_gathered`` and
    ``rows_scanned`` counters at the window's start and end."""
    if "rows_scanned" not in start or "rows_scanned" not in end:
        return None
    scanned = end["rows_scanned"] - start["rows_scanned"]
    if scanned <= 0:
        return None
    return (end["rows_gathered"] - start["rows_gathered"]) / scanned * 100.0
