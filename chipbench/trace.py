"""Reduce a profiler trace of one run to the numbers the per-layer metrics read.

Two steps, so that the second can be tested on a small recorded trace:

* ``load`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps plain
  lists: each TPU plane's step programs ("XLA Modules") and operations
  ("XLA Ops"), and the host spans the harness annotated (names starting
  ``chipbench.``, with their arguments).
* ``reduce`` clips them to the traced window and returns busy time, the
  kernels' device time per served batch, the costliest operations and the
  longest idle gaps labelled with what the host was doing.

Kernels are found by the stable names of their jitted wrappers, which the
HLO custom call of each Pallas kernel carries.
"""

from __future__ import annotations

import glob
import os
import re

HOST_PREFIX = "chipbench."
KERNELS = {
    "distance": re.compile(r"^%?(int8_)?partial_distance_update(\.\d+)?$"),
    "topk": re.compile(r"^%?running_topk_update(\.\d+)?$"),
}
# idle gaps shorter than this lie between the operations of one step and
# are not looked up among the host spans
SHORT_GAP_NS = 10_000
_SUFFIX = re.compile(r"\.\d+$")


def op_name(text: str) -> str:
    """HLO instruction name of a trace event ("%fusion.3 = f32[..] ..." ->
    "%fusion.3")."""
    return text.split(" = ", 1)[0]


def family(name: str) -> str:
    """An operation's name without its '%' and numeric suffix."""
    return _SUFFIX.sub("", name.lstrip("%"))


def kernel_of(name: str):
    for k, pat in KERNELS.items():
        if pat.match(name):
            return k
    return None


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> dict:
    """Plain event lists from an ``.xplane.pb`` (see ``events``)."""
    from jax.profiler import ProfileData

    return events(ProfileData.from_file(path))


def events(data) -> dict:
    """Plain event lists from a ``jax.profiler.ProfileData``: {"devices":
    {plane: {"modules": [[name, start_ns, dur_ns]], "ops": [...]}},
    "host": [[span, args, start_ns, dur_ns]]}."""
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if key is None:
                    continue
                for e in line.events:
                    name = e.name if key == "modules" else op_name(e.name)
                    dev[key].append([name, e.start_ns, e.duration_ns])
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        out["host"].append([e.name[len(HOST_PREFIX):],
                                            {k: v for k, v in e.stats},
                                            e.start_ns, e.duration_ns])
    return out


def _merge(intervals: list) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _label(host: list, t: float) -> str:
    """What the host was doing at ``t``: the shortest harness span that
    covers it, or "idle host" where none does."""
    best = None
    for name, _, s, d in host:
        if name != "window" and s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "idle host"


def _self_times(starts, ends):
    """(index, self time) of each interval: its length less the intervals
    nested in it, as a loop op holds its body's ops. ``starts`` sorted."""
    out, stack = [], []            # stack: [index, end, self time]
    for i, (s, e) in enumerate(zip(starts, ends)):
        while stack and s >= stack[-1][1]:
            out.append(tuple(stack.pop()[::2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([i, e, e - s])
    out += [tuple(x[::2]) for x in stack]
    return out


def _device_arrays(dev: dict) -> dict:
    """Operation starts, ends, families and kernel labels as sorted arrays
    (names repeat, so each distinct one is parsed once)."""
    import numpy as np

    ops = sorted(dev["ops"], key=lambda o: o[1])
    fam_of, kern_of = {}, {}
    for name, _, _ in ops:
        if name not in fam_of:
            fam_of[name], kern_of[name] = family(name), kernel_of(name)
    return {
        "start": np.array([o[1] for o in ops], dtype=np.float64),
        "end": np.array([o[1] + o[2] for o in ops], dtype=np.float64),
        "family": [fam_of[o[0]] for o in ops],
        "kernel": [kern_of[o[0]] for o in ops],
        "modules": sorted((m[1], m[2]) for m in dev["modules"]),
    }


def reduce(events: dict, top: int = 10) -> dict:
    """Numbers of the traced window (the host span ``chipbench.window``).

    ``busy_s`` is the union of operation intervals, averaged over the TPU
    planes. ``batches`` has one entry per ``chipbench.engine`` span that
    lies wholly in the window: its batch number, its own and its executor
    span's length, and the device time of the step programs and of each
    kernel inside it."""
    import numpy as np

    win = [h for h in events["host"] if h[0] == "window"]
    if not win:
        raise ValueError("the trace has no chipbench.window span")
    t0, t1 = win[0][2], win[0][2] + win[0][3]
    if not events["devices"]:
        raise ValueError("the trace has no TPU plane")
    devices = [_device_arrays(d) for d in events["devices"].values()]
    n_dev = len(devices)
    busy, op_time, gaps, summed = 0.0, {}, [], 0.0
    kernel_total = {k: 0.0 for k in KERNELS}
    for dev in devices:
        s2 = np.maximum(dev["start"], t0)
        e2 = np.minimum(dev["end"], t1)
        live = np.nonzero(e2 > s2)[0]
        summed += float(np.sum(e2[live] - s2[live])) * 1e-9
        for i in live:
            if dev["kernel"][i]:
                kernel_total[dev["kernel"][i]] += (e2[i] - s2[i]) * 1e-9 / n_dev
        for i, self_ns in _self_times(s2[live], e2[live]):
            f = dev["family"][live[i]]
            op_time[f] = op_time.get(f, 0.0) + self_ns * 1e-9
        merged = _merge([[s2[i], e2[i]] for i in live])
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a >= SHORT_GAP_NS:
                gaps.append((b - a, _label(events["host"], 0.5 * (a + b))))
            elif b > a:
                gaps.append((b - a, "between ops"))
    batches = []
    for name, args, s, d in events["host"]:
        if name != "engine" or s < t0 or s + d > t1:
            continue
        row = {"batch": int(args.get("batch", -1)), "engine_s": d * 1e-9,
               "executor_s": sum(ed for en, _, es, ed in events["host"]
                                 if en == "executor" and s <= es <= s + d) * 1e-9,
               "step_s": 0.0, "kernel_s": {k: 0.0 for k in KERNELS}}
        for dev in devices:
            row["step_s"] += sum(md for ms, md in dev["modules"]
                                 if s <= ms <= s + d) * 1e-9 / n_dev
            lo, hi = np.searchsorted(dev["start"], [s, s + d], side="left")
            for i in range(lo, hi):
                k = dev["kernel"][i]
                if k:
                    row["kernel_s"][k] += (dev["end"][i] - dev["start"][i]) * 1e-9 / n_dev
        batches.append(row)
    by_label: dict = {}
    for length, label in gaps:
        by_label[label] = by_label.get(label, 0.0) + length * 1e-9 / n_dev
    return {
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": busy / n_dev,
        "batches": sorted(batches, key=lambda r: r["batch"]),
        "device_ops": sorted(([k, v / n_dev] for k, v in op_time.items()),
                             key=lambda r: -r[1])[:top],
        "idle_gaps": [[label, length * 1e-9] for length, label
                      in sorted(gaps, key=lambda g: -g[0])[:top]],
        "n_ops": sum(len(d["start"]) for d in devices),
        # whole durations of the kernels' events in the window, and how far
        # all events' durations exceed the busy time (nested or overlapping)
        "kernel_total_s": kernel_total,
        "overlap_s": summed / n_dev - busy / n_dev,
        "idle_by_host": by_label,
    }
