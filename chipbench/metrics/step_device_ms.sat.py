"""Ring step: device time of the jitted step program per dispatch (ms),
from the trace's "XLA Modules" events inside each traced batch."""


def read(run):
    rows = run.trace["batches"] if run.trace else []
    rows = [r for r in rows if r["step_s"] > 0]
    if not rows:
        return None
    return sum(r["step_s"] for r in rows) / len(rows) * 1e3
