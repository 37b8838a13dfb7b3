"""Kernels: device time of the distance kernel (``partial_distance_update``
or ``int8_partial_distance_update``) per dispatch (ms), over the traced
batches."""


def read(run):
    rows = run.trace["batches"] if run.trace else []
    rows = [r for r in rows if r["kernel_s"]["distance"] > 0]
    if not rows:
        return None
    return sum(r["kernel_s"]["distance"] for r in rows) / len(rows) * 1e3
