"""Kernels: device time of ``running_topk_update`` per dispatch (ms),
over the traced batches."""


def read(run):
    rows = run.trace["batches"] if run.trace else []
    rows = [r for r in rows if r["kernel_s"]["topk"] > 0]
    if not rows:
        return None
    return sum(r["kernel_s"]["topk"] for r in rows) / len(rows) * 1e3
