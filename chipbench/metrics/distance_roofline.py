"""Kernels: the distance kernel's share of its roofline (%): the least
time the chip could take for the traced batches' IVF work
(``chipbench/work.py``: the larger of operations over the compute peak
and bytes over HBM bandwidth) over the summed device time of the
distance-kernel events in them."""


def read(run):
    rows = run.trace["batches"] if run.trace else []
    least = kernel = 0.0
    for r in rows:
        w = run.batch_work(r["batch"])
        if w is None or r["kernel_s"]["distance"] <= 0:
            continue
        least += w[2]
        kernel += r["kernel_s"]["distance"]
    return least / kernel * 100.0 if kernel > 0 else None
