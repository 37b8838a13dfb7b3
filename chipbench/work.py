"""Work that IVF search needs, and the least time a chip could take for it.

The counts follow the IVF semantics, not the implementation: a later
change to the gather, the chunking or the padding does not change them.
Per served batch:

* operations = 2 * D * sum over its queries of the rows in that query's
  probed lists (one multiply and one add per dimension and pair);
* bytes = the distinct rows of the lists the batch probes, each read once
  (D elements of the stored width, a 4-byte norm and a 4-byte id), plus
  the queries read and the k results written (4-byte score, 4-byte id).
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).resolve().parent / "peaks.json"
# bytes per stored element, and the peak that bounds the distance kernel's
# multiply-adds, for each precision a configuration can state
ELEMENT_BYTES = {"fp32": 4, "int8": 1}
PEAK_KEY = {"fp32": "bf16_flops_per_s", "int8": "int8_ops_per_s"}


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """Published peaks of one chip. A device missing from the table is an
    error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def batch_work(sizes: np.ndarray, probes: list, dim: int, precision: str,
               k_out: int) -> tuple:
    """(operations, bytes) of one batch. ``sizes`` [n_lists] rows per list;
    ``probes`` one array of list ids per query of the batch."""
    rows_scanned = sum(int(sizes[p].sum()) for p in probes)
    distinct = np.unique(np.concatenate(probes)) if probes else np.zeros(0, int)
    row_bytes = dim * ELEMENT_BYTES[precision] + 4 + 4
    nq = len(probes)
    flops = 2.0 * dim * rows_scanned
    nbytes = (float(sizes[distinct].sum()) * row_bytes
              + nq * dim * ELEMENT_BYTES[precision] + nq * k_out * 8)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict, precision: str) -> tuple:
    """(seconds, bound): the larger of operations over the compute peak and
    bytes over the memory bandwidth, and which of the two it is. fp32
    distances are held to the bf16 peak, since the chip publishes no f32
    one: that makes the time a floor, so a share of it cannot read high."""
    t_ops = flops / peak[PEAK_KEY[precision]]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
