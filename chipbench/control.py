#!/usr/bin/env python3
"""The control of the output check: the reference put in the program's
place, one precision lower than the configuration states, must come out
as not correct.

* IVF-Flat (float32 at full precision): probe selection and distances with
  bfloat16x3 products (the ``high`` precision: hi*hi + hi*lo + lo*hi with
  hi = v rounded to bfloat16 and lo = v - hi rounded to bfloat16,
  accumulated in float32). The rounding is done on the bits, so that no
  compiler can fold a float32 -> bfloat16 -> float32 round trip away, and
  it computes the same on any backend.
* IVF-SQ8 (int8 codes): the same two stages on an int4 grid (codes in
  [-7, 7]).

    python chipbench/control.py --workload <cell> --seeds 11,12,13 [--modes control,answer,half]

For each seed it builds the cell's deployment (corpus and index, as a run
does), answers the first ``check_sample`` queries of the cell's mix with
the control, and prints the numbers a run compares beside the
configuration's limits, and ``correct`` as a run decides it
(``run.verdict``). The modes ``answer`` and
``half`` read a fault instead, planted in the reference put in the
program's place: one row of every answer altered where it is produced,
or the second half of every 32-query batch left unanswered. The
benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import gen, run as harness, spec  # noqa: E402
from chipbench.reference import Reference, sq_encode, sq_grid  # noqa: E402

INT4_LEVELS = 7


def _bf16x3(a, b):
    """a @ b.T with bfloat16x3 products, float32 accumulation."""
    import jax.numpy as jnp
    from jax import lax

    def round8(v):
        """v rounded to nearest-even at 8 significant bits (exact in
        bfloat16), as a float32 -> bfloat16 conversion rounds."""
        bits = lax.bitcast_convert_type(v, jnp.uint32)
        bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
        return lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000), jnp.float32)

    def split(v):
        hi = round8(v)
        return hi.astype(jnp.bfloat16), round8(v - hi).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)
    dot = lambda u, v: lax.dot_general(  # noqa: E731
        u, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def flat_control(x, centroids, list_of_id, q, nprobe, k, block=128):
    """IVF-Flat answers with every product in bfloat16x3: (ids, scores)."""
    import jax
    import jax.numpy as jnp

    xd = jnp.asarray(x)
    xn = jnp.sum(xd * xd, axis=1)
    cd = jnp.asarray(np.asarray(centroids, np.float32))
    cn = jnp.sum(cd * cd, axis=1)
    lists = jnp.asarray(list_of_id.astype(np.int32))

    @jax.jit
    def answer(qb):
        qn = jnp.sum(qb * qb, axis=1)
        dc = qn[:, None] - 2.0 * _bf16x3(qb, cd) + cn[None, :]
        probes = jax.lax.top_k(-dc, nprobe)[1]                       # [b, nprobe]
        d = qn[:, None] - 2.0 * _bf16x3(qb, xd) + xn[None, :]       # [b, rows]
        probed = (lists[None, :, None] == probes[:, None, :]).any(-1)
        neg, ids = jax.lax.top_k(jnp.where(probed, -d, -jnp.inf), k)
        return ids, -neg

    ids, sc = [], []
    for lo in range(0, len(q), block):
        qb = np.zeros((block, q.shape[1]), np.float32)
        part = q[lo:lo + block]
        qb[: len(part)] = part
        i, s = answer(jnp.asarray(qb))
        ids.append(np.asarray(i)[: len(part)])
        sc.append(np.asarray(s)[: len(part)])
    return np.concatenate(ids).astype(np.int64), np.concatenate(sc)


def sq_control(x, centroids, list_of_id, q, nprobe, k, kp, levels=INT4_LEVELS):
    """IVF-SQ with ``levels`` code range: stage 1 by integer code distance
    over the probed lists, exact float32 re-rank of its ``kp`` rows."""
    ref = Reference(x, centroids, list_of_id, {"k": k, "nprobe": nprobe,
                                                 "precision": "fp32"})
    grid = sq_grid(x, levels)
    codes = sq_encode(x, grid, levels)
    ids = np.empty((len(q), k), np.int64)
    sc = np.empty((len(q), k), np.float32)
    for i, ch in enumerate(ref.probe_sets(q)):
        cand = np.concatenate([ref.lists.rows(int(lst)) for lst in ch[0]])
        diff = codes[cand] - sq_encode(q[i], grid, levels)[None]
        keep = cand[np.argsort((diff * diff).sum(1), kind="stable")[:kp]]
        d = ((x[keep] - q[i][None]) ** 2).sum(1, dtype=np.float32)
        top = np.argsort(d, kind="stable")[:k]
        ids[i], sc[i] = keep[top], d[top]
    return ids, sc


def faulty(ref: Reference, q: np.ndarray, fault: str, batch: int = 32):
    """The reference's own answers, with a fault planted in them unless
    ``fault`` is ``reference``."""
    ids, d2 = ref.answers(q)
    ids, sc = ids.copy(), d2.astype(np.float32)
    if fault == "reference":
        pass
    elif fault == "answer":
        ids[:, 0] = (ids[:, 0] + 1) % len(ref.x)
    elif fault == "half":
        drop = (np.arange(len(q)) % batch) >= batch // 2
        ids[drop], sc[drop] = -1, np.inf
    else:
        raise ValueError(f"unknown fault {fault!r}")
    return ids, sc


def control_gaps(cfg: dict, mix: dict, seed: int, n: int, modes: list) -> list:
    """Build the deployment of ``seed`` and, for each mode, answer ``n``
    queries of the mix with the control (``control``) or with the
    reference carrying a fault, and judge them against the reference."""
    from repro.config import HarmonyConfig
    from repro.core import build_ivf

    x = gen.corpus(seed, cfg["rows"], cfg["dim"], cfg["generator"])
    index = build_ivf(x, HarmonyConfig(
        dim=cfg["dim"], nlist=cfg["nlist"], nprobe=cfg["nprobe"], topk=cfg["k"],
        kmeans_iters=cfg["kmeans_iters"], kmeans_seed=int(seed) % (2**31 - 1)))
    list_of_id = np.full(len(x), -1, np.int64)
    list_of_id[index.ids] = index.cluster_of
    q = gen.queries(seed, n, cfg["dim"], cfg["generator"], mix)
    ref = Reference(x, index.centers, list_of_id, cfg)
    cover = harness.lists_cover(ref, index.ids)
    out = []
    for mode in modes:
        t0 = time.perf_counter()
        if mode != "control":
            ids, sc = faulty(ref, q, mode)
        elif cfg["precision"] == "int8":
            ids, sc = sq_control(x, index.centers, list_of_id, q, cfg["nprobe"],
                                 cfg["k"], cfg["k"] * cfg["rerank_factor"])
        else:
            ids, sc = flat_control(x, index.centers, list_of_id, q, cfg["nprobe"],
                                   cfg["k"])
        answer_s = time.perf_counter() - t0
        checks = {"unanswered": 0.0, "lists_cover": cover,
                  **harness.answer_gaps(ref, q, ids, sc)}
        compared, correct = harness.verdict(checks, cfg["check"])
        out.append({"seed": seed, "mode": mode, "answers": n, "answer_s": answer_s,
                    "correct": correct, "checks": compared})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--modes", default="control",
                    help="comma-separated: control, answer, half, reference")
    args = ap.parse_args(argv)
    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    mix = spec.traffic(cell["traffic"])
    for s in args.seeds.split(","):
        for row in control_gaps(cfg, mix, int(s), int(mix["check_sample"]),
                                args.modes.split(",")):
            print(json.dumps({"workload": args.workload, **row}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
