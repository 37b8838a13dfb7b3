#!/usr/bin/env python3
"""Find the knee of open-loop traffic on one configuration: the highest
offered rate at which completions keep pace with arrivals.

    python chipbench/sweep.py --config sift1m-ivfflat --zipf 0.99 --seed 5 \
        --seconds 8 --rates 200,300,400,500

One process builds the configuration's deployment once and offers each
rate in turn for ``--seconds``: Poisson arrivals (``gen.arrivals``) of
queries whose mixture components are Zipf-distributed with the given
constant (``--zipf 0`` for uniform), draining between steps. For each step
it prints the queue (requests sent and not answered) half-way and at the
end, the completions per second, the latency percentiles from each
request's due time, the compiles inside the step and the longest pause
between answers. A step keeps pace when the queue at its end is no longer
than one batch. An open-loop cell's rate is set by hand to 0.8 of the
knee; the benchmark's own runs never sweep.
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


def main(argv=None) -> int:
    import jax

    from repro.core import SearchRequest
    from repro.serve import SchedulerConfig, ServingFrontend

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--zipf", type=float, default=0.99)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True, help="comma-separated queries/s")
    args = ap.parse_args(argv)
    cfg = spec.config(spec.benchmark(), args.config)
    mix = ({"components": "zipf", "zipf": args.zipf} if args.zipf > 0
           else {"components": "uniform"})
    harness.require_chip(jax, cfg["chips"])
    harness.use_compile_cache(jax)
    comp = harness.CompileCounter(jax)
    _, _, server = harness.build(cfg, args.seed)
    sched = SchedulerConfig(max_retries=0, **cfg["scheduler"])
    with ServingFrontend(server, sched, k=cfg["k"]) as fe:
        warm = gen.queries(args.seed, 261, cfg["dim"], cfg["generator"], mix,
                           stream=gen.STREAM_WARM)
        wt = harness.Timeline(len(warm), cfg["k"])
        for i, v in enumerate(warm):
            wt.submit(fe, SearchRequest(vector=v), i)
        wt.wait(harness.DRAIN_S)
        for step, rate in enumerate(float(r) for r in args.rates.split(",")):
            seed = args.seed + step + 1
            compiles0 = comp.compiles
            due = gen.arrivals(seed, rate, args.seconds)
            pool = gen.queries(seed, len(due), cfg["dim"], cfg["generator"], mix)
            tl = harness.Timeline(len(due), cfg["k"])
            t0 = time.perf_counter()
            for i, off in enumerate(due):
                harness.sleep_until(t0 + off)
                tl.submit(fe, SearchRequest(vector=pool[i]), i)
            t_end = t0 + args.seconds
            harness.sleep_until(t_end)
            queue_end = int(np.sum(~(tl.done <= t_end)))
            mid = t0 + args.seconds / 2
            queue_mid = int(np.sum((tl.sent <= mid) & ~(tl.done <= mid)))
            tl.wait(harness.DRAIN_S)
            lat = (tl.done - (t0 + due)) * 1e3
            done_in = np.sum(tl.done <= t_end)
            finished = np.sort(tl.done[tl.done <= t_end])
            print(json.dumps({
                "rate": rate, "offered": len(due), "queue_mid": queue_mid,
                "queue_end": queue_end, "completed_per_s": float(done_in / args.seconds),
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "keeps_pace": queue_end <= cfg["scheduler"]["max_batch"],
                "compiles": comp.compiles - compiles0,
                "longest_pause_ms": float(np.diff(finished).max() * 1e3)
                if len(finished) > 1 else None,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
