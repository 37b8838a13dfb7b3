#!/usr/bin/env python3
"""On-chip benchmark of the served vector-search path: one cell, one run.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``chipbench/configs/*.json``: shapes, precision, executor and scheduler
settings, generator) and a traffic mix (``chipbench/traffic/<name>.json``).
One run:

1. refuses to run without a TPU, or with fewer chips than the cell asks;
2. keeps JAX's compile cache at ``$JAX_COMPILATION_CACHE_DIR``, else at
   ``.jax_cache`` in the checkout;
3. builds the deployment from ``--seed`` (corpus, IVF index built on the
   chip, ``HarmonyServer(backend="spmd")``) behind a ``ServingFrontend``,
   which compiles and runs every (qb, cap) bucket of the configured ladder;
4. serves a warm-up batch mix, then drives the mix's closed loop through
   ``ServingFrontend.submit`` for ``--seconds``;
5. checks a seeded sample of the answers served in the window, and every
   id's list, against the plain reference (``chipbench/reference.py``);
6. prints one JSON line last: the cell's end-to-end metrics
   (``--trace 0``) or its per-layer metrics read from a profiler trace of
   the window's last seconds (``--trace 1``).

Set-up (``setup_s``) runs from process start to the window's start.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import gen, spec, trace as tracing, work  # noqa: E402
from chipbench.reference import INVALID, Reference  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".chipbench_trace"
TRACE_SECONDS = 2.0     # the traced part of the window, at its end
TRACE_LEAD_S = 1.0      # the profiler starts this long before it, to settle
DRAIN_S = 60.0          # how long answers may come after the window closes
RECALL_QUERIES = 64     # served answers whose recall@k is measured


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def say(name: str, **fields) -> None:
    print(f"{name}: " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def require_chip(jax, chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX reports {len(devs)}")
    return devs


def use_compile_cache(jax) -> str:
    """The persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` where set
    (JAX reads it itself), else one fixed path in the checkout. Every
    program is cached, however quickly it compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (as ``chip_smoke.py`` counts them)."""

    def __init__(self, jax):
        self.compiles, self.compile_s, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# ------------------------------------------------------------ deployment


def build(cfg: dict, seed: int):
    """Corpus, IVF index (k-means on the default device) and the server."""
    from repro.config import HarmonyConfig
    from repro.core import SegmentedIndex, build_ivf
    from repro.serve import HarmonyServer
    from repro.serve.executor import ExecutorConfig

    t0 = time.perf_counter()
    x = gen.corpus(seed, cfg["rows"], cfg["dim"], cfg["generator"])
    data_s = time.perf_counter() - t0
    hcfg = HarmonyConfig(
        dim=cfg["dim"], nlist=cfg["nlist"], nprobe=cfg["nprobe"], topk=cfg["k"],
        metric=cfg["metric"], rerank_factor=cfg["rerank_factor"],
        quant_blocks=cfg["quant_blocks"],
        kmeans_iters=cfg["kmeans_iters"], kmeans_seed=int(seed) % (2**31 - 1),
    )
    t0 = time.perf_counter()
    index = build_ivf(x, hcfg)
    build_s = time.perf_counter() - t0
    ecfg = dict(cfg["executor"])
    ecfg["qb_buckets"] = tuple(ecfg["qb_buckets"])
    server = HarmonyServer(SegmentedIndex.from_static(index), n_nodes=1,
                           backend="spmd", executor_cfg=ExecutorConfig(**ecfg),
                           precision=cfg["precision"])
    say("build", rows=cfg["rows"], dim=cfg["dim"], nlist=cfg["nlist"],
        data_s=data_s, build_s=build_s, train_s=index.build_times["train"])
    return x, index, server


# -------------------------------------------------------------- traffic


class Timeline:
    """Per request: when it was sent and answered (perf_counter seconds),
    its batch, whether it failed, and its answer."""

    def __init__(self, n: int, k: int):
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.batch = np.full(n, -1, np.int64)
        self.failed = np.zeros(n, bool)
        self.ids = np.full((n, k), -1, np.int64)
        self.scores = np.full((n, k), np.inf, np.float32)
        self.used = 0
        self._all_done = threading.Event()
        self._open = 0
        self._mu = threading.Lock()

    def submit(self, fe, request, i: int, on_done=None) -> None:
        import jax

        with self._mu:
            self._open += 1
            self._all_done.clear()
            self.used = max(self.used, i + 1)
        with jax.profiler.TraceAnnotation("chipbench.submit"):
            self.sent[i] = time.perf_counter()
            fut = fe.submit(request)
        fut.add_done_callback(lambda f: self._finish(i, f, on_done))

    def _finish(self, i: int, fut, on_done) -> None:
        import jax

        with jax.profiler.TraceAnnotation("chipbench.callback"):
            self.done[i] = time.perf_counter()
            try:
                r = fut.result()
                self.ids[i], self.scores[i], self.batch[i] = r.ids, r.scores, r.batch_id
            except Exception as e:  # noqa: BLE001 - a failed request is counted
                self.failed[i] = True
                print(f"request {i} failed: {e!r}", file=sys.stderr)
            if on_done is not None:
                on_done(i)            # may send the caller's next request
            with self._mu:
                self._open -= 1
                if self._open == 0:
                    self._all_done.set()

    def wait(self, timeout: float) -> bool:
        return self._all_done.wait(timeout)


def sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def closed_loop(fe, make_request, pool, clients, seconds, tracer) -> tuple:
    """``clients`` callers, each sending its next request when its last
    one is answered, until the window closes. Returns (timeline, start)."""
    tl = Timeline(len(pool), fe.k)
    nxt = itertools.count()
    t_start = time.perf_counter()
    t_end = t_start + seconds

    def send(_=None):
        now = time.perf_counter()
        if now >= t_end:
            return
        i = next(nxt)
        if i >= len(pool):
            raise RuntimeError(f"the query pool ({len(pool)}) ran out")
        tl.submit(fe, make_request(pool[i]), i, send)

    for _ in range(clients):
        send()
    sleep_until(t_end - TRACE_SECONDS - TRACE_LEAD_S)
    tracer.start()
    sleep_until(t_end - TRACE_SECONDS)
    tracer.open()
    sleep_until(t_end)
    tracer.close()
    if not tl.wait(DRAIN_S):
        print("closed loop: requests still open after the drain", file=sys.stderr)
    return tl, t_start


class Tracer:
    """Profiler trace of the window's last ``TRACE_SECONDS``, marked by the
    host span ``chipbench.window``; does nothing when not enabled. The
    profiler starts ``TRACE_LEAD_S`` before the span opens, so that its own
    start-up falls outside the span. The span opens and closes on the
    thread that drives the mix."""

    def __init__(self, jax, enabled: bool):
        self.jax, self.enabled = jax, enabled
        self.started, self.span = False, None
        self.path = TRACE_DIR

    def start(self) -> None:
        """Start the profiler."""
        if not self.enabled:
            return
        shutil.rmtree(self.path, ignore_errors=True)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        self.jax.profiler.start_trace(str(self.path), profiler_options=opts)
        self.started = True

    def open(self) -> None:
        """Open the window span."""
        if not self.enabled:
            return
        self.span = self.jax.profiler.TraceAnnotation("chipbench.window")
        self.span.__enter__()

    def close(self) -> None:
        if self.span is not None:
            self.span.__exit__(None, None, None)

    def stop(self):
        """Stop the profiler and reduce its trace; None when not tracing."""
        if not self.started:
            return None
        self.jax.profiler.stop_trace()
        try:
            return tracing.reduce(tracing.load(tracing.find_xplane(str(self.path))))
        finally:
            shutil.rmtree(self.path, ignore_errors=True)


def annotate(jax, server, calls: list) -> None:
    """Host spans around the calls into the engine and the executor (trace
    runs only). Each engine call is recorded with its queries, so the work
    of every traced batch can be counted."""
    engine = server.search_batch
    ex = server.executor
    execute = ex.search_batch
    seq = itertools.count()

    def search_batch(queries, *a, **kw):
        b = next(seq)
        with jax.profiler.TraceAnnotation("chipbench.engine", batch=b):
            res = engine(queries, *a, **kw)
        calls.append((b, np.array(queries, np.float32)))
        return res

    def executor_search(*a, **kw):
        with jax.profiler.TraceAnnotation("chipbench.executor"):
            return execute(*a, **kw)

    server.search_batch = search_batch
    ex.search_batch = executor_search


# -------------------------------------------------------------- metrics


class RunRecord:
    """What one run collected, as the per-layer metric readers see it."""

    def __init__(self, cfg, mix, timeline, t_start, seconds, counters,
                 trace, calls, ref, peak):
        self.cfg, self.mix = cfg, mix
        self.timeline, self.t_start, self.seconds = timeline, t_start, seconds
        self.t_end = t_start + seconds
        self.counters = counters
        self.trace = trace
        self.calls = dict(calls)
        self.reference = ref
        self.peak = peak

    def delta(self, name: str) -> float:
        return self.counters["end"][name] - self.counters["start"][name]

    def batch_work(self, batch: int):
        """(operations, bytes, least seconds) of one traced batch, counted
        over the reference's probe selection."""
        q = self.calls.get(batch)
        if q is None:
            return None
        probes = [ch[0] for ch in self.reference.probe_sets(q)]
        prec = self.cfg["precision"]
        k_out = self.cfg["k"] * (self.cfg["rerank_factor"] if prec == "int8" else 1)
        flops, nbytes = work.batch_work(self.reference.lists.sizes, probes,
                                        self.cfg["dim"], prec, k_out)
        t, bound = work.least_time(flops, nbytes, self.peak, prec)
        return flops, nbytes, t, bound


def counters(server) -> dict:
    st, ex = server.stats, server.executor
    return {"serve_wall_s": st.wall_s, "batches": st.batches,
            "queries": st.queries, "exec_wall_s": ex.wall_s,
            "dispatches": ex.dispatches, "compiles": ex.compiles,
            "tile_skipped": ex.tile_skipped, "tile_total": ex.tile_total}


def end_to_end(names: list, tl: Timeline, t_start: float, seconds: float,
               setup_s: float) -> dict:
    """``qps``: answered requests completed in the window per second."""
    n = tl.used
    answered = ~np.isnan(tl.done[:n]) & ~tl.failed[:n]
    out = {"setup_s": (setup_s, "s"),
           "qps": (float(np.sum(answered & (tl.done[:n] <= t_start + seconds))) / seconds,
                   "queries/s")}
    return {k: {"value": v, "unit": u} for k, (v, u) in out.items() if k in names}


# ---------------------------------------------------------------- checks


def answer_gaps(ref: Reference, q: np.ndarray, ids: np.ndarray, scores: np.ndarray) -> dict:
    """The worst rank and score gaps of the given answers."""
    t0 = time.perf_counter()
    rank, score = ref.gaps(q, ids, scores)
    say("reference", answers=len(q), seconds=time.perf_counter() - t0,
        rank_gap_median=float(np.median(rank)) if len(q) else None,
        score_gap_median=float(np.median(score)) if len(q) else None,
        invalid=int((rank >= INVALID).sum()))
    worst = lambda g: float(g.max()) if len(g) else INVALID   # noqa: E731
    return {"rank_gap": worst(rank), "score_gap": worst(score)}


def check(ref: Reference, pool, tl: Timeline, sample: int, seed: int) -> tuple:
    """Compare a seeded sample of the answers served in the window with the
    reference. Returns (checks, sample indices)."""
    n = tl.used
    answered = np.nonzero(~np.isnan(tl.done[:n]) & ~tl.failed[:n])[0]
    rng = gen.rng_for(seed, gen.STREAM_SAMPLE)
    pick = np.sort(rng.choice(answered, size=min(sample, len(answered)), replace=False))
    gaps = answer_gaps(ref, pool[pick], tl.ids[pick], tl.scores[pick])
    return {"unanswered": float(n - len(answered)), **gaps}, pick


def lists_cover(ref: Reference, index_ids: np.ndarray) -> float:
    """Ids not in exactly one IVF list, plus ids whose list is not their
    nearest centroid's (``Reference.misassigned``)."""
    t0 = time.perf_counter()
    uncovered = int(np.sum(np.bincount(index_ids, minlength=len(ref.x)) != 1))
    wrong = ref.misassigned()
    say("lists", uncovered=uncovered, misassigned=wrong, seconds=time.perf_counter() - t0)
    return float(uncovered + wrong)


def verdict(checks: dict, limits: dict) -> tuple:
    """(each number compared beside its limit, whether all are within)."""
    compared = {n: {"value": checks[n], "limit": limits[n]} for n in limits}
    return compared, all(c["value"] <= c["limit"] for c in compared.values())


def recall(x: np.ndarray, q: np.ndarray, ids: np.ndarray, k: int) -> float:
    """recall@k of served ids against brute force over the whole corpus."""
    q = q.astype(np.float32)
    best = np.full((len(q), k), np.inf)
    best_i = np.zeros((len(q), k), np.int64)
    for lo in range(0, len(x), 1 << 17):
        xb = x[lo:lo + (1 << 17)]
        d = (xb * xb).sum(1)[None, :] - 2.0 * q @ xb.T
        cat = np.concatenate([best, d], 1)
        cat_i = np.concatenate([best_i, np.arange(lo, lo + len(xb))[None].repeat(len(q), 0)], 1)
        sel = np.argpartition(cat, k - 1, axis=1)[:, :k]
        best, best_i = np.take_along_axis(cat, sel, 1), np.take_along_axis(cat_i, sel, 1)
    return float(np.mean([len(set(a) & set(b)) / k for a, b in zip(ids, best_i)]))


# ------------------------------------------------------------------ run


def run(workload: str, seed: int, seconds: float, trace: bool,
        root: Path = ROOT, need_chip: bool = True) -> dict:
    import jax

    from repro.core import SearchRequest
    from repro.serve import SchedulerConfig, ServingFrontend

    bench = spec.benchmark(root)
    cell = spec.cell(bench, workload)
    cfg = spec.config(bench, cell["config"], root)
    mix = spec.traffic(cell["traffic"], root)
    if mix.get("k", cfg["k"]) != cfg["k"]:
        raise ValueError(f"traffic k {mix['k']} differs from the configuration's {cfg['k']}")
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}: the harness drives a closed loop")
    devs = require_chip(jax, cell["chips"]) if need_chip else jax.devices()
    cache = use_compile_cache(jax)
    comp = CompileCounter(jax)
    say("device", platform=devs[0].platform, kind=repr(devs[0].device_kind),
        count=len(devs), jax=jax.__version__, cache=cache)
    peak = work.peaks(devs[0].device_kind) if need_chip else None

    x, index, server = build(cfg, seed)
    dim, k = cfg["dim"], cfg["k"]
    sched = SchedulerConfig(max_retries=0, **cfg["scheduler"])
    pool = gen.queries(seed, int(mix["pool"]), dim, cfg["generator"], mix)
    warm = gen.queries(seed, int(mix["warm_requests"]), dim, cfg["generator"], mix,
                       stream=gen.STREAM_WARM)
    calls: list = []
    tracer = Tracer(jax, trace)
    t0 = time.perf_counter()
    with ServingFrontend(server, sched, k=k) as fe:      # compiles the ladder
        ladder_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        # warm-up: the mix's own queries, sent at once, so that full
        # batches and a partial one run before the window
        wt = Timeline(len(warm), k)
        for i, v in enumerate(warm):
            wt.submit(fe, SearchRequest(vector=v), i)
        wt.wait(DRAIN_S)
        say("warmup", ladder_s=ladder_s, requests=len(warm),
            seconds=time.perf_counter() - t0,
            failed=int(wt.failed.sum()), compiles=comp.compiles,
            cache_hits=comp.cache_hits, compile_s=comp.compile_s)
        if trace:
            annotate(jax, server, calls)
        compiles0 = comp.compiles
        start = counters(server)
        setup_s = time.perf_counter() - T_PROCESS
        make = lambda v: SearchRequest(vector=v)          # noqa: E731
        tl, t_start = closed_loop(fe, make, pool, int(mix["clients"]), seconds, tracer)
        end = counters(server)
    window_compiles = comp.compiles - compiles0
    reduced = tracer.stop()
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    say("window", seconds=seconds, requests=tl.used, compiles=window_compiles,
        batches=end["batches"] - start["batches"],
        executor_compiles=end["compiles"] - start["compiles"],
        tile_skipped=end["tile_skipped"] - start["tile_skipped"],
        tile_total=end["tile_total"] - start["tile_total"])

    centers = np.array(index.centers)
    list_of_id = np.full(len(x), -1, np.int64)
    list_of_id[index.ids] = index.cluster_of
    index_ids = np.asarray(index.ids)
    del fe, server, index
    gc.collect()
    ref = Reference(x, centers, list_of_id, cfg)
    checks, pick = check(ref, pool, tl, int(mix["check_sample"]), seed)
    checks["lists_cover"] = lists_cover(ref, index_ids)
    rq = pick[:RECALL_QUERIES]
    if len(rq):
        say("recall", queries=len(rq), k=k, recall_at_k=recall(x, pool[rq], tl.ids[rq], k))

    record = RunRecord(cfg, mix, tl, t_start, seconds, {"start": start, "end": end},
                       reduced, calls, ref, peak)
    n_att = tl.used
    failed = int(n_att - np.sum(~np.isnan(tl.done[:n_att]) & ~tl.failed[:n_att]))
    if trace:
        metrics = {}
        for m in spec.metrics(bench, workload, per_layer=True):
            v = spec.reader(m["name"], root)(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        names = [m["name"] for m in spec.metrics(bench, workload, per_layer=False)]
        metrics = end_to_end(names, tl, t_start, seconds, setup_s)
    compared, correct = verdict(checks, cfg["check"])
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    out = {"correct": correct, "attempted": n_att, "failed": failed,
           "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        rows = reduced["batches"]
        say("trace", ops=reduced["n_ops"], batches=len(rows),
            kernel_total_s=json.dumps(reduced["kernel_total_s"]),
            kernel_in_batches_s=json.dumps({k: sum(r["kernel_s"][k] for r in rows)
                                            for k in reduced["kernel_total_s"]}),
            step_in_batches_s=sum(r["step_s"] for r in rows),
            overlap_s=reduced["overlap_s"],
            idle_by_host=json.dumps(reduced["idle_by_host"]))
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}; this benchmark runs only on a TPU", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
