"""Executor: ``SpmdExecutor.search_batch`` host time per dispatch (ms):
its span less the step program's device time, over the traced batches
(gather table, tau prewarm, uploads, waiting for results, re-rank)."""


def read(run):
    rows = run.trace["batches"] if run.trace else []
    rows = [r for r in rows if r["step_s"] > 0]
    if not rows:
        return None
    return sum(r["executor_s"] - r["step_s"] for r in rows) / len(rows) * 1e3
