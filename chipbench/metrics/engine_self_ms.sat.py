"""Engine: ``HarmonyServer.search_batch`` wall less the executor's, per
batch (ms), from the ``ServeStats.wall_s`` and ``SpmdExecutor.wall_s``
counters over the window: probe selection, the workload sample, result
assembly."""


def read(run):
    b = run.delta("batches")
    if not b:
        return None
    return (run.delta("serve_wall_s") - run.delta("exec_wall_s")) / b * 1e3
