"""Device-idle milliseconds per grid point while the path driver works
on the host: idle time inside the program's ``fw_path_batched`` span
and outside its ``fw_path_batched/solve`` spans (warm start, host
copies, sparsify, checkpoint; ``bench/phases.py``)."""
from bench import phases


def read(ctx):
    if ctx.entry != "path" or ctx.trace is None or not ctx.path_results:
        return None
    idle = phases.driver_idle_s(ctx.trace)
    if idle is None:
        return None
    points = sum(len(r.points) for r in ctx.path_results)
    return 1e3 * idle / points
