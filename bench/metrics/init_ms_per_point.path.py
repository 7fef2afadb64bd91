"""Device milliseconds per grid point of the traced path in the
``fw/init`` phase of the batched solve program: each lane's warm-start
``X alpha0`` and co-state, and the backend padding (``bench/phases.py``)."""
from bench import phases


def read(ctx):
    if ctx.entry != "path" or not ctx.path_results:
        return None
    by_phase = phases.seconds(ctx, phases.PATH_ENTRY)
    if by_phase is None:
        return None
    points = sum(len(r.points) for r in ctx.path_results)
    return 1e3 * by_phase.get("fw/init", 0.0) / points
