"""Device microseconds per turn of the batched engine loop in the traced
path: device time of the ``fw/loop`` phase (score and update steps
included) inside the batched solve program's runs, over
``PathResult.loop_turns``. The per-lane warm starts and gaps are not
in it (``bench/phases.py``)."""
from bench import phases


def read(ctx):
    if ctx.entry != "path" or not ctx.path_results:
        return None
    turns = sum(getattr(r, "loop_turns", 0) for r in ctx.path_results)
    by_phase = phases.seconds(ctx, phases.PATH_ENTRY)
    if not turns or by_phase is None:
        return None
    return 1e6 * phases.loop_seconds(by_phase) / turns
