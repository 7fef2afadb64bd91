"""Device milliseconds per traced fit in the ``fw/gap`` phase of the
solve program: the certified duality gap, one full gradient pass
(``bench/phases.py``)."""
from bench import phases


def read(ctx):
    if ctx.entry != "fit" or not ctx.answers:
        return None
    by_phase = phases.seconds(ctx, phases.FIT_ENTRY)
    if by_phase is None:
        return None
    return 1e3 * by_phase.get("fw/gap", 0.0) / len(ctx.answers)
