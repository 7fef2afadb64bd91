"""Device microseconds per engine-loop turn in the traced fits: device
time of the ``fw/loop`` phase (score and update steps included) inside
the solve program's runs, over the fits' iterations (a fit turns its
loop once an iteration at ``fuse_steps`` 1; ``bench/phases.py``)."""
from bench import phases


def read(ctx):
    if ctx.entry != "fit" or not ctx.answers:
        return None
    by_phase = phases.seconds(ctx, phases.FIT_ENTRY)
    turns = sum(a["iterations"] for a in ctx.answers)
    if by_phase is None or not turns:
        return None
    return 1e6 * phases.loop_seconds(by_phase) / turns
