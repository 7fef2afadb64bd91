"""Seconds per fit: from the window's start to the end of its last fit,
over the number of fits (whole rounds of the grid). A fit ends when the caller holds alpha as
index/value pairs, the objective and the gap on the host."""


def read(ctx):
    if ctx.entry != "fit" or ctx.trace is not None:
        return None
    return ctx.window_s / len(ctx.answers)
