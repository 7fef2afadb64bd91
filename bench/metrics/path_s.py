"""Seconds per path: from the window's start to the end of its last
path, over the number of paths (gaps between paths count)."""


def read(ctx):
    if ctx.entry != "path" or ctx.trace is not None:
        return None
    return ctx.window_s / ctx.units
