"""Idle share (%) of the device over the profiler trace of one whole path:
1 - (union of device operation intervals) / window."""
from bench import trace


def read(ctx):
    if ctx.entry != "path" or ctx.trace is None:
        return None
    idle = trace.idle_share(ctx.trace)
    return None if idle is None else 100.0 * idle
