"""Device-idle milliseconds between consecutive runs of the batched
solve program, per grid point of the traced path: the path driver's host
work between chunks (alpha copies to the host, sparsify, warm start)."""
from bench import trace

PROGRAM = "solve_batched"


def read(ctx):
    if ctx.entry != "path" or ctx.trace is None:
        return None
    idle, gaps = trace.idle_between(ctx.trace, PROGRAM)
    if gaps == 0:
        return None
    return 1e3 * idle / len(ctx.answers)
