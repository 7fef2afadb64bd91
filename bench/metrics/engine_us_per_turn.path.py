"""Device-busy microseconds per turn of the batched engine loop in the
traced path: busy time inside the runs of the batched solve program over
the loop turns they made (each chunk turns as often as its slowest lane
iterates). The O(nnz) passes of each chunk are included."""
from bench import trace

PROGRAM = "solve_batched"


def read(ctx):
    if ctx.entry != "path" or ctx.trace is None or not ctx.path_results:
        return None
    runs = len(trace.module_runs(ctx.trace, PROGRAM))
    points = ctx.path_results[0].points
    if runs == 0 or len(ctx.path_results) != 1:
        return None
    lanes = -(-len(points) // runs)
    turns = sum(max(pt.iterations for pt in points[c:c + lanes])
                for c in range(0, len(points), lanes))
    return 1e6 * trace.busy_within(ctx.trace, PROGRAM) / turns
