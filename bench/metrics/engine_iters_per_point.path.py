"""Engine iterations per grid point of the traced path (a count:
PathResult.total_iters over the points)."""


def read(ctx):
    if ctx.entry != "path" or ctx.trace is None or not ctx.path_results:
        return None
    points = sum(len(r.points) for r in ctx.path_results)
    return sum(r.total_iters for r in ctx.path_results) / points
