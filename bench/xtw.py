"""The bytes one X^T w over every feature of a block-ELL design needs, and
the share of the HBM roofline that the kernel computing it reaches.

X^T w is the O(nnz) pass of the certified duality gap. Its bytes are what
the pass has to move, whatever implements it: read every values and rows
slot once and w once, write one f32 per (padded) feature.
"""
from __future__ import annotations

from bench import roofline

XTW_KERNEL = "fw_sparse_xtw"


def sparse_xtw_bytes(nblocks: int, block_size: int, nnz_max: int, m: int) -> int:
    slots = nblocks * block_size * nnz_max
    return (slots * (roofline.F32 + roofline.I32) + m * roofline.F32
            + nblocks * block_size * roofline.F32)


def _is_kernel(short: str) -> bool:
    # by the op's own name: an op that reads the kernel's output names it
    # among its operands, and a substring match would count that op too
    return short == XTW_KERNEL or short.startswith(XTW_KERNEL + ".")


def kernel_runs(tr):
    """(device seconds, calls) of the kernel's own operations inside the
    trace's window."""
    from bench import trace

    lo, hi = tr.window
    seconds, calls = 0.0, 0
    for d in tr.devices:
        for e in trace.ops(tr, d):
            if lo <= e.start_ns < hi and _is_kernel(trace.short_name(e.name)):
                seconds += (min(e.end_ns, hi) - e.start_ns) / 1e9
                calls += 1
    return seconds, calls


def xtw_share(ctx):
    """Share (%) of the HBM roofline that ``fw_sparse_xtw`` reaches in a
    traced run: the bytes one X^T w needs times the kernel's calls, over
    their device time, over the peak bandwidth. None where the design is
    not block-ELL or the trace holds no call of the kernel."""
    if ctx.trace is None or ctx.data["kind"] != "block_ell":
        return None
    seconds, calls = kernel_runs(ctx.trace)
    if seconds <= 0 or calls == 0:
        return None
    nblocks, block_size, nnz_max = ctx.shapes["values"]
    per_call = sparse_xtw_bytes(nblocks, block_size, nnz_max, ctx.data["m"])
    return roofline.roofline_share(per_call * calls, seconds, ctx.device_kind)
