"""Peaks of each device kind and the bytes each measured kernel needs.

The bytes are what the algorithm has to move for one call, computed from
the shapes of the benchmark's own data, whatever implements the call: a
later change to a kernel changes its time, never its count.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
F32 = 4
I32 = 4


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peak table entry for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def sparse_colstats_bytes(nblocks: int, block_size: int, nnz_max: int, m: int) -> int:
    """z_j^T y and ||z_j||^2 for every stored column of a block-ELL
    design: read every values and rows slot once and y once, write two
    f32 statistics per (padded) feature."""
    slots = nblocks * block_size * nnz_max
    return slots * (F32 + I32) + m * F32 + 2 * nblocks * block_size * F32


def roofline_share(bytes_moved: float, seconds: float, device_kind: str) -> float:
    """Share (%) of the HBM roofline: the least time the bytes need at the
    peak bandwidth, over the measured time. Bound by bandwidth: these
    kernels do about one multiply-add per four bytes."""
    least = bytes_moved / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / seconds


COLSTATS_KERNEL = "fw_sparse_colstats"


def colstats_share(ctx):
    """Share (%) of the HBM roofline that the sparse column-statistics
    kernel reaches in a traced run: the bytes each call needs times its
    calls, over the device time of its operations, over the peak
    bandwidth. None where the trace holds no call of it."""
    from bench import trace

    if ctx.trace is None or ctx.data["kind"] != "block_ell":
        return None
    seconds = sum(trace.op_seconds(ctx.trace, COLSTATS_KERNEL).values())
    calls = trace.op_count(ctx.trace, COLSTATS_KERNEL)
    if seconds <= 0 or calls == 0:
        return None
    nblocks, block_size, nnz_max = ctx.shapes["values"]
    per_call = sparse_colstats_bytes(nblocks, block_size, nnz_max, ctx.data["m"])
    return roofline_share(per_call * calls, seconds, ctx.device_kind)
