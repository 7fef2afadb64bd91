"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A traced run wraps its traced work in ``jax.profiler.TraceAnnotation``
``WINDOW`` and records an XSpace (``*.xplane.pb``). This module reads it
with ``jax.profiler.ProfileData`` into plain event tuples and reduces:

* busy: the union of the intervals in which a device operation ran
  (events of the ``XLA Ops`` line of each device plane), clipped to the
  window and averaged over the devices;
* op time by name, and by program (``XLA Modules`` line);
* idle between consecutive runs of one program: the part of each gap
  between two runs of a program that no device operation covers;
* ``breakdown``: the device operations that took most time, and the
  longest idle gaps, each named by the host event that covers most of it.
"""
from __future__ import annotations

import glob
import os
import warnings
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

WINDOW = "bench/window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
# operations whose interval holds other operations of the same line
CONTAINERS = ("while", "conditional", "call")


def short_name(hlo: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


class Event(NamedTuple):
    name: str
    start_ns: float
    end_ns: float
    stats: Tuple[Tuple[str, str], ...] = ()


class Trace(NamedTuple):
    devices: Dict[str, Dict[str, List[Event]]]  # plane -> line -> events
    host: List[Event]  # every host event, all threads
    window: Tuple[float, float]  # ns


def _events(line, with_stats: bool) -> List[Event]:
    out = []
    with warnings.catch_warnings():
        # jaxlib's event-stats type warns on construction (no __module__)
        warnings.simplefilter("ignore", DeprecationWarning)
        for e in line.events:
            start = float(e.start_ns)
            stats = (tuple((str(k), str(v)) for k, v in dict(e.stats).items())
                     if with_stats else ())
            out.append(Event(str(e.name), start, start + float(e.duration_ns), stats))
    return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` into a ``Trace``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices[plane.name] = {ln.name: _events(ln, True) for ln in plane.lines}
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                host.extend(_events(ln, False))
    # The window is the benchmark's span, widened to every device event:
    # the device clock runs about a millisecond off the host's, and the
    # trace holds nothing but the traced work.
    ends = [(e.start_ns, e.end_ns) for e in host if e.name == WINDOW]
    ends += [(e.start_ns, e.end_ns) for d in devices.values()
             for ln in d.values() for e in ln]
    if not ends:
        raise ValueError(f"{path}: no window span and no device event")
    window = (min(a for a, _ in ends), max(b for _, b in ends))
    return Trace(devices, host, window)


def union(intervals: Iterable[Tuple[float, float]],
          lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    clipped = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                     if b > lo and a < hi)
    merged: List[Tuple[float, float]] = []
    for a, b in clipped:
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _first(trace: Trace) -> Optional[str]:
    return sorted(trace.devices)[0] if trace.devices else None


def ops(trace: Trace, plane: str) -> List[Event]:
    return trace.devices[plane].get(OPS_LINE, [])


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    lo, hi = trace.window
    per = [_length(union(((e.start_ns, e.end_ns) for e in ops(trace, d)), lo, hi))
           for d in trace.devices]
    return sum(per) / len(per) / 1e9 if per else 0.0


def window_s(trace: Trace) -> float:
    return (trace.window[1] - trace.window[0]) / 1e9


def idle_share(trace: Trace) -> Optional[float]:
    """1 - busy / window, as a fraction (None with no device plane)."""
    if not trace.devices:
        return None
    return 1.0 - busy_s(trace) / window_s(trace)


def _matches(e: Event, name: str) -> bool:
    return name in e.name or any(name in v for _, v in e.stats)


def op_seconds(trace: Trace, name: Optional[str] = None) -> Dict[str, float]:
    """Device seconds per operation (its short name) inside the window,
    summed over devices; with ``name``, only operations whose HLO text or
    stats hold it."""
    lo, hi = trace.window
    out: Dict[str, float] = {}
    for d in trace.devices:
        for e in ops(trace, d):
            if e.end_ns <= lo or e.start_ns >= hi:
                continue
            if name is not None and not _matches(e, name):
                continue
            k = short_name(e.name)
            out[k] = out.get(k, 0.0) + (min(e.end_ns, hi) - max(e.start_ns, lo)) / 1e9
    return out


def op_count(trace: Trace, name: str) -> int:
    lo, hi = trace.window
    return sum(1 for d in trace.devices for e in ops(trace, d)
               if lo <= e.start_ns < hi and _matches(e, name))


def module_runs(trace: Trace, program: str, plane: Optional[str] = None) -> List[Event]:
    """Runs of the programs whose module name holds ``program``, in order,
    on one device plane (the first by default)."""
    plane = plane or _first(trace)
    if plane is None:
        return []
    lo, hi = trace.window
    mods = trace.devices[plane].get(MODULES_LINE, [])
    return sorted((e for e in mods if program in e.name and lo <= e.start_ns < hi),
                  key=lambda e: e.start_ns)


def idle_between(trace: Trace, program: str) -> Tuple[float, int]:
    """(idle seconds, number of gaps) between consecutive runs of
    ``program`` on the first device: the part of each gap that no device
    operation covers."""
    plane = _first(trace)
    if plane is None:
        return 0.0, 0
    runs = module_runs(trace, program, plane)
    spans = [(e.start_ns, e.end_ns) for e in ops(trace, plane)]
    idle = 0.0
    for a, b in zip(runs, runs[1:]):
        if b.start_ns > a.end_ns:
            idle += (b.start_ns - a.end_ns) - _length(union(spans, a.end_ns, b.start_ns))
    return idle / 1e9, max(0, len(runs) - 1)


def busy_within(trace: Trace, program: str) -> float:
    """Device-busy seconds inside the runs of ``program`` (first device)."""
    plane = _first(trace)
    if plane is None:
        return 0.0
    spans = [(e.start_ns, e.end_ns) for e in ops(trace, plane)]
    return sum(_length(union(spans, r.start_ns, r.end_ns))
               for r in module_runs(trace, program, plane)) / 1e9


def idle_gaps(trace: Trace, plane: Optional[str] = None) -> List[Tuple[float, float]]:
    """Idle intervals of one device inside the window, longest first."""
    plane = plane or _first(trace)
    if plane is None:
        return []
    lo, hi = trace.window
    busy = union(((e.start_ns, e.end_ns) for e in ops(trace, plane)), lo, hi)
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return sorted(gaps, key=lambda g: g[0] - g[1])


def host_label(trace: Trace, a: float, b: float) -> str:
    """The host event that best names [a, b]: the shortest one covering at
    least half of it, else the one that covers most of it."""
    best, best_key = "unattributed", None
    for e in trace.host:
        if e.name == WINDOW:
            continue
        over = min(e.end_ns, b) - max(e.start_ns, a)
        if over <= 0:
            continue
        key = (0, e.end_ns - e.start_ns) if over >= 0.5 * (b - a) else (1, -over)
        if best_key is None or key < best_key:
            best, best_key = e.name, key
    return best


def breakdown(trace: Trace, top: int = 10) -> dict:
    """Top device operations by time (control flow that holds other
    operations, such as ``while``, left out so nothing counts twice) and
    the longest idle gaps."""
    by_op = sorted(((k, v) for k, v in op_seconds(trace).items()
                    if k.rsplit(".", 1)[0] not in CONTAINERS),
                   key=lambda kv: -kv[1])[:top]
    gaps = idle_gaps(trace)[:top]
    return {
        "device_ops": [[name, sec] for name, sec in by_op],
        "idle_gaps": [[host_label(trace, a, b), (b - a) / 1e9] for a, b in gaps],
    }
