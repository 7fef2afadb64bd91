"""Device time by engine phase inside the runs of one entry's program.

The program names its phases with ``jax.named_scope`` and keeps, per
entry point (``solve``, ``solve_batched``), what it needs to compile a
map from each op's short name to its phase (``repro.obs.phases``). Here
each device op of the first device inside a run of the entry's module
(``XLA Modules`` events named ``jit_<entry>(...)``) gets the phase of
its name. An op that holds others (a ``while``) keeps only the time no
op inside it covers: each instant of a run goes to the innermost op
running then (the latest to start). Time of an op the map does not
name goes to ``UNATTRIBUTED``.

A program without that map (one older than it) gives None, and every
reader built on this module then reports nothing.

    python3 -m bench.phases --workload <name> --seed <n>

from the root of a checkout, on the chip, runs one traced unit of the
cell (as ``bench/run.py --trace 1``) and prints the split of each
entry's device time by phase, the share the map names, and the longest
ops of each phase.
"""
from __future__ import annotations

import bisect
import heapq
import sys
from typing import Dict, List, Optional, Tuple

from bench import trace

UNATTRIBUTED = "unattributed"
PATH_ENTRY, FIT_ENTRY = "solve_batched", "solve"
_cache: dict = {}


def op_phases(entry: str) -> Optional[Dict[str, str]]:
    """The program's ``{op short name: phase}`` for ``entry``, or None
    where the program has no such map or ran nothing under ``entry``."""
    try:
        from repro.obs import phases
    except ImportError:
        return None
    return phases.op_phases(entry) or None


def _exclusive(events: List[Tuple[float, float, str]]) -> Dict[str, float]:
    """Nanoseconds per label, each instant to the latest-started event
    covering it."""
    out: Dict[str, float] = {}
    edges = sorted({t for a, b, _ in events for t in (a, b)})
    events = sorted(events)
    active: list = []
    k = 0
    for lo, hi in zip(edges, edges[1:]):
        while k < len(events) and events[k][0] <= lo:
            a, b, label = events[k]
            heapq.heappush(active, (-a, b, label))
            k += 1
        while active and active[0][1] <= lo:
            heapq.heappop(active)
        if active:
            label = active[0][2]
            out[label] = out.get(label, 0.0) + (hi - lo)
    return out


def split(tr: trace.Trace, entry: str, phase_map: Dict[str, str]
          ) -> Tuple[Dict[str, float], Dict[str, Dict[str, float]]]:
    """(seconds by phase, {phase: {op: seconds}}) inside the runs of
    ``jit_<entry>(`` on the first device."""
    runs = trace.module_runs(tr, f"jit_{entry}(")
    if not runs:
        return {}, {}
    plane = sorted(tr.devices)[0]
    starts = [r.start_ns for r in runs]
    per_run: List[list] = [[] for _ in runs]
    for e in trace.ops(tr, plane):
        i = bisect.bisect_right(starts, e.start_ns) - 1
        if i < 0 or e.start_ns >= runs[i].end_ns:
            continue
        per_run[i].append((e.start_ns, min(e.end_ns, runs[i].end_ns),
                           trace.short_name(e.name)))
    by_op: Dict[str, float] = {}
    for events in per_run:
        for op, ns in _exclusive(events).items():
            by_op[op] = by_op.get(op, 0.0) + ns / 1e9
    by_phase: Dict[str, float] = {}
    ops: Dict[str, Dict[str, float]] = {}
    for op, sec in by_op.items():
        phase = phase_map.get(op, UNATTRIBUTED)
        by_phase[phase] = by_phase.get(phase, 0.0) + sec
        ops.setdefault(phase, {})[op] = sec
    return by_phase, ops


def seconds(ctx, entry: str) -> Optional[Dict[str, float]]:
    """Device seconds by phase in the traced run's ``entry`` program, or
    None (no trace, no map, no run)."""
    if ctx.trace is None:
        return None
    if _cache.get("trace") is not ctx.trace:  # one trace at a time
        _cache.clear()
        _cache["trace"] = ctx.trace
    if entry not in _cache:
        phase_map = op_phases(entry)
        by_phase = split(ctx.trace, entry, phase_map)[0] if phase_map else {}
        _cache[entry] = by_phase or None
    return _cache[entry]


def loop_seconds(by_phase: Dict[str, float]) -> float:
    """The engine loop's seconds, its score and update steps included."""
    return sum(v for k, v in by_phase.items() if k.startswith("fw/loop"))


def driver_idle_s(tr: trace.Trace, span: str = "fw_path_batched",
                  inner: str = "fw_path_batched/solve") -> Optional[float]:
    """Seconds the first device sat idle inside the host spans ``span``
    and outside their ``inner`` spans; None where the trace has no
    ``span``."""
    outer = [(e.start_ns, e.end_ns) for e in tr.host if e.name == span]
    if not outer or not tr.devices:
        return None
    solves = trace.union(((e.start_ns, e.end_ns) for e in tr.host
                          if e.name == inner), tr.window[0], tr.window[1])
    plane = sorted(tr.devices)[0]
    busy = [(e.start_ns, e.end_ns) for e in trace.ops(tr, plane)]
    idle = 0.0
    for lo, hi in trace.union(outer, tr.window[0], tr.window[1]):
        edges = [lo] + [x for ab in trace.union(solves, lo, hi) for x in ab] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                idle += (b - a) - sum(y - x for x, y in trace.union(busy, a, b))
    return idle / 1e9


def main(argv=None) -> int:
    """Run one traced unit of a cell and print its phase split."""
    import argparse
    import json

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--top", type=int, default=5)
    args = ap.parse_args(argv)
    from bench import run

    # run.run keeps its trace to itself: hold on to what it loads
    kept = []
    load = trace.load
    trace.load = lambda path: kept.append(load(path)) or kept[-1]
    try:
        out = run.run(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(run.TRACED_FIT_SECONDS), "--trace", "1"])
    finally:
        trace.load = load
    tr = kept[-1]
    report = {"workload": args.workload, "seed": args.seed, "result": out}
    for entry in (PATH_ENTRY, FIT_ENTRY):
        phase_map = op_phases(entry)
        if not phase_map or not trace.module_runs(tr, f"jit_{entry}("):
            continue
        by_phase, ops = split(tr, entry, phase_map)
        total = sum(by_phase.values())
        report[entry] = {
            "busy_s": trace.busy_within(tr, f"jit_{entry}("),
            "attributed_share": 1 - by_phase.get(UNATTRIBUTED, 0.0) / total,
            "phases": {
                k: {"seconds": v,
                    "top": sorted(ops[k].items(), key=lambda kv: -kv[1])[:args.top]}
                for k, v in sorted(by_phase.items(), key=lambda kv: -kv[1])},
        }
    print(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
