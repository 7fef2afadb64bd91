"""Device time by engine phase, the path driver's host spans, and the
six readers built on them, on synthetic traces with a planted phase map
and on a real profile of a tiny path on the CPU."""
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import harness, phases, trace
from bench.trace import Event, Trace

ROOT = Path(__file__).resolve().parents[2]
DEV = "/device:TPU:0"
# a planted map of op names to phases, as the program's would be
PLANTED = {"fusion.1": "fw/loop/score", "fusion.4": "fw/loop/update",
           "while.1": "fw/loop", "fusion.2": "fw/init", "fusion.3": "fw/gap"}


def _op(name, a, b):
    return Event(f"%{name} = f32[] {name.split('.')[0]}()", a, b)


def _path_trace():
    # two runs of the batched program, [0, 40) and [60, 90); another
    # program's op between them; the driver's spans on the host
    ops = [_op("fusion.2", 0, 5), _op("while.1", 5, 30), _op("fusion.1", 6, 16),
           _op("fusion.4", 16, 26), _op("fusion.3", 30, 38), _op("copy.1", 38, 40),
           _op("fusion.9", 45, 50),
           _op("fusion.2", 60, 64), _op("fusion.1", 64, 80), _op("fusion.3", 80, 90)]
    mods = [Event("jit_solve_batched(1)", 0, 40), Event("jit_other(2)", 45, 50),
            Event("jit_solve_batched(1)", 60, 90)]
    host = [Event(trace.WINDOW, 0, 100), Event("fw_path_batched", 0, 100),
            Event("fw_path_batched/solve", 0, 40),
            Event("fw_path_batched/solve", 60, 90)]
    return Trace({DEV: {trace.OPS_LINE: ops, trace.MODULES_LINE: mods}}, host,
                 (0.0, 100.0))


def _fit_trace():
    ops = [_op("fusion.1", 0, 10), _op("fusion.3", 10, 20), _op("fusion.7", 22, 28),
           _op("fusion.1", 30, 45), _op("fusion.3", 45, 50)]
    mods = [Event("jit_solve(7)", 0, 20), Event("jit_solve_batched(1)", 22, 28),
            Event("jit_solve(7)", 30, 50)]
    return Trace({DEV: {trace.OPS_LINE: ops, trace.MODULES_LINE: mods}},
                 [Event(trace.WINDOW, 0, 50)], (0.0, 50.0))


def _path_ctx(entry="path"):
    points = [SimpleNamespace(iterations=i) for i in (3, 10, 4, 6)]
    return SimpleNamespace(entry=entry, trace=_path_trace(), answers=[{}] * 4,
                           path_results=[SimpleNamespace(points=points,
                                                         loop_turns=10)])


def _fit_ctx(entry="fit"):
    return SimpleNamespace(entry=entry, trace=_fit_trace(), path_results=[],
                           answers=[{"iterations": 5}, {"iterations": 10}])


@pytest.fixture
def planted(monkeypatch):
    monkeypatch.setattr(phases, "_cache", {})
    monkeypatch.setattr(phases, "op_phases", lambda entry: dict(PLANTED))


def test_split_gives_each_instant_to_the_innermost_op(planted):
    by_phase, ops = phases.split(_path_trace(), "solve_batched", PLANTED)
    assert by_phase == pytest.approx({
        "fw/init": 9e-9, "fw/loop": 5e-9, "fw/loop/score": 26e-9,
        "fw/loop/update": 10e-9, "fw/gap": 18e-9, phases.UNATTRIBUTED: 2e-9})
    assert ops[phases.UNATTRIBUTED] == {"copy.1": pytest.approx(2e-9)}
    # the other program's op lies outside every run
    assert "fusion.9" not in {op for d in ops.values() for op in d}
    assert phases.loop_seconds(by_phase) == pytest.approx(41e-9)


# reader, the context it reads, the value it should read
READINGS = [
    ("loop_us_per_turn.path", _path_ctx, 1e6 * 41e-9 / 10),
    ("init_ms_per_point.path", _path_ctx, 1e3 * 9e-9 / 4),
    ("gap_ms_per_point.path", _path_ctx, 1e3 * 18e-9 / 4),
    # idle outside the solves: [40, 60) less [45, 50), and [90, 100)
    ("driver_idle_ms.path", _path_ctx, 1e3 * 25e-9 / 4),
    ("loop_us_per_turn.fit", _fit_ctx, 1e6 * 25e-9 / 15),
    ("gap_ms_per_fit.fit", _fit_ctx, 1e3 * 15e-9 / 2),
]


@pytest.mark.parametrize("name,make,value", READINGS, ids=[r[0] for r in READINGS])
def test_reader(planted, monkeypatch, name, make, value):
    reader = harness.metric_reader(ROOT, name)
    assert reader.read(make()) == pytest.approx(value)
    other = "fit" if make is _path_ctx else "path"
    assert reader.read(make(other)) is None  # off its entry
    # a program with no phase map and no driver spans (an older one)
    monkeypatch.setattr(phases, "_cache", {})
    monkeypatch.setattr(phases, "op_phases", lambda entry: None)
    ctx = make()
    ctx.trace = ctx.trace._replace(host=[e for e in ctx.trace.host
                                         if not e.name.startswith("fw_path")])
    for r in ctx.path_results:
        del r.loop_turns
    assert reader.read(ctx) is None


def test_path_spans_reach_the_profile(tmp_path):
    """A tiny path under the profiler on the CPU: the driver's spans land
    in the profile's host plane, nested as the driver opens them."""
    from repro.core import path as path_lib
    from repro.core.solver_config import FWConfig

    rng = np.random.default_rng(0)
    X = rng.standard_normal((300, 40)).astype(np.float32)
    y = rng.standard_normal(40).astype(np.float32)
    cfg = FWConfig(delta=1.0, kappa=20, max_iters=50, report_gap=True)
    grid = np.geomspace(0.1, 5.0, 4)
    path_lib.fw_path_batched(X, y, grid, cfg, lane_width=2)  # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            path_lib.fw_path_batched(X, y, grid, cfg, lane_width=2)
    finally:
        jax.profiler.stop_trace()
    tr = trace.load(trace.find_xplane(str(tmp_path)))

    def spans(name):
        return [(e.start_ns, e.end_ns) for e in tr.host if e.name == name]

    def inside(inner, outer):
        return all(any(a <= s and e <= b for a, b in outer) for s, e in inner)

    (path,) = spans("fw_path_batched")
    chunks = spans("fw_path_batched/chunk")
    assert len(chunks) == 2 and inside(chunks, [path])
    for child in ("warm_start", "solve", "collect"):
        found = spans(f"fw_path_batched/{child}")
        assert len(found) == 2 and inside(found, chunks)
    assert inside(spans("solve_batched"), spans("fw_path_batched/solve"))
    assert phases.driver_idle_s(tr) is None  # no device plane on the CPU
