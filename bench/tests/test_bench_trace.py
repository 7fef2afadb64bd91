"""The reduction from a profiler trace to busy, idle and kernel time."""
from pathlib import Path

import pytest

from bench import trace
from bench.trace import Event, Trace

CHIP_TRACE = Path(__file__).resolve().parent / "data" / "chip_trace.xplane.pb"


def _synthetic():
    # device ops: [0,10) [5,20) [30,40) [60,70), window [0, 100)
    ops = [Event("fusion.1", 0, 10), Event("fw_sparse_colstats", 5, 20),
           Event("fusion.2", 30, 40), Event("fusion.3", 60, 70)]
    mods = [Event("jit_solve_batched(1)", 0, 40), Event("jit_solve_batched(1)", 60, 70),
            Event("jit_other(2)", 45, 50)]
    host = [Event(trace.WINDOW, 0, 100), Event("driver/host_copy", 40, 60),
            Event("outer", 0, 100)]
    dev = {"/device:TPU:0": {trace.OPS_LINE: ops, trace.MODULES_LINE: mods}}
    return Trace(dev, host, (0.0, 100.0))


def test_union_merges_and_clips():
    assert trace.union([(0, 10), (5, 20), (30, 40)], 2, 35) == [(2, 20), (30, 35)]
    assert trace.union([], 0, 1) == []


def test_busy_and_idle():
    tr = _synthetic()
    assert trace.busy_s(tr) == pytest.approx(40e-9)
    assert trace.window_s(tr) == pytest.approx(100e-9)
    assert trace.idle_share(tr) == pytest.approx(0.6)


def test_kernel_time_by_name():
    tr = _synthetic()
    assert trace.op_seconds(tr, "fw_sparse_colstats") == {"fw_sparse_colstats": pytest.approx(15e-9)}
    assert trace.op_count(tr, "fw_sparse_colstats") == 1


def test_idle_between_runs_of_one_program():
    tr = _synthetic()
    idle, gaps = trace.idle_between(tr, "solve_batched")
    assert gaps == 1
    assert idle == pytest.approx(20e-9)  # [40, 60): nothing ran
    # busy inside the two runs: [0,20) + [30,40) + [60,70)
    assert trace.busy_within(tr, "solve_batched") == pytest.approx(40e-9)


def test_breakdown_names_gaps_by_host_work():
    tr = _synthetic()
    b = trace.breakdown(tr)
    assert b["device_ops"][0] == ["fw_sparse_colstats", pytest.approx(15e-9)]
    assert b["idle_gaps"][0] == ["outer", pytest.approx(30e-9)]  # [70, 100)
    assert ["driver/host_copy", pytest.approx(20e-9)] in b["idle_gaps"]


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5 lite chip: two runs of the batched
    solve program on a small block-ELL design, 50 ms of host spinning
    between them, inside the benchmark's window span."""
    tr = trace.load(str(CHIP_TRACE))
    assert list(tr.devices) == ["/device:TPU:0"]
    busy, window = trace.busy_s(tr), trace.window_s(tr)
    assert 0 < busy < window
    runs = trace.module_runs(tr, "solve_batched")
    assert len(runs) == 2
    idle, gaps = trace.idle_between(tr, "solve_batched")
    assert gaps == 1
    assert 0.05 <= idle < 0.05 + 0.02
    assert trace.op_count(tr, "fw_sparse_colstats") == 2
    assert list(trace.op_seconds(tr, "fw_sparse_colstats")) == ["fw_sparse_colstats.1"]
    b = trace.breakdown(tr)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][1] >= 0.05
