"""The X^T w roofline metrics of the certified gap's kernel."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import harness, trace, xtw
from bench.roofline import COLSTATS_KERNEL
from bench.trace import Event, Trace

ROOT = Path(__file__).resolve().parents[2]
METRICS = {"fw_sparse_xtw_roofline.fit": "fit", "fw_sparse_xtw_roofline.path": "path"}
NB, BS, NNZ, M = 16_689, 256, 67, 16_087  # e2006-log1p's block-ELL shapes
CALL = '%{} = f32[16689,1,256] custom-call(f32[16689,256,67] %p0), custom_call_target="tpu_custom_call"'


def test_sparse_xtw_bytes_at_e2006_log1p():
    # values and rows, 4 + 4 bytes a slot: 4,272,384 * 67 * 8 = 2,289,997,824
    # w once: 16,087 * 4 = 64,348
    # one f32 per padded feature: 4,272,384 * 4 = 17,089,536
    assert xtw.sparse_xtw_bytes(NB, BS, NNZ, M) == 2_307_151_708


@pytest.mark.parametrize("kernel,other", [(xtw.XTW_KERNEL, COLSTATS_KERNEL),
                                          (COLSTATS_KERNEL, xtw.XTW_KERNEL)])
def test_kernel_names_do_not_match_each_other(kernel, other):
    assert not trace._matches(Event(CALL.format(kernel + ".1"), 0, 1), other)


def _ctx(entry, tr, kind="block_ell"):
    shapes = {"values": (NB, BS, NNZ)} if kind == "block_ell" else {"xt": (635_376, 186)}
    return SimpleNamespace(entry=entry, trace=tr, data={"kind": kind, "m": M},
                           shapes=shapes, device_kind="TPU v5 lite")


def _trace():
    # two kernel calls of 2 s each, and the fusion that negates the
    # kernel's output (it names the kernel among its operands)
    ops = [Event(CALL.format("fw_sparse_xtw.1"), 0, 2e9),
           Event("%fusion.7 = f32[4272227] fusion(f32[16689,1,256] %fw_sparse_xtw.1)",
                 2e9, 3e9),
           Event(CALL.format("fw_sparse_xtw.1"), 3e9, 5e9)]
    return Trace({"/device:TPU:0": {trace.OPS_LINE: ops}}, [], (0.0, 6e9))


@pytest.mark.parametrize("name,entry", sorted(METRICS.items()))
def test_share_counts_the_kernels_own_calls(name, entry):
    reader = harness.metric_reader(ROOT, name)
    assert xtw.kernel_runs(_trace()) == (pytest.approx(4.0), 2)
    want = 100.0 * xtw.sparse_xtw_bytes(NB, BS, NNZ, M) * 2 / 819e9 / 4.0
    assert reader.read(_ctx(entry, _trace())) == pytest.approx(want)
    other = "path" if entry == "fit" else "fit"
    assert reader.read(_ctx(other, _trace())) is None


@pytest.mark.parametrize("name,entry", sorted(METRICS.items()))
def test_none_on_dense_data_and_without_a_trace(name, entry):
    reader = harness.metric_reader(ROOT, name)
    assert reader.read(_ctx(entry, _trace(), kind="dense")) is None
    assert reader.read(_ctx(entry, None)) is None
    # a program without the kernel (the XLA gather) has no call to read
    tr = Trace({"/device:TPU:0": {trace.OPS_LINE: [Event("%fusion.3 = f32[]", 0, 1e9)]}},
               [], (0.0, 1e9))
    assert reader.read(_ctx(entry, tr)) is None
