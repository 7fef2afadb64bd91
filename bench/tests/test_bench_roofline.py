"""The peaks table and the bytes each measured kernel needs."""
import json

import pytest

from bench import roofline


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")


def test_v5e_peaks_and_source():
    table = json.loads(roofline.PEAKS_FILE.read_text())
    assert "TPU v5e" in table["source"]
    v5e = roofline.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12


def test_sparse_colstats_bytes_at_e2006_log1p():
    # 4,272,227 features in 16,689 blocks of 256, 67 slots each, m = 16,087:
    # values and rows, 4 + 4 bytes a slot: 4,272,384 * 67 * 8 = 2,289,997,824
    # y once: 16,087 * 4 = 64,348
    # two f32 statistics per padded feature: 4,272,384 * 8 = 34,179,072
    assert roofline.sparse_colstats_bytes(16_689, 256, 67, 16_087) == 2_324_241_244


def test_roofline_share_is_least_time_over_measured():
    # 819e9 bytes at 819 GB/s take one second: measured in two, 50%
    assert roofline.roofline_share(819e9, 2.0, "TPU v5 lite") == pytest.approx(50.0)
