"""Peaks by device kind and the tile SpMV roofline share, on numbers
worked out by hand."""
import pytest

from bench import roofline


def test_v5e_peaks():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")


def test_spmv_roofline_by_hand():
    p = roofline.peaks("TPU v5 lite")
    # 1e9 edges x 8 B = 8 GB; at 819 GB/s that is 9.768 ms of HBM time;
    # over 10 s of kernel time: 0.09768 %
    assert roofline.spmv_roofline_pct(1e9, 10.0, p) == pytest.approx(
        100 * 8e9 / 819e9 / 10.0)
    assert roofline.spmv_roofline_pct(1e9, 10.0, p) == pytest.approx(
        0.0976801, rel=1e-6)
    # the least time itself reads 100 %
    assert roofline.spmv_roofline_pct(819e9 / 8, 1.0, p) == pytest.approx(
        100.0)
    with pytest.raises(ValueError):
        roofline.spmv_roofline_pct(1.0, 0.0, p)
