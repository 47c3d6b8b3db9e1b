import pytest

from bench.lib.peaks import UnknownDevice, peaks_for, roofline_seconds


def test_v5e_peaks():
    p = peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_is_an_error():
    with pytest.raises(UnknownDevice):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")


def test_bound_names_the_larger_term():
    p = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert roofline_seconds(1000.0, 10.0, p) == ("compute", 10.0)
    assert roofline_seconds(10.0, 1000.0, p) == ("memory", 100.0)
