"""opcount.py on two shapes, and the table of peaks."""

import pytest

from benchmarks import opcount


def test_step_min_bytes_two_shapes():
    assert opcount.step_min_bytes(65536, 64) == 33_554_432
    assert opcount.step_min_bytes(16384, 64) == 8_388_608
    with pytest.raises(ValueError):
        opcount.step_min_bytes(0, 64)


def test_roofline_share():
    # 33.5 MB at 819 GB/s is 40.97 us; a step of 1 ms is 4.097 % of it
    pct = opcount.step_roofline_pct(65536, 64, 1e-3, "TPU v5 lite")
    assert pct == pytest.approx(4.0970, abs=1e-3)
    # a step at the roofline reads 100 %
    least = 33_554_432 / 819e9
    assert opcount.step_roofline_pct(65536, 64, least, "TPU v5 lite") == pytest.approx(100.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        opcount.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        opcount.step_roofline_pct(64, 64, 1e-3, "cpu")
