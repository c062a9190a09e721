"""The peaks table: v5e's published numbers, and an unknown device is an
error."""
import pytest

from benchmarks.chip.peaks import peaks


def test_v5e_peaks():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks("cpu")
