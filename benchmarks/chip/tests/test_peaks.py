"""The peaks table."""
import pytest

from harness.device import peaks


def test_v5e_peaks():
    p = peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


def test_unknown_kind_raises():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        peaks("TPU v9 imaginary")
