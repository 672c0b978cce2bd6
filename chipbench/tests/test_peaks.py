import pytest

from chipbench import peaks


def test_v5e_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_unknown_kind_is_refused():
    with pytest.raises(LookupError):
        peaks.peaks("TPU v9 imaginary")
