"""The table of peaks: keyed by device kind, an unknown kind is an error."""
import pytest

from bench.spec import device_peaks


def test_v5e_peaks():
    p = device_peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5"])
def test_unknown_kind_is_an_error(kind):
    with pytest.raises(KeyError):
        device_peaks(kind)
