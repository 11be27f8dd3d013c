"""The table of peaks: one row per device kind, and no default."""

import pytest

from bench import spec


def test_v5e_peaks():
    p = spec.peaks_of("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5"])
def test_unknown_device_is_an_error(kind):
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        spec.peaks_of(kind)
