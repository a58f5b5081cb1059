"""A run on anything but a known TPU stops before any result."""
import pytest

from harness import device


def test_known_tpu_passes():
    peaks = device.load_peaks()
    p = device.check("tpu", "TPU v5 lite", 1, 1, peaks)
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("platform,kind,count,chips", [
    ("cpu", "cpu", 1, 1),                  # not a TPU
    ("tpu", "TPU v9 imaginary", 1, 1),     # not in the table of peaks
    ("tpu", "TPU v5 lite", 1, 4),          # fewer chips than the cell asks
])
def test_refused(platform, kind, count, chips):
    with pytest.raises(device.DeviceError):
        device.check(platform, kind, count, chips, device.load_peaks())
