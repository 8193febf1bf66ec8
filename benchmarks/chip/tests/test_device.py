"""The peak table and the look for a chip refuse what they do not know."""
import jax
import pytest

from chipbench import device


def test_known_kind_has_published_peaks():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="not in peaks.json"):
        device.peaks("TPU v99 imaginary")


def test_cpu_is_refused():
    assert jax.devices()[0].platform == "cpu"
    with pytest.raises(device.NoChip, match="no TPU"):
        device.tpu_devices(1)
