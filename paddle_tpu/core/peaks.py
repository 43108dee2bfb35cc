"""Published per-chip peaks — the one table utilization is computed
against (bench.py MFU, utils/xplane.py roofline shares, chip_smoke.py).

Keyed by ``jax.devices()[0].device_kind``.  A device that is not in the
table is an error, never a default: a utilization against another
chip's peak is a wrong number under a right name.
"""
from __future__ import annotations

__all__ = ["DEVICE_PEAKS", "V5E", "device_peaks"]

V5E = "TPU v5 lite"          # how jax names a v5e chip

DEVICE_PEAKS = {
    V5E: {
        "bf16_tflops": 197.0,
        "int8_tops": 393.0,
        "hbm_gbps": 819.0,
        "hbm_gb": 16.0,
        "source": 'Google Cloud documentation, "TPU v5e" '
                  "(system architecture: per-chip specifications)",
    },
}


def device_peaks(device_kind):
    """The peaks row for ``device_kind``; unknown kinds raise."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            "no published peaks for device_kind %r (known: %s) — add a "
            "sourced row to paddle_tpu/core/peaks.py rather than "
            "borrowing another chip's" % (
                device_kind, ", ".join(sorted(DEVICE_PEAKS)))) from None
