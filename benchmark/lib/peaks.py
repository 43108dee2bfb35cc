"""Published per-chip peaks, the one table every share in this benchmark
is computed against.  A copy of ``paddle_tpu/core/peaks.py`` (PR 21): the
yardstick lives here so that a change to the program cannot move it.

Keyed by ``jax.devices()[0].device_kind``.  A device that is not in the
table is an error, never a default.
"""

DEVICE_PEAKS = {
    "TPU v5 lite": {          # how jax names a v5e chip
        "bf16_flops": 197.0e12,
        "int8_ops": 393.0e12,
        "hbm_bytes_per_s": 819.0e9,
        "hbm_bytes": 16.0e9,
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
            "no published peaks for device_kind %r (known: %s): add a "
            "sourced row to benchmark/lib/peaks.py" % (
                device_kind, ", ".join(sorted(DEVICE_PEAKS)))) from None
