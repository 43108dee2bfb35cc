"""Device places.

Parity: reference platform/place.h:75 (CPUPlace:25, CUDAPlace:35).  The GPU
place is replaced by TPUPlace; `CUDAPlace` is kept as a migration alias so
reference user code runs unchanged.  A Place resolves to a jax.Device.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax


class Place:
    device_type = None

    def __init__(self, device_id=0):
        self.device_id = device_id

    def __eq__(self, other):
        return (type(self) is type(other)
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)

    def jax_device(self):
        devs = _devices_for(self.device_type)
        if not devs:
            raise RuntimeError("no %s devices available" % self.device_type)
        return devs[self.device_id % len(devs)]


def _devices_for(kind):
    # local_devices, not devices: under jax.distributed the global list
    # spans every process, and a Place must resolve to a device THIS
    # process can address (a multi-host run would otherwise pin local
    # work to another host's device id and die on a cross-host reshard)
    if kind == "cpu":
        try:
            return jax.local_devices(backend="cpu")
        except RuntimeError:
            return []
    # "accelerator": whatever the default backend exposes, minus
    # pure-host.  No accelerator means no devices — a TPUPlace never
    # resolves to a host CPU (a CPU run says CPUPlace)
    return [d for d in jax.local_devices() if d.platform != "cpu"]


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    device_type = "accelerator"


# Migration alias for reference user code (platform/place.h:35).
CUDAPlace = TPUPlace


def is_accelerator_available():
    return any(d.platform != "cpu" for d in jax.devices())


def default_place():
    """The Place of jax's default device: TPUPlace on a process that
    holds a chip, CPUPlace on one started with JAX_PLATFORMS=cpu."""
    return TPUPlace() if is_accelerator_available() else CPUPlace()


# ---------------------------------------------------------------------------
# Placement of a computation while it is traced.  Tracers carry no
# device, so whoever places the computation — the executor from its
# Place or mesh, the serving engine from its device — declares it with
# ``placed_on(device)`` inside the traced function, and device-dependent
# lowerings (kernels/dispatch.py) ask ``target_platform()``.
# ---------------------------------------------------------------------------

_PLATFORM = contextvars.ContextVar("paddle_tpu_traced_platform",
                                   default=None)


@contextlib.contextmanager
def placed_on(device):
    """Declare the device the computation traced inside runs on."""
    token = _PLATFORM.set(device.platform)
    try:
        yield
    finally:
        _PLATFORM.reset(token)


def target_platform():
    """Platform ('tpu'/'cpu'/...) of the computation being traced.  One
    nobody placed goes where jax sends it: ``jax.default_device`` when
    set, else the default backend."""
    platform = _PLATFORM.get()
    if platform is not None:
        return platform
    dev = jax.config.jax_default_device
    if dev is not None:
        return getattr(dev, "platform", dev)   # a Device or a platform name
    return jax.devices()[0].platform
