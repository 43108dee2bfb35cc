"""Device-mesh construction.

Replaces the reference's flat rank map (platform/nccl_helper.h:81
NCCLContextMap: rank = dev_id + trainer_id * ngpus) with a named,
multi-axis jax.sharding.Mesh over which all collectives are expressed.
"""
from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh

__all__ = ["make_mesh", "auto_mesh_axes"]


def make_mesh(axes, devices=None):
    """axes: dict axis-name -> size (insertion order = mesh order).
    devices: flat device list (default: every device of the default
    platform).  Too few devices is an error, never a quiet move to
    another platform."""
    sizes = list(axes.values())
    n = int(np.prod(sizes))
    if devices is None:
        devices = jax.devices()
    if len(devices) < n:
        raise ValueError("mesh %r needs %d devices, have %d"
                         % (axes, n, len(devices)))
    arr = np.array(devices[:n]).reshape(sizes)
    return Mesh(arr, tuple(axes.keys()))


def auto_mesh_axes(n_devices, prefer=("dp", "tp", "sp", "pp")):
    """Factor n_devices over the preferred axes, largest-first: spread
    factors of 2 across as many axes as possible so every strategy gets a
    non-trivial extent when the device count allows."""
    axes = {a: 1 for a in prefer}
    remaining = n_devices
    i = 0
    order = list(prefer)
    while remaining > 1:
        f = _smallest_prime_factor(remaining)
        axes[order[i % len(order)]] *= f
        remaining //= f
        i += 1
    return {a: s for a, s in axes.items()}


def _smallest_prime_factor(n):
    for p in (2, 3, 5, 7):
        if n % p == 0:
            return p
    d = 11
    while d * d <= n:
        if n % d == 0:
            return d
        d += 2
    return n
