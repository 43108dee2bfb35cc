"""The one rule for whether a kernel takes its Pallas path, counted.

A kernel runs compiled on a TPU-placed computation
(core/place.target_platform) or interpreted elsewhere on request; the
shape-based ``usable`` fall-backs to the identical-math XLA path are a
design, a silent one is not: every decision increments
``kernel_dispatch_total.<kernel>.<pallas|xla>`` in the always-on
metrics registry, so a caller that expected the Pallas path can assert
it was taken.
"""
from __future__ import annotations

from paddle_tpu.core.place import target_platform
from paddle_tpu.observability import metrics as _metrics

__all__ = ["take_pallas", "counts"]

_PREFIX = "kernel_dispatch_total."


def take_pallas(kernel, usable=True, force_xla=False, interpret=False):
    """True when ``kernel``'s Pallas path runs: the shape is ``usable``,
    the caller did not ``force_xla``, and the computation is TPU-placed
    (compiled) or ``interpret`` was asked for off-TPU."""
    on_tpu = target_platform() == "tpu"
    if interpret and on_tpu:
        raise ValueError(
            "%s: interpret=True on a TPU-placed computation — the "
            "interpreter is the CPU rehearsal of the kernel, never a "
            "chip path" % kernel)
    pallas = bool(usable) and not force_xla and (on_tpu or interpret)
    path = "pallas" if pallas else "xla"
    _metrics.counter(
        _PREFIX + "%s.%s" % (kernel, path),
        "trace-time dispatches of kernel %r down its %s path"
        % (kernel, path)).inc()
    return pallas


def counts():
    """{'<kernel>.<path>': dispatches so far} over every kernel seen."""
    return {name[len(_PREFIX):]: snap["value"]
            for name, snap in _metrics.snapshot().items()
            if name.startswith(_PREFIX)}
