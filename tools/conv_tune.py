"""Fused conv-stage kernel sweep at the ResNet-50 bs256 stage shapes.

flash_tune.py's method applied to ISSUE 5 lever (b): for each distinct
conv+BN+ReLU stage of the headline model, measure fwd wall time of

  nchw    — lax conv NCHW/OIHW + BN(batch stats)+relu, XLA-fused
            (the round-4 baseline the byte floor was measured on),
  nhwc    — same math, NHWC/HWIO operands (lever a alone), and
  fused   — the Pallas conv-stage kernel with in-kernel BN statistics
            (kernels/conv_fused.py; lever a + b),

with the microbench traps handled: distinct pre-staged inputs, unrolled
chain, one final d2h drain.  On the real chip the per-kernel xplane
attribution comes from wrapping this in ``jax.profiler.trace``
(CONV_TUNE_PROFILE=<dir>).

Usage: python tools/conv_tune.py [steps] [batch]
"""
from __future__ import annotations

import contextlib
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.kernels import conv_fused  # noqa: E402

STEPS = int(sys.argv[1]) if len(sys.argv) > 1 else 8
BATCH = int(sys.argv[2]) if len(sys.argv) > 2 else 256

# (name, h, ci, co, k, stride, pad) — the distinct ResNet-50 stage
# shapes (each repeats across blocks; counts in the comment)
STAGES = [
    ("stem7x7s2", 224, 3, 64, 7, 2, 3),       # x1
    ("r1_1x1", 56, 64, 64, 1, 1, 0),          # bottleneck reduce
    ("r1_3x3", 56, 64, 64, 3, 1, 1),          # x3
    ("r1_expand", 56, 64, 256, 1, 1, 0),
    ("r2_3x3", 28, 128, 128, 3, 1, 1),        # x4
    ("r2_down", 56, 256, 512, 1, 2, 0),       # shortcut downsample
    ("r3_3x3", 14, 256, 256, 3, 1, 1),        # x6
    ("r4_3x3", 7, 512, 512, 3, 1, 1),         # x3
]


def _bn_relu(y, eps=1e-5):
    """Batch-stats BN + relu on an NHWC (or NCHW via axis) conv out —
    the elementwise tail XLA fuses either way."""
    red = tuple(range(y.ndim - 1))
    yf = y.astype(jnp.float32)
    mean = yf.mean(axis=red)
    var = jnp.square(yf).mean(axis=red) - jnp.square(mean)
    inv = jax.lax.rsqrt(var + eps)
    return jnp.maximum((yf - mean) * inv, 0.0).astype(y.dtype)


def bench_stage(name, h, ci, co, k, s, p, dtype=jnp.bfloat16):
    rng = np.random.RandomState(0)
    ho = (h + 2 * p - k) // s + 1
    xs_nhwc = [jnp.asarray(rng.randn(BATCH, h, h, ci), dtype)
               for _ in range(STEPS)]
    xs_nchw = [jnp.transpose(x, (0, 3, 1, 2)) for x in xs_nhwc]
    w_hwio = jnp.asarray(rng.randn(k, k, ci, co) * 0.1, dtype)
    w_oihw = jnp.transpose(w_hwio, (3, 2, 0, 1))

    def run_nchw(xs):
        acc = 0.0
        for x in xs:
            y = jax.lax.conv_general_dilated(
                x, w_oihw, (s, s), [(p, p), (p, p)],
                dimension_numbers=("NCHW", "OIHW", "NCHW"))
            y = _bn_relu(jnp.transpose(y, (0, 2, 3, 1)))
            acc = acc + y[0, 0, 0, 0].astype(jnp.float32)
        return acc

    def run_nhwc(xs):
        acc = 0.0
        for x in xs:
            y = conv_fused.conv_nhwc_xla(x, w_hwio, (s, s), (p, p))
            acc = acc + _bn_relu(y.astype(dtype))[0, 0, 0, 0].astype(
                jnp.float32)
        return acc

    def run_fused(xs):
        acc = 0.0
        for x in xs:
            y, su, ss = conv_fused.conv2d_nhwc(
                x, w_hwio, (s, s), (p, p), stats=True)
            n = y.size // co
            mean = su / n
            inv = jax.lax.rsqrt(ss / n - jnp.square(mean) + 1e-5)
            z = jnp.maximum((y.astype(jnp.float32) - mean) * inv, 0.0)
            acc = acc + z[0, 0, 0, 0]
        return acc

    out = {}
    for label, fn, xs in (("nchw", run_nchw, xs_nchw),
                          ("nhwc", run_nhwc, xs_nhwc),
                          ("fused", run_fused, xs_nhwc)):
        try:
            jfn = jax.jit(fn)
            float(np.asarray(jfn(xs)))          # compile + warm
            t0 = time.time()
            float(np.asarray(jfn(xs)))          # d2h drain = the sync
            out[label] = (time.time() - t0) / STEPS * 1e3
        except Exception as exc:  # noqa: BLE001 — survey tool
            out[label] = "FAIL:%s" % str(exc)[:40]
    return out


def _record_stage(stage, r):
    """Persist the per-stage pallas-vs-xla winner into the autotune
    cache (ISSUE 7): the fused_conv2d_bn_act lowering consults it and
    takes the identical-math XLA path where that measured faster.
    Keyed exactly as the lowering keys its lookup."""
    from paddle_tpu import tuning

    name, h, ci, co, k, s, p = stage
    fused, nhwc = r.get("fused"), r.get("nhwc")
    if not (isinstance(fused, float) and isinstance(nhwc, float)):
        return
    impl = "pallas" if fused <= nhwc else "xla"
    shape = (BATCH, h, h, ci, k, k, ci, co, s, s, p, p)
    ok = tuning.record("fused_conv2d_bn_act", shape, "bfloat16",
                       {"impl": impl}, ms=min(fused, nhwc),
                       source="conv_tune:%s" % name)
    if ok:
        print("  autotune cache <- %s impl=%s" % (name, impl))


def main():
    print("ResNet-50 stage sweep, bs=%d, %d unrolled steps, bf16" %
          (BATCH, STEPS))
    print("%-12s %10s %10s %10s  %s" % ("stage", "nchw ms", "nhwc ms",
                                        "fused ms", "fused/nchw"))
    prof = os.environ.get("CONV_TUNE_PROFILE")
    ctx = jax.profiler.trace(prof) if prof else contextlib.nullcontext()
    with ctx:
        for stage in STAGES:
            r = bench_stage(*stage)
            ratio = ""
            if isinstance(r.get("fused"), float) and \
                    isinstance(r.get("nchw"), float) and r["nchw"]:
                ratio = "%.2fx" % (r["fused"] / r["nchw"])

            def fmt(v):
                return "%10.2f" % v if isinstance(v, float) else \
                    "%10s" % v
            print("%-12s %s %s %s  %s" % (
                stage[0], fmt(r["nchw"]), fmt(r["nhwc"]),
                fmt(r["fused"]), ratio), flush=True)
            _record_stage(stage, r)


if __name__ == "__main__":
    main()
