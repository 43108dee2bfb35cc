"""Pallas fused conv-stage kernels (NHWC activations, HWIO weights).

The ResNet byte floor (PROFILE_r04.md): 94% of device step time runs
inside XLA conv fusions at 82-85% of HBM peak, and the profiler
attributes the residual to XLA materializing re-laid-out intermediates
between conv fusions.  These kernels attack the bytes directly:

- The conv consumes NHWC input and HWIO weights *as stored* (the layout
  transpiler pins them at creation), so no per-fusion re-layout traffic
  exists to begin with.
- Train mode fuses the batch-norm statistics into the conv epilogue:
  per-channel sum/sum-of-squares come out of the same VMEM-resident
  f32 accumulator that the conv writes, saving one full HBM read of the
  conv output that a separate stats reduction would cost (and computing
  the stats from f32 partials even when the stored activation is bf16).
- Test mode fuses the whole conv+BN(+residual)(+ReLU) stage: the raw
  conv output never reaches HBM at all.

One image per grid step: ResNet stage shapes keep the padded input
image, the filter, and the f32 accumulator comfortably inside VMEM
(budget-checked below; anything over budget, grouped, dilated, or
off-TPU falls back to the identical-math XLA path, like
flash_attention).  The kernel unrolls the KHxKW taps into plain
[Ho*Wo, Ci] @ [Ci, Co] MXU dots — no im2col materialization.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from paddle_tpu.observability.trace import traced as _traced

from .dispatch import take_pallas

__all__ = ["conv2d_nhwc", "fused_conv_bn_act_reference"]

# Per-image VMEM budget for (padded input + weights + f32 accumulator +
# output): stay well under the ~16MB/core limit incl. double buffering.
VMEM_BUDGET_BYTES = 10 << 20


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v), int(v))


def conv_nhwc_xla(x, w, strides, paddings):
    """Reference-math NHWC x HWIO conv (f32 MXU accumulation)."""
    sh, sw = _pair(strides)
    ph, pw = _pair(paddings)
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(sh, sw),
        padding=[(ph, ph), (pw, pw)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)


def _epilogue(acc, a_ref, b_ref, res_ref, act):
    """acc [Ho*Wo, Co] f32 -> fused affine (+residual) (+act)."""
    y = acc
    if a_ref is not None:
        y = y * a_ref[...][0][None, :] + b_ref[...][0][None, :]
    if res_ref is not None:
        y = y + res_ref[...].reshape(y.shape).astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    return y


def _conv_stage_kernel(*refs, kh, kw, sh, sw, ho, wo, ci, co,
                       with_stats, with_affine, with_residual, act):
    """One image: x_ref [Hp, Wp, Ci] (pre-padded), w_ref [KH, KW, Ci, Co]
    -> out_ref [Ho, Wo, Co] (+ stats_ref [2, Co] f32 partials)."""
    it = iter(refs)
    x_ref = next(it)
    w_ref = next(it)
    a_ref = next(it) if with_affine else None
    b_ref = next(it) if with_affine else None
    res_ref = next(it) if with_residual else None
    out_ref = next(it)
    stats_ref = next(it) if with_stats else None

    xv = x_ref[...].astype(jnp.float32)            # [Hp, Wp, Ci]
    acc = jnp.zeros((ho * wo, co), jnp.float32)
    for i in range(kh):
        for j in range(kw):
            # the (i, j) tap sees a strided [Ho, Wo, Ci] window; taps are
            # Python-unrolled so every slice is static
            win = jax.lax.slice(
                xv, (i, j, 0),
                (i + (ho - 1) * sh + 1, j + (wo - 1) * sw + 1, ci),
                (sh, sw, 1))
            acc += win.reshape(ho * wo, ci) @ \
                w_ref[i, j].astype(jnp.float32)
    if with_stats:
        # f32 partials from the VMEM accumulator: the stats reduction
        # never re-reads the conv output from HBM
        stats_ref[0, :] = acc.sum(axis=0)
        stats_ref[1, :] = (acc * acc).sum(axis=0)
    y = _epilogue(acc, a_ref, b_ref, res_ref, act)
    out_ref[...] = y.reshape(ho, wo, co).astype(out_ref.dtype)


def _vmem_bytes(hp, wp, ci, kh, kw, co, ho, wo, in_dtype):
    ib = jnp.dtype(in_dtype).itemsize
    return (hp * wp * ci * 4            # f32 image copy in registers
            + kh * kw * ci * co * ib    # weights
            + ho * wo * co * 4          # f32 accumulator
            + ho * wo * co * ib)        # output block


# launch-site span (FLAGS_telemetry): records the TRACE/lowering-time
# cost of building this kernel — the on-device execution shows up in
# the profiler capture's device plane
@_traced("pallas.conv2d_nhwc",
         lambda x, w, *a, **kw: {"x": str(x.shape), "w": str(w.shape)})
def conv2d_nhwc(x, w, strides=(1, 1), paddings=(0, 0), *, stats=False,
                affine=None, residual=None, act="", out_dtype=None,
                force_xla=False, interpret=False):
    """NHWC x [N,H,W,Ci] * HWIO w [KH,KW,Ci,Co] -> [N,Ho,Wo,Co].

    ``stats=True``: also return per-channel (sum, sum_sq) f32 of the raw
    conv output — the fused-BN training form.  ``affine=(a, b)``: fuse
    ``y*a + b`` per channel (test-mode BN fold).  ``residual``: fuse a
    same-shape add; ``act``: '' | 'relu'.  Falls back to the
    identical-math XLA path off-TPU / over-budget / odd configs.
    """
    n, h, wd, ci = x.shape
    kh, kw, wci, co = w.shape
    sh, sw = _pair(strides)
    ph, pw = _pair(paddings)
    out_dtype = out_dtype or x.dtype
    ho = (h + 2 * ph - kh) // sh + 1
    wo = (wd + 2 * pw - kw) // sw + 1
    hp, wp = h + 2 * ph, wd + 2 * pw

    # stride 1 only: the kernel's strided tap windows lower to a
    # vector.extract_strided_slice Mosaic rejects ("strides confined to
    # [1, 2)"), so ResNet's stem and downsample stages take the XLA conv
    usable = (wci == ci and ho >= 1 and wo >= 1
              and (sh, sw) == (1, 1)
              and _vmem_bytes(hp, wp, ci, kh, kw, co, ho, wo,
                              x.dtype) <= VMEM_BUDGET_BYTES)
    if not take_pallas("conv2d_nhwc", usable, force_xla, interpret):
        acc = conv_nhwc_xla(x, w, (sh, sw), (ph, pw))       # f32
        yf = acc
        if affine is not None:
            a, b = affine
            yf = yf * a.astype(jnp.float32) + b.astype(jnp.float32)
        if residual is not None:
            yf = yf + residual.astype(jnp.float32)
        if act == "relu":
            yf = jnp.maximum(yf, 0.0)
        y = yf.astype(out_dtype)
        if not stats:
            return y
        s = acc.reshape(-1, co).sum(axis=0)
        ss = jnp.square(acc).reshape(-1, co).sum(axis=0)
        return y, s, ss

    if ph or pw:
        x = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))

    with_affine = affine is not None
    with_residual = residual is not None
    kernel = functools.partial(
        _conv_stage_kernel, kh=kh, kw=kw, sh=sh, sw=sw, ho=ho, wo=wo,
        ci=ci, co=co, with_stats=stats, with_affine=with_affine,
        with_residual=with_residual, act=act)

    in_specs = [
        pl.BlockSpec((None, hp, wp, ci), lambda i: (i, 0, 0, 0)),
        pl.BlockSpec((kh, kw, ci, co), lambda i: (0, 0, 0, 0)),
    ]
    operands = [x, w]
    if with_affine:
        a, b = affine
        in_specs += [pl.BlockSpec((1, co), lambda i: (0, 0)),
                     pl.BlockSpec((1, co), lambda i: (0, 0))]
        operands += [a.astype(jnp.float32).reshape(1, co),
                     b.astype(jnp.float32).reshape(1, co)]
    if with_residual:
        in_specs.append(pl.BlockSpec((None, ho, wo, co),
                                     lambda i: (i, 0, 0, 0)))
        operands.append(residual)

    out_specs = [pl.BlockSpec((None, ho, wo, co), lambda i: (i, 0, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((n, ho, wo, co), out_dtype)]
    if stats:
        # per-image f32 partials; the (tiny) cross-image reduce runs in
        # XLA right after the kernel
        out_specs.append(pl.BlockSpec((None, 2, co), lambda i: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((n, 2, co), jnp.float32))

    outs = pl.pallas_call(
        kernel,
        grid=(n,),
        in_specs=in_specs,
        out_specs=out_specs if stats else out_specs[0],
        out_shape=out_shape if stats else out_shape[0],
        interpret=interpret,
    )(*operands)
    if not stats:
        return outs
    y, partials = outs
    return y, partials[:, 0, :].sum(axis=0), partials[:, 1, :].sum(axis=0)


def fused_conv_bn_act_reference(x, w, scale, bias, mean, var, *, strides,
                                paddings, eps, act="", residual=None):
    """Pure-XLA reference for the fused stage in TEST mode (running
    stats): what the Pallas path must match bit-for-bit-ish."""
    inv = jax.lax.rsqrt(var.astype(jnp.float32) + eps)
    a = scale.astype(jnp.float32) * inv
    b = bias.astype(jnp.float32) - mean.astype(jnp.float32) * a
    y = conv_nhwc_xla(x, w, strides, paddings) * a + b
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if act == "relu":
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype)
