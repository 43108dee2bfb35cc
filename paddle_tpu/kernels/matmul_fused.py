"""Pallas fused matmul-stage kernels for the transformer block
(ISSUE 7 tentpole, part 1).

The transformer-LM bench sits at MFU 0.526 with flash attention already
hand-tiled; the remaining ~47% of the step is QKV/output projections,
the MLP matmul+bias+act chains and the residual+LayerNorm seams, all
left to XLA's default fusion.  These kernels apply the conv_fused.py
discipline to those stages:

- ``matmul_epilogue``: one tiled [M, K] @ [K, N] matmul with an f32
  VMEM accumulator; the bias add, activation (relu/gelu) and residual
  add run as the accumulator's epilogue — the raw matmul output never
  round-trips HBM between the matmul and its elementwise tail.  The
  fused QKV projection is the same kernel over the width-concatenated
  weight (one wide matmul feeding q/k/v instead of three reads of x).
- ``add_ln``: the pre-LN seam ``LayerNorm(x + y)``: the residual sum
  and the LN statistics come out of the same VMEM-resident tile (the
  sum is also an output — the residual stream needs it), so the
  statistics reduction never re-reads the sum from HBM.

Both fall back to an identical-math XLA path off-TPU, over the VMEM
budget, or when a dimension doesn't tile (odd tails) — mirroring
kernels/conv_fused.py.  Tile sizes consult the persistent autotune
cache (paddle_tpu/tuning) at trace time; a miss uses the defaults
below.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.observability.trace import traced as _traced

from .dispatch import take_pallas

__all__ = ["matmul_epilogue", "add_ln", "matmul_epilogue_reference",
           "add_ln_reference", "plan_matmul", "plan_add_ln", "apply_act",
           "quantize_weight", "dequantize_weight", "matmul_int8_dequant"]

# Per-grid-step VMEM budget (operand tiles + f32 accumulator + output
# tiles, double-buffering headroom included) — same ceiling discipline
# as conv_fused.VMEM_BUDGET_BYTES.
VMEM_BUDGET_BYTES = 10 << 20

# Built-in tile defaults (the values the autotune cache overrides):
# 256x256 output tiles keep the accumulator at 256KB f32 while bk=512
# amortizes the K-stream DMA; all multiples of the MXU's 128 lanes.
DEF_BLOCK_M = 256
DEF_BLOCK_N = 256
DEF_BLOCK_K = 512
DEF_LN_BLOCK_M = 256


def _fit_tile(block, size, floor):
    """Largest power-of-two tile <= requested that divides ``size``
    (stops halving at ``floor``; a non-divisor result means 'fallback',
    checked by the caller) — flash_attention._fit_block's rule."""
    block = max(1, min(int(block), int(size)))
    while block > floor and size % block:
        block //= 2
    return block


def apply_act(y, act):
    """The epilogue activation, shared by the kernel, the XLA fallback
    and the op-level reference math (keep these in lockstep with the
    'relu'/'gelu' op lowerings)."""
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "gelu":
        return jax.nn.gelu(y, approximate=True)
    if act:
        raise ValueError("unsupported fused activation %r" % (act,))
    return y


# ---------------------------------------------------------------------------
# Fused matmul + bias/act/residual epilogue
# ---------------------------------------------------------------------------

def plan_matmul(m, k, n, in_dtype, config=None):
    """Tile plan for [m,k]@[k,n]: (block_m, block_n, block_k, usable).

    ``config`` (an autotune-cache hit: {'block_m','block_n','block_k'})
    overrides the defaults; the plan still clamps to divisors and the
    VMEM budget, so a stale cache entry can demote to the XLA fallback
    but never produce a wrong kernel."""
    config = config or {}
    bm = _fit_tile(config.get("block_m", DEF_BLOCK_M), m, 8)
    bn = _fit_tile(config.get("block_n", DEF_BLOCK_N), n, 128)
    bk = _fit_tile(config.get("block_k", DEF_BLOCK_K), k, 128)
    ib = jnp.dtype(in_dtype).itemsize
    vmem = (bm * bk * ib + bk * bn * ib     # x / w tiles
            + bm * bn * 4                   # f32 accumulator
            + 2 * bm * bn * ib              # out (+ optional pre) tiles
            + bm * bn * ib                  # optional residual tile
            + bn * 4)                       # bias tile
    usable = (m % bm == 0 and n % bn == 0 and k % bk == 0
              and bn % 128 == 0 and bk % 128 == 0 and bm % 8 == 0
              and vmem <= VMEM_BUDGET_BYTES)
    return bm, bn, bk, usable


def _matmul_kernel(*refs, nk, act, with_bias, with_residual,
                   save_preact):
    it = iter(refs)
    x_ref = next(it)
    w_ref = next(it)
    b_ref = next(it) if with_bias else None
    r_ref = next(it) if with_residual else None
    o_ref = next(it)
    pre_ref = next(it) if save_preact else None
    acc_ref = next(it)

    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _done():
        y = acc_ref[...]
        if with_bias:
            y = y + b_ref[...][0][None, :]
        if save_preact:
            # the grad residual (gelu'(pre) etc.) — written from the
            # accumulator, not recomputed by the backward
            pre_ref[...] = y.astype(pre_ref.dtype)
        y = apply_act(y, act)
        if with_residual:
            y = y + r_ref[...].astype(jnp.float32)
        o_ref[...] = y.astype(o_ref.dtype)


def matmul_epilogue_reference(x2, w, bias=None, residual=None, act="",
                              out_dtype=None):
    """Identical-math XLA fallback — association-identical to the
    UNFUSED mul -> elementwise_add -> act -> elementwise_add op chain,
    so CPU parity against the unfused program is tight."""
    out_dtype = out_dtype or x2.dtype
    y = jnp.dot(x2, w, preferred_element_type=jnp.result_type(x2, w))
    if bias is not None:
        y = y + bias
    pre = y
    y = apply_act(y, act)
    if residual is not None:
        y = y + residual
    return y.astype(out_dtype), pre


# launch-site span (FLAGS_telemetry): the trace-time cost of building
# the kernel; on-device time shows in the xplane capture
@_traced("pallas.matmul_fused",
         lambda x, w, *a, **kw: {"x": str(x.shape), "w": str(w.shape)})
def matmul_epilogue(x2, w, bias=None, residual=None, act="", *,
                    save_preact=False, out_dtype=None, config=None,
                    force_xla=False, interpret=False):
    """[M, K] @ [K, N] with the bias/act/residual tail fused into the
    accumulator epilogue.  Returns ``out`` or ``(out, pre)`` with
    ``save_preact`` (pre = x@w + bias, the activation's input — the
    saved residual the explicit grad lowering consumes).

    Tile sizes: ``config`` > autotune cache > defaults.  Off-TPU, over
    budget, or non-tiling shapes take the identical-math XLA path.
    """
    from paddle_tpu import tuning

    m, k = x2.shape
    k2, n = w.shape
    assert k == k2, (x2.shape, w.shape)
    out_dtype = out_dtype or x2.dtype
    if config is None:
        config = tuning.lookup("matmul_fused", (m, k, n),
                               jnp.dtype(x2.dtype).name)
    bm, bn, bk, usable = plan_matmul(m, k, n, x2.dtype, config)
    if not take_pallas("matmul_epilogue", usable, force_xla, interpret):
        y, pre = matmul_epilogue_reference(x2, w, bias, residual, act,
                                           out_dtype)
        return (y, pre.astype(out_dtype)) if save_preact else y

    with_bias = bias is not None
    with_residual = residual is not None
    nk = k // bk
    kernel = functools.partial(
        _matmul_kernel, nk=nk, act=act, with_bias=with_bias,
        with_residual=with_residual, save_preact=save_preact)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
    ]
    operands = [x2, w]
    if with_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(bias.astype(jnp.float32).reshape(1, n))
    if with_residual:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
        operands.append(residual)

    out_specs = [pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))]
    out_shape = [jax.ShapeDtypeStruct((m, n), out_dtype)]
    if save_preact:
        out_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
        out_shape.append(jax.ShapeDtypeStruct((m, n), out_dtype))

    outs = _pallas_call(
        kernel,
        grid=(m // bm, n // bn, nk),
        in_specs=in_specs,
        out_specs=out_specs if save_preact else out_specs[0],
        out_shape=out_shape if save_preact else out_shape[0],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return outs


# ---------------------------------------------------------------------------
# int8 weight-quantized matmul with epilogue dequant (ISSUE 11)
# ---------------------------------------------------------------------------
#
# Serving decode is weight-bound: every step re-reads every parameter
# for one token per sequence, so int8 weights halve-again the byte
# floor bf16 set.  The quantizer is distributed/compress.py's per-chunk
# symmetric rule (scale = absmax/127 per chunk) applied along K — each
# [chunk, 1] column segment of W gets one f32 scale, so one outlier
# weight cannot flatten a whole matrix's resolution.  The kernel DMAs
# the int8 tile and rescales it in VMEM right before the MXU dot — the
# f32 weights never exist in HBM.

def quantize_weight(w, chunk=None):
    """Quantize a [K, N] weight matrix int8, per-(K-chunk, column):
    returns (q int8 [K, N], scales f32 [K//chunk, N], chunk).  ``chunk``
    defaults to the wire codec's granularity (compress.CHUNK) and clamps
    to a divisor of K (whole-K when K doesn't divide — coarse, never
    wrong)."""
    import numpy as np

    from paddle_tpu.distributed.compress import CHUNK, quantize_symmetric

    w = np.ascontiguousarray(np.asarray(w), np.float32)
    k, n = w.shape
    chunk = int(chunk or CHUNK)
    chunk = min(chunk, k)
    if k % chunk:
        chunk = k
    nc = k // chunk
    # [nc, chunk, N] -> chunks along K per column: [nc*N, chunk]
    cols = w.reshape(nc, chunk, n).transpose(0, 2, 1).reshape(-1, chunk)
    q, scales = quantize_symmetric(cols)
    q = q.reshape(nc, n, chunk).transpose(0, 2, 1).reshape(k, n)
    return np.ascontiguousarray(q), \
        np.ascontiguousarray(scales.reshape(nc, n)), chunk


def dequantize_weight(q, scales, chunk):
    """The [K, N] f32 weights ``quantize_weight``'s output reconstructs
    — the XLA-fallback half of the kernel's in-VMEM rescale (works on
    numpy or traced jnp values)."""
    k, n = q.shape
    nc = k // chunk
    return (q.astype(jnp.float32).reshape(nc, chunk, n)
            * scales.reshape(nc, 1, n)).reshape(k, n)


def _matmul_int8_kernel(*refs, nk, act, with_bias, with_residual):
    it = iter(refs)
    x_ref = next(it)
    w_ref = next(it)
    s_ref = next(it)
    b_ref = next(it) if with_bias else None
    r_ref = next(it) if with_residual else None
    o_ref = next(it)
    acc_ref = next(it)

    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dequant in VMEM: within one K tile every row shares the chunk, so
    # the scale varies only by column — one [1, bn] tile broadcast
    w = w_ref[...].astype(jnp.float32) * s_ref[...][0][None, :]
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _done():
        y = acc_ref[...]
        if with_bias:
            y = y + b_ref[...][0][None, :]
        y = apply_act(y, act)
        if with_residual:
            y = y + r_ref[...].astype(jnp.float32)
        o_ref[...] = y.astype(o_ref.dtype)


@_traced("pallas.matmul_int8",
         lambda x, w, *a, **kw: {"x": str(x.shape), "w": str(w.shape)})
def matmul_int8_dequant(x2, wq, scales, chunk, bias=None, residual=None,
                        act="", *, out_dtype=None, config=None,
                        force_xla=False, interpret=False):
    """[M, K] @ dequant(int8 [K, N]) with the per-chunk scales applied
    in the kernel's VMEM epilogue-side rescale and the bias/act/residual
    tail fused like ``matmul_epilogue``.  Identical-math XLA fallback
    (dequantize + the reference epilogue) off-TPU / non-tiling shapes —
    both paths answer the same floats, so serving parity tests run on
    CPU transfer to the kernel."""
    from paddle_tpu import tuning

    m, k = x2.shape
    k2, n = wq.shape
    assert k == k2, (x2.shape, wq.shape)
    assert k % int(chunk) == 0, (k, chunk)
    out_dtype = out_dtype or x2.dtype
    if config is None:
        config = tuning.lookup("matmul_int8", (m, k, n),
                               jnp.dtype(x2.dtype).name)
    bm, bn, bk, usable = plan_matmul(m, k, n, x2.dtype, config)
    # each K tile must sit inside ONE quantization chunk (the kernel
    # rescales a tile with a single [1, bn] scale row)
    usable = usable and (int(chunk) % bk == 0 or bk % int(chunk) == 0)
    if bk > int(chunk):
        usable = False
    if not take_pallas("matmul_int8_dequant", usable, force_xla,
                       interpret):
        w = dequantize_weight(jnp.asarray(wq), jnp.asarray(scales),
                              int(chunk))
        y, _ = matmul_epilogue_reference(
            x2.astype(jnp.float32), w, bias, residual, act, out_dtype)
        return y

    with_bias = bias is not None
    with_residual = residual is not None
    nk = k // bk
    per = int(chunk) // bk          # K tiles per quantization chunk
    kernel = functools.partial(
        _matmul_int8_kernel, nk=nk, act=act, with_bias=with_bias,
        with_residual=with_residual)
    # scales travel [nc, 1, N] with the chunk axis squeezed: a (1, bn)
    # block of the [nc, N] array breaks Mosaic's last-two-dims rule
    # whenever nc > 1 (K spanning several quantization chunks)
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((None, 1, bn), lambda i, j, kk: (kk // per, 0, j)),
    ]
    operands = [x2, wq, scales.reshape(k // int(chunk), 1, n)]
    if with_bias:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)))
        operands.append(bias.astype(jnp.float32).reshape(1, n))
    if with_residual:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)))
        operands.append(residual)
    return _pallas_call(
        kernel,
        grid=(m // bm, n // bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# Fused residual-add + LayerNorm
# ---------------------------------------------------------------------------

def plan_add_ln(m, d, in_dtype, config=None):
    """Row-tile plan for add+LN over [m, d]: (block_m, usable)."""
    config = config or {}
    bm = _fit_tile(config.get("block_m", DEF_LN_BLOCK_M), m, 8)
    ib = jnp.dtype(in_dtype).itemsize
    vmem = (2 * bm * d * ib           # x / y tiles
            + 2 * bm * d * ib         # out / sum tiles
            + bm * d * 4              # f32 working copy
            + 2 * d * 4)              # scale / bias
    usable = (m % bm == 0 and bm % 8 == 0 and d % 128 == 0
              and vmem <= VMEM_BUDGET_BYTES)
    return bm, usable


def _add_ln_kernel(*refs, eps, with_scale, with_bias):
    it = iter(refs)
    x_ref = next(it)
    y_ref = next(it)
    s_ref = next(it) if with_scale else None
    b_ref = next(it) if with_bias else None
    out_ref = next(it)
    sum_ref = next(it)
    mean_ref = next(it)
    var_ref = next(it)

    s = x_ref[...] + y_ref[...]
    sum_ref[...] = s
    # statistics in f32 from the VMEM-resident sum, then the SAME
    # cast/normalize order as the layer_norm op lowering — the fused op
    # must be numerically interchangeable with add + layer_norm
    sf = s.astype(jnp.float32)
    mean = jnp.mean(sf, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(sf - mean), axis=1, keepdims=True)
    mean = mean.astype(s.dtype)
    var = var.astype(s.dtype)
    # rsqrt itself in f32, rounded back: v5e has no bf16 transcendental
    # unit (libtpu aborts the process on a bf16 vector rsqrt), and this
    # is what XLA's own bf16 rsqrt in ln_from_sum lowers to
    inv = jax.lax.rsqrt((var + eps).astype(jnp.float32)).astype(s.dtype)
    yn = (s - mean) * inv
    if with_scale:
        yn = yn * s_ref[...][0][None, :].astype(s.dtype)
    if with_bias:
        yn = yn + b_ref[...][0][None, :].astype(s.dtype)
    out_ref[...] = yn.astype(out_ref.dtype)
    mean_ref[...] = mean
    var_ref[...] = var


def ln_from_sum(s, scale=None, bias=None, eps=1e-5):
    """The layer_norm lowering's exact computation order applied to an
    already-summed [M, D] input: f32 statistics, cast back to the input
    dtype BEFORE normalize, scale/bias cast per-use.  Both the XLA
    fallback and the fused_add_ln grad replay (which differentiates
    this via jax.vjp) share this one definition so their numerics can
    never drift apart.  Returns (out, mean, var) with mean/var [M]."""
    sf = s.astype(jnp.float32)
    mean = jnp.mean(sf, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(sf - mean), axis=1, keepdims=True)
    mean = mean.astype(s.dtype)
    var = var.astype(s.dtype)
    yn = (s - mean) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        yn = yn * scale.astype(s.dtype)[None, :]
    if bias is not None:
        yn = yn + bias.astype(s.dtype)[None, :]
    return yn, mean[:, 0], var[:, 0]


def add_ln_reference(x2, y2, scale=None, bias=None, eps=1e-5):
    """Identical-math XLA fallback: elementwise_add + the layer_norm
    lowering's exact computation order.  Returns (out, sum, mean, var)
    with mean/var as [M] rows."""
    s = x2 + y2
    yn, mean, var = ln_from_sum(s, scale, bias, eps)
    return yn, s, mean, var


@_traced("pallas.add_ln", lambda x, *a, **kw: {"x": str(x.shape)})
def add_ln(x2, y2, scale=None, bias=None, eps=1e-5, *, config=None,
           force_xla=False, interpret=False):
    """LayerNorm(x + y) over [M, D] rows, sum and statistics from one
    VMEM tile.  Returns (out, sum, mean, var); mean/var are [M]."""
    from paddle_tpu import tuning

    m, d = x2.shape
    if config is None:
        config = tuning.lookup("add_ln", (m, d),
                               jnp.dtype(x2.dtype).name)
    bm, usable = plan_add_ln(m, d, x2.dtype, config)
    if not take_pallas("add_ln", usable, force_xla, interpret):
        return add_ln_reference(x2, y2, scale, bias, eps)

    with_scale = scale is not None
    with_bias = bias is not None
    kernel = functools.partial(_add_ln_kernel, eps=eps,
                               with_scale=with_scale, with_bias=with_bias)
    in_specs = [pl.BlockSpec((bm, d), lambda i: (i, 0)),
                pl.BlockSpec((bm, d), lambda i: (i, 0))]
    operands = [x2, y2]
    if with_scale:
        in_specs.append(pl.BlockSpec((1, d), lambda i: (0, 0)))
        operands.append(scale.reshape(1, d))
    if with_bias:
        in_specs.append(pl.BlockSpec((1, d), lambda i: (0, 0)))
        operands.append(bias.reshape(1, d))
    out, sm, mean, var = _pallas_call(
        kernel,
        grid=(m // bm,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((bm, d), lambda i: (i, 0)),
                   pl.BlockSpec((bm, d), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0)),
                   pl.BlockSpec((bm, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((m, d), x2.dtype),
                   jax.ShapeDtypeStruct((m, d), x2.dtype),
                   jax.ShapeDtypeStruct((m, 1), x2.dtype),
                   jax.ShapeDtypeStruct((m, 1), x2.dtype)],
        interpret=interpret,
    )(*operands)
    return out, sm, mean[:, 0], var[:, 0]


def _pallas_call(kernel, **kwargs):
    """Indirection the autotune tests hook to observe the grid/block
    specs an entry actually lowered with."""
    return pl.pallas_call(kernel, **kwargs)
