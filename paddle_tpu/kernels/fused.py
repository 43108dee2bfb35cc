"""Fused softmax + cross-entropy Pallas kernel.

SURVEY stage 7's softmax+CE fusion target (reference
operators/softmax_with_cross_entropy_op.cc runs two kernels + a
gather): one pass over the logits row computes max, log-sum-exp and
picks the label logit, so the [N, C] probability matrix never hits HBM.
XLA usually fuses this chain too; the kernel exists for the very wide
vocab case (C in the tens of thousands) where keeping the row resident
in VMEM wins.  Same-math XLA fallback everywhere else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .dispatch import take_pallas

__all__ = ["fused_softmax_cross_entropy"]


def _xla_path(logits, labels):
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    lab = jnp.take_along_axis(logits.astype(jnp.float32),
                              labels[:, None], axis=1)[:, 0]
    return (lse - lab).astype(logits.dtype)


def _ce_kernel(logits_ref, labels_ref, o_ref, *, block_c, n_classes):
    # labels/out travel as [block_n, 1]: 1-D int operands trip Mosaic's
    # XLA-layout check, 2-D lanes do not.  The class axis streams
    # through VMEM in block_c tiles with an online logsumexp (a 30k-wide
    # fp32 row block would blow the VMEM stack limit otherwise).
    lab = labels_ref[...][:, 0]                      # [block_n]
    bn = lab.shape[0]
    m = jnp.full((bn,), -1e30, jnp.float32)
    s = jnp.zeros((bn,), jnp.float32)
    picked = jnp.zeros((bn,), jnp.float32)
    n_tiles = n_classes // block_c

    def body(i, carry):
        m, s, picked = carry
        x = logits_ref[:, pl.dslice(i * block_c, block_c)].astype(
            jnp.float32)                             # [bn, block_c]
        m_new = jnp.maximum(m, x.max(axis=1))
        s = s * jnp.exp(m - m_new) + jnp.exp(
            x - m_new[:, None]).sum(axis=1)
        cls = i * block_c + jax.lax.broadcasted_iota(
            jnp.int32, x.shape, 1)
        picked = picked + jnp.where(cls == lab[:, None], x,
                                    0.0).sum(axis=1)
        return m_new, s, picked

    m, s, picked = jax.lax.fori_loop(0, n_tiles, body, (m, s, picked))
    o_ref[...] = (m + jnp.log(s) - picked)[:, None].astype(o_ref.dtype)


def fused_softmax_cross_entropy(logits, labels, block_n=256,
                                block_c=2048, force_xla=False,
                                interpret=False):
    """Per-row -log softmax(logits)[label]; logits [N, C], labels [N]
    int.  Pallas on TPU when N and C divide their blocks; XLA
    otherwise."""
    n, c = logits.shape
    labels = labels.reshape(-1).astype(jnp.int32)
    # the logits block is [block_n, C] in VMEM: cap it at ~4MB so the
    # scoped-vmem limit (16MB incl. double buffering) is never hit
    cap = max(8, (4 << 20) // (4 * c))
    block_n = min(block_n, n, cap - cap % 8 or 8)
    block_c = min(block_c, c)
    usable = n % block_n == 0 and c % block_c == 0
    if not take_pallas("fused_softmax_cross_entropy", usable, force_xla,
                       interpret):
        return _xla_path(logits, labels)
    kernel = functools.partial(_ce_kernel, block_c=block_c,
                               n_classes=c)
    out = pl.pallas_call(
        kernel,
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((block_n, c), lambda i: (i, 0)),
                  pl.BlockSpec((block_n, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), logits.dtype),
        interpret=interpret,
    )(logits, labels[:, None])
    return out[:, 0]
