"""Ring attention: sequence/context parallelism over a mesh axis.

Long-context story (SURVEY §5.7: the reference's is LoDTensor ragged
batching — it predates sequence parallelism; this is the first-class
TPU-native mechanism).  Q/K/V live sharded on the sequence dim over the
``sp`` axis; each device folds one K/V block at a time into a flash
online-softmax carry while blocks rotate around the ring via ppermute
over ICI (Liu et al., "Ring Attention with Blockwise Transformers").

ISSUE 15 rebuilt the hot path on kernels/flash_attention.py's
chunk-carry form:

- **Tiled inner compute.**  Each ring step is ONE
  ``flash_attention_chunk`` call — the (m, l, acc) online-softmax carry
  threads across steps and no dense [Sq_local, Sk_local] score block
  ever materializes in HBM (the blockwise XLA fallback is
  memory-bounded too, so CPU parity transfers).
- **Double-buffered rotation.**  The ``ppermute`` for block j+1 is
  issued BEFORE block j's compute; the collective has no data
  dependency on the running chunk so the latency-hiding scheduler
  overlaps it (FLAGS_xla_latency_hiding_scheduler; the
  tools/longctx_bench.py HLO inventory verifies the structure).
- **Causal block skipping.**  The ring loop is Python-unrolled (p is
  static): step 0 is the diagonal chunk (causal mask, always live) and
  every later step is a ``lax.cond`` on the ring-position predicate —
  a K/V block entirely in this shard's future skips its FLOPs at
  runtime, not just its probability mass (~(p+1)/2p of the dense step
  count at causal; ``causal_step_counts`` is the measured evidence).
- **A real backward.**  ``ring_attention`` carries a custom_vjp: the
  forward saves the per-shard log-sum-exp, and the backward runs a
  REVERSE-direction ring — the (q, dO, lse, delta) package rotates
  while K/V and their gradient accumulators stay device-resident, P is
  rebuilt per chunk from the saved lse (no forward recompute), and the
  travelling dQ returns home after a full cycle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from paddle_tpu.kernels.flash_attention import (
    NEG_INF, chunk_finalize, flash_attention_chunk,
    flash_attention_chunk_bwd)
from paddle_tpu.observability.trace import traced as _traced

__all__ = ["ring_attention", "ring_attention_fwd_lse",
           "ring_attention_bwd", "causal_step_counts"]


def _step_live(j, my, p, causal, direction):
    """Liveness of ring step ``j`` on the device at ring position
    ``my`` — (static_live, traced_pred).  Static True for the diagonal
    step and every non-causal step; otherwise the block-index
    predicate that drives causal skipping.

    forward: after j forward rotations the local K/V block came from
    shard (my - j) mod p; it is entirely in the past iff j <= my.
    backward: after j reverse rotations the visiting Q package came
    from shard (my + j) mod p; it is at-or-after the local K/V block
    iff j < p - my.
    """
    if j == 0 or not causal:
        return True, None
    if direction == "fwd":
        return False, j <= my
    return False, j < p - my


def _ring_fwd_shard(q, k, v, *, axis_name, causal, scale, block_q,
                    block_k, force_xla, interpret):
    """Per-shard forward under shard_map.  q,k,v: [B, H, S_local, D];
    returns (out [B,H,S,D], lse [B,H,S] f32)."""
    p = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    m = jnp.full(q.shape[:3], NEG_INF, jnp.float32)
    l = jnp.zeros(q.shape[:3], jnp.float32)
    acc = jnp.zeros(q.shape, jnp.float32)
    perm = [(i, (i + 1) % p) for i in range(p)]
    chunk = functools.partial(flash_attention_chunk, scale=scale,
                              block_q=block_q, block_k=block_k,
                              force_xla=force_xla, interpret=interpret)
    k_cur, v_cur = k, v
    for j in range(p):
        if j + 1 < p:
            # double-buffer: the rotation feeding step j+1 is issued
            # BEFORE step j's compute — no data dependency between
            # them, so the collective hides under the chunk
            k_nxt = lax.ppermute(k_cur, axis_name, perm)
            v_nxt = lax.ppermute(v_cur, axis_name, perm)
        live, pred = _step_live(j, my, p, causal, "fwd")
        if live:
            m, l, acc = chunk(q, k_cur, v_cur, m, l, acc,
                              causal=(causal and j == 0))
        else:
            # causal block skipping: the whole K/V block is in this
            # shard's future — skip its FLOPs, not just its mass
            m, l, acc = lax.cond(
                pred,
                lambda mla, _k=k_cur, _v=v_cur:
                    chunk(q, _k, _v, *mla, causal=False),
                lambda mla: mla,
                (m, l, acc))
        if j + 1 < p:
            k_cur, v_cur = k_nxt, v_nxt
    return chunk_finalize(m, l, acc, q.dtype)


def _ring_bwd_shard(q, k, v, out, lse, do, *, axis_name, causal, scale,
                    block_q, block_k, force_xla, interpret):
    """Per-shard backward: reverse-direction ring over the saved lse.
    K/V and their gradient accumulators stay home; the (q, dO, lse,
    delta, dQ) package rotates.  No forward recompute anywhere."""
    p = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    delta = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    dq = jnp.zeros(q.shape, jnp.float32)
    rev = [(i, (i - 1) % p) for i in range(p)]
    chunk_bwd = functools.partial(flash_attention_chunk_bwd, scale=scale,
                                  block_q=block_q, block_k=block_k,
                                  force_xla=force_xla,
                                  interpret=interpret)
    q_cur, do_cur, lse_cur, delta_cur = q, do, lse, delta
    for j in range(p):
        if j + 1 < p:
            # prefetch the next Q package (not dq — THIS step's compute
            # still contributes to it before it moves on)
            q_nxt = lax.ppermute(q_cur, axis_name, rev)
            do_nxt = lax.ppermute(do_cur, axis_name, rev)
            lse_nxt = lax.ppermute(lse_cur, axis_name, rev)
            delta_nxt = lax.ppermute(delta_cur, axis_name, rev)

        def upd(args, _q=q_cur, _do=do_cur, _lse=lse_cur,
                _delta=delta_cur, _j=j):
            dq_a, dk_a, dv_a = args
            dqj, dkj, dvj = chunk_bwd(_q, k, v, _do, _lse, _delta,
                                      causal=(causal and _j == 0))
            return (dq_a + dqj.astype(jnp.float32),
                    dk_a + dkj.astype(jnp.float32),
                    dv_a + dvj.astype(jnp.float32))

        live, pred = _step_live(j, my, p, causal, "bwd")
        if live:
            dq, dk, dv = upd((dq, dk, dv))
        else:
            dq, dk, dv = lax.cond(pred, upd, lambda args: args,
                                  (dq, dk, dv))
        # the travelling dQ rotates AFTER every step (including the
        # last: p reverse rotations bring each shard's dQ home)
        dq = lax.ppermute(dq, axis_name, rev)
        if j + 1 < p:
            q_cur, do_cur = q_nxt, do_nxt
            lse_cur, delta_cur = lse_nxt, delta_nxt
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _specs(batch_axis, head_axis, axis_name):
    qspec = P(batch_axis, head_axis, axis_name, None)
    rspec = P(batch_axis, head_axis, axis_name)
    return qspec, rspec


def _shard_fns(mesh, axis_name, causal, scale, batch_axis, head_axis,
               block_q, block_k, force_xla, interpret):
    qspec, rspec = _specs(batch_axis, head_axis, axis_name)
    fwd = functools.partial(_ring_fwd_shard, axis_name=axis_name,
                            causal=causal, scale=scale, block_q=block_q,
                            block_k=block_k, force_xla=force_xla,
                            interpret=interpret)
    bwd = functools.partial(_ring_bwd_shard, axis_name=axis_name,
                            causal=causal, scale=scale, block_q=block_q,
                            block_k=block_k, force_xla=force_xla,
                            interpret=interpret)
    # check_vma=False here and below: the strategies' collectives
    # (masked psum broadcasts, reverse all_to_all reconstructions) are
    # replication-correct by construction but not inferable by the
    # varying-manual-axes type system
    fwd_sm = jax.shard_map(fwd, mesh=mesh,
                           in_specs=(qspec, qspec, qspec),
                           out_specs=(qspec, rspec), check_vma=False)
    bwd_sm = jax.shard_map(bwd, mesh=mesh,
                           in_specs=(qspec, qspec, qspec, qspec, rspec,
                                     qspec),
                           out_specs=(qspec, qspec, qspec),
                           check_vma=False)
    return fwd_sm, bwd_sm


@_traced("pallas.ring_attention",
         lambda q, *a, **kw: {"q": str(q.shape)})
def ring_attention_fwd_lse(q, k, v, mesh, axis_name="sp", causal=True,
                           scale=None, batch_axis=None, head_axis=None,
                           block_q=None, block_k=None, force_xla=False,
                           interpret=False):
    """Forward returning ``(out, lse)`` — the op-level residual form.

    ``lse`` is the REAL per-position log-sum-exp ([B, H, S] f32, S
    sharded like q): with it saved as an op output the grad op runs
    ``ring_attention_bwd`` directly instead of re-executing the forward
    inside a generic vjp (MIGRATION.md "Ring attention" note)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    fwd_sm, _ = _shard_fns(mesh, axis_name, causal, scale, batch_axis,
                           head_axis, block_q, block_k, force_xla,
                           interpret)
    return fwd_sm(q, k, v)


@_traced("pallas.ring_attention_bwd",
         lambda q, *a, **kw: {"q": str(q.shape)})
def ring_attention_bwd(q, k, v, out, lse, do, mesh, axis_name="sp",
                       causal=True, scale=None, batch_axis=None,
                       head_axis=None, block_q=None, block_k=None,
                       force_xla=False, interpret=False):
    """Backward from op-level residuals: (dq, dk, dv) via the
    reverse-direction ring over the saved lse.  No forward
    re-execution."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    _, bwd_sm = _shard_fns(mesh, axis_name, causal, scale, batch_axis,
                           head_axis, block_q, block_k, force_xla,
                           interpret)
    return bwd_sm(q, k, v, out, lse, do)


def ring_attention(q, k, v, mesh, axis_name="sp", causal=True, scale=None,
                   batch_axis=None, head_axis=None, block_q=None,
                   block_k=None, force_xla=False, interpret=False):
    """q,k,v: [B, H, S, D] global; S sharded over ``axis_name`` (B over
    ``batch_axis``, H over ``head_axis`` — tensor parallelism composes
    for free since heads are independent).  Returns [B, H, S, D] with
    the same sharding.  Differentiable: the custom_vjp replays the
    saved-lse reverse ring (no forward recompute, no [S, S] block)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    fwd_sm, bwd_sm = _shard_fns(mesh, axis_name, causal, scale,
                                batch_axis, head_axis, block_q, block_k,
                                force_xla, interpret)

    @jax.custom_vjp
    def _ring(q, k, v):
        out, _ = fwd_sm(q, k, v)
        return out

    def _fwd(q, k, v):
        out, lse = fwd_sm(q, k, v)
        return out, (q, k, v, out, lse)

    def _bwd(res, g):
        q, k, v, out, lse = res
        return bwd_sm(q, k, v, out, lse, g)

    _ring.defvjp(_fwd, _bwd)
    return _ring(q, k, v)


def causal_step_counts(mesh, axis_name="sp", causal=True,
                       direction="fwd"):
    """Executed-chunk count per ring position ([p] int32) — the causal
    block-skipping evidence, from the SAME liveness predicate the real
    loops branch on (``_step_live``).  Causal at p devices sums to
    p*(p+1)/2 executed chunks vs p*p dense — ~2x fewer at p=8."""
    p = dict(mesh.shape)[axis_name]

    def body(x):
        my = lax.axis_index(axis_name)
        c = jnp.zeros((1,), jnp.int32)
        for j in range(p):
            live, pred = _step_live(j, my, p, causal, direction)
            if live:
                c = c + 1
            else:
                c = lax.cond(pred, lambda c: c + 1, lambda c: c, c)
        return c

    counts = jax.shard_map(body, mesh=mesh, in_specs=(P(axis_name),),
                           out_specs=P(axis_name), check_vma=False)(
                               jnp.zeros((p,), jnp.float32))
    return counts
