"""Expert parallelism: top-1 routed mixture-of-experts FFN over an ``ep``
mesh axis.

The reference's sparse-scaling analog is the distributed lookup table
(transpiler/distribute_transpiler.py:611: rows sharded over pservers,
fetched via prefetch RPC).  TPU-native: experts are sharded over ``ep``;
tokens are dispatched to their expert's device with all_to_all over ICI,
transformed, and combined back — no parameter server in the hot path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["moe_ffn", "emit_router_stats"]


def _metrics_on():
    from paddle_tpu.core.flags import FLAGS

    return bool(FLAGS.moe_metrics)


def _note_stats(tokens, idx, load, dropped, entropy):
    """Host side of the routing-stats callback (ISSUE 15 MoE rider):
    feed the always-on metrics registry.  Only ring/ep position 0
    reports — under a pure ep mesh every shard routes the SAME
    replicated tokens, so emitting from all of them would multiply
    the counts (dp shards each carry idx 0 for their ep row and land
    as independent samples, which is what we want)."""
    if int(np.asarray(idx)) != 0:
        return
    from paddle_tpu.observability import metrics

    load = np.asarray(load)
    hist = metrics.histogram(
        "moe_expert_load_tokens",
        "tokens routed to one expert in one step (pre-capacity): the "
        "per-expert load distribution — a balanced router keeps the "
        "spread tight")
    for c in load:
        hist.observe(float(c))
    dropped = float(np.asarray(dropped))
    metrics.gauge("moe_dropped_token_frac",
                  "fraction of tokens dropped by expert capacity in "
                  "the latest routed step").set(dropped)
    metrics.gauge("moe_router_entropy",
                  "mean per-token entropy of the router softmax in "
                  "the latest routed step (nats; ln(E) = uniform)"
                  ).set(float(np.asarray(entropy)))
    metrics.counter("moe_tokens_total",
                    "tokens routed through moe_ffn").inc(tokens)
    metrics.counter("moe_dropped_tokens_total",
                    "tokens dropped by expert capacity").inc(
                        int(round(dropped * tokens)))
    metrics.counter("moe_router_steps_total",
                    "moe_ffn routed steps observed").inc(1)


def emit_router_stats(gates, expert, keep, shard_idx=0):
    """Emit capacity-factor routing stats from inside a traced
    computation: per-expert load, dropped-token fraction, router
    entropy -> the always-on metrics registry (jax.debug.callback, one
    [E]+2-scalar transfer per step; FLAGS_moe_metrics gates the
    callback out of the program entirely).  ``gates`` [T, E] softmax
    output, ``expert`` [T] argmax routing, ``keep`` [T] bool kept
    mask, ``shard_idx`` the ep ring position (only 0 reports)."""
    if not _metrics_on():
        return
    e = gates.shape[-1]
    load = jnp.sum(jax.nn.one_hot(expert, e, dtype=jnp.int32), axis=0)
    dropped = 1.0 - keep.astype(jnp.float32).mean()
    entropy = -(gates * jnp.log(jnp.clip(gates, 1e-20, None))
                ).sum(-1).mean()
    jax.debug.callback(
        functools.partial(_note_stats, int(gates.shape[0])),
        shard_idx, load, dropped, entropy)


def _moe_shard(x, wg, w1, w2, axis_name, capacity_factor):
    """x: [T_local, D] tokens; wg: [D, E] router; w1: [E_local, D, F],
    w2: [E_local, F, D] expert weights (E = E_local * ep_size)."""
    p = lax.psum(1, axis_name)
    t, d = x.shape
    e_local = w1.shape[0]
    e = e_local * p

    gates = jax.nn.softmax(x @ wg, axis=-1)           # [T, E]
    expert = jnp.argmax(gates, axis=-1)               # [T]
    gate = jnp.take_along_axis(gates, expert[:, None], axis=1)[:, 0]

    cap = max(1, int(capacity_factor * t / e))        # tokens/expert/device
    # position of each token within its expert's capacity buffer
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.int32)       # [T, E]
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1             # [T, E]
    pos_tok = jnp.max(pos, axis=1)                            # [T]
    keep = (pos_tok >= 0) & (pos_tok < cap)
    emit_router_stats(gates, expert, keep,
                      shard_idx=lax.axis_index(axis_name))
    # dispatch buffer [E, cap, D]
    disp = jnp.zeros((e, cap, d), x.dtype)
    disp = disp.at[expert, jnp.clip(pos_tok, 0, cap - 1)].add(
        jnp.where(keep[:, None], x, 0.0))
    # [E, cap, D] -> [p, E_local, cap, D] -> all_to_all over ep
    disp = disp.reshape(p, e_local, cap, d)
    recv = lax.all_to_all(disp, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)                # [p, E_local, cap, D]
    recv = jnp.swapaxes(recv, 0, 1).reshape(e_local, p * cap, d)
    h = jax.nn.relu(jnp.einsum("ecd,edf->ecf", recv, w1))
    y = jnp.einsum("ecf,efd->ecd", h, w2)             # [E_local, p*cap, D]
    y = jnp.swapaxes(y.reshape(e_local, p, cap, d), 0, 1)  # [p,E_local,cap,D]
    back = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)                # [p, E_local, cap, D]
    back = back.reshape(e, cap, d)
    out = back[expert, jnp.clip(pos_tok, 0, cap - 1)]  # [T, D]
    return jnp.where(keep[:, None], out * gate[:, None], 0.0)


def moe_ffn(x, router_w, w1, w2, mesh, axis_name="ep", dp_axis=None,
            capacity_factor=2.0):
    """Top-1 MoE FFN.  x: [T, D] (T sharded over dp_axis if given);
    router_w: [D, E] replicated; w1: [E, D, F], w2: [E, F, D] sharded on
    the expert dim over ``axis_name``.  Returns [T, D] like x."""
    xspec = P(dp_axis, None)
    espec = P(axis_name, None, None)
    fn = functools.partial(_moe_shard, axis_name=axis_name,
                           capacity_factor=capacity_factor)
    # When tokens are replicated over the ep axis (dp_axis=None), every
    # shard reconstructs the full [T, D] output after the reverse
    # all_to_all, so the result is replicated — but the vma type system
    # cannot infer that through the collectives; the check is disabled.
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(xspec, P(None, None), espec, espec),
        out_specs=xspec, check_vma=False)(x, router_w, w1, w2)
