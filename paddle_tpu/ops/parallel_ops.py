"""SPMD annotation ops.

TPU-native additions with no per-op reference analog: the reference placed
whole tensors on devices and moved data with NCCL op handles
(details/*_op_handle.cc); here placement is expressed as mesh-axis
annotations inside the compiled program and GSPMD inserts the collectives.
"""
from __future__ import annotations

import jax

from paddle_tpu.core.registry import register_op


@register_op("sharding_constraint")
def _sharding_constraint_lower(ctx, ins, attrs, op=None):
    x = ins["X"]
    if ctx.mesh is None:
        return {"Out": x}
    from jax.sharding import NamedSharding, PartitionSpec as P
    spec = tuple(a if a and a in ctx.mesh.axis_names else None
                 for a in attrs.get("spec", ()))
    spec = spec[:x.ndim]
    sharding = NamedSharding(ctx.mesh, P(*spec))
    return {"Out": jax.lax.with_sharding_constraint(x, sharding)}


def _axis_or_none(mesh, name):
    return name if (name and mesh is not None
                    and name in mesh.axis_names
                    and dict(mesh.shape)[name] > 1) else None


def _per_shard(ctx, attrs, fn):
    """``fn`` run per (batch, head) shard of the mesh.  A Mosaic kernel
    is an opaque custom call GSPMD cannot partition (jax refuses it
    under a mesh: "wrap the call in a shard_map"); attention is
    independent per batch row and head, so the dense kernels shard_map
    over the axes those two leading dims are sharded on (the ring
    path's batch/head axes) — every operand and result is [B, H, ...]."""
    b_ax = _axis_or_none(ctx.mesh, attrs.get("batch_axis", "dp"))
    h_ax = _axis_or_none(ctx.mesh, attrs.get("head_axis", "tp"))
    if b_ax is None and h_ax is None:
        return fn
    from jax.sharding import PartitionSpec as P

    spec = P(b_ax, h_ax)
    return jax.shard_map(fn, mesh=ctx.mesh, in_specs=spec, out_specs=spec,
                         check_vma=False)


@register_op("ring_attention", no_vjp_outputs=("LSE",))
def _ring_attention_lower(ctx, ins, attrs, op=None):
    """Scaled-dot-product attention, sequence-parallel when compiled under
    a mesh with the configured sp axis; dense otherwise.  Q/K/V: [B,H,S,D].
    """
    import jax.numpy as jnp

    q, k, v = ins["Q"], ins["K"], ins["V"]
    causal = bool(attrs.get("causal", True))
    # scale attr: explicit softmax scale (the attention transpiler sets
    # it when fusing a plain matmul-softmax-matmul chain whose scaling
    # differs from the 1/sqrt(D) default).  ABSENT means default; a
    # present value — including 0.0 — is used verbatim, or the fusion
    # pass would not be semantics-preserving.
    scale = attrs["scale"] if "scale" in attrs else None
    sp_axis = _axis_or_none(ctx.mesh, attrs.get("sp_axis", "sp"))
    if sp_axis is not None:
        from paddle_tpu.parallel.ring import (ring_attention,
                                              ring_attention_fwd_lse)
        axes = dict(
            batch_axis=_axis_or_none(ctx.mesh, attrs.get("batch_axis", "dp")),
            head_axis=_axis_or_none(ctx.mesh, attrs.get("head_axis", "tp")))
        if op is not None and op.outputs.get("LSE"):
            # saved-LSE contract (ISSUE 15): the ring forward's REAL
            # per-position log-sum-exp rides as the op output, so the
            # grad op replays the reverse-direction ring from it — no
            # forward re-execution inside a generic vjp (MIGRATION.md)
            out, lse = ring_attention_fwd_lse(
                q, k, v, ctx.mesh, axis_name=sp_axis, causal=causal,
                scale=scale, **axes)
            return {"Out": out, "LSE": lse}
        return {"Out": ring_attention(
            q, k, v, ctx.mesh, axis_name=sp_axis, causal=causal,
            scale=scale, **axes)}
    # dense path: the Pallas flash kernel on a TPU-placed program (the
    # executor declares the place's or the mesh's device), same-math
    # XLA elsewhere
    from paddle_tpu.kernels import flash_attention
    from paddle_tpu.kernels.flash_attention import flash_attention_fwd_lse
    if op is not None and op.outputs.get("LSE"):
        # residual form: lse rides as an op output so the grad op runs
        # the flash backward directly instead of re-executing the
        # forward inside its vjp (see ring_attention_grad)
        out, lse = _per_shard(
            ctx, attrs,
            lambda q, k, v: flash_attention_fwd_lse(
                q, k, v, scale=scale, causal=causal))(q, k, v)
        return {"Out": out, "LSE": lse}
    return {"Out": _per_shard(
        ctx, attrs,
        lambda q, k, v: flash_attention(q, k, v, scale=scale,
                                        causal=causal))(q, k, v)}


@register_op("moe_ffn")
def _moe_ffn_lower(ctx, ins, attrs, op=None):
    """Top-1 mixture-of-experts FFN; expert-parallel over the ep axis when
    compiled under a mesh, dense-dispatch otherwise.  X: [T, D] or
    [B, S, D] (flattened internally)."""
    import jax.numpy as jnp

    x, wg = ins["X"], ins["RouterW"]
    w1, w2 = ins["W1"], ins["W2"]
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    ep_axis = _axis_or_none(ctx.mesh, attrs.get("ep_axis", "ep"))
    if ep_axis is not None:
        from paddle_tpu.parallel.moe import moe_ffn
        out = moe_ffn(x2, wg, w1, w2, ctx.mesh, axis_name=ep_axis,
                      dp_axis=_axis_or_none(ctx.mesh,
                                            attrs.get("dp_axis", "dp")),
                      capacity_factor=float(
                          attrs.get("capacity_factor", 2.0)))
    else:
        gates = jax.nn.softmax(x2 @ wg, axis=-1)
        expert = jnp.argmax(gates, axis=-1)
        gate = jnp.take_along_axis(gates, expert[:, None], axis=1)[:, 0]
        h = jax.nn.relu(jnp.einsum("td,edf->tef", x2, w1))
        y = jnp.einsum("tef,efd->ted", h, w2)
        out = y[jnp.arange(x2.shape[0]), expert] * gate[:, None]
        # dense dispatch has no capacity drop; routing stats still feed
        # the registry so the --moe rollup works off-mesh too
        from paddle_tpu.parallel.moe import emit_router_stats
        emit_router_stats(gates, expert,
                          jnp.ones(expert.shape, jnp.bool_))
    return {"Out": out.reshape(shape)}


@register_op("ring_attention_grad", grad_maker=None)
def _ring_attention_grad_lower(ctx, ins, attrs, op=None):
    """Flash backward from the forward's saved lse (no forward
    re-execution): the reverse-direction ring under sp, the two flash
    backward kernels dense.  Falls back to the generic vjp — which
    re-runs the forward — only when the residual is absent (ops built
    without the LSE output, e.g. the inference transpiler's fused
    chains)."""
    from paddle_tpu.core import lowering as core_lowering
    from paddle_tpu.kernels.flash_attention import flash_attention_bwd

    sp_axis = _axis_or_none(ctx.mesh, attrs.get("sp_axis", "sp"))
    lse = ins.get("LSE")
    if lse is None:
        return core_lowering.generic_grad_lower(ctx, ins, attrs, op)
    if sp_axis is not None:
        from paddle_tpu.parallel.ring import ring_attention_bwd
        dq, dk, dv = ring_attention_bwd(
            ins["Q"], ins["K"], ins["V"], ins["Out"], lse,
            ins["Out@GRAD"], ctx.mesh, axis_name=sp_axis,
            causal=bool(attrs.get("causal", True)),
            scale=attrs["scale"] if "scale" in attrs else None,
            batch_axis=_axis_or_none(ctx.mesh,
                                     attrs.get("batch_axis", "dp")),
            head_axis=_axis_or_none(ctx.mesh,
                                    attrs.get("head_axis", "tp")))
        return {"Q@GRAD": dq, "K@GRAD": dk, "V@GRAD": dv}
    scale = attrs["scale"] if "scale" in attrs else None
    causal = bool(attrs.get("causal", True))
    dq, dk, dv = _per_shard(
        ctx, attrs,
        lambda q, k, v, out, lse, do: flash_attention_bwd(
            q, k, v, out, lse, do, scale=scale, causal=causal))(
                ins["Q"], ins["K"], ins["V"], ins["Out"], lse,
                ins["Out@GRAD"])
    return {"Q@GRAD": dq, "K@GRAD": dk, "V@GRAD": dv}
