"""Pipeline parallelism: GPipe-style microbatching over a mesh axis.

The reference's only model-parallel mechanism is the legacy per-layer
device assignment (--parallel_nn, gserver/gradientmachines/
ParallelNeuralNetwork.cpp) which pipelines layers across GPUs with
host-side threads.  TPU-native version: stage parameters are sharded over
the ``pp`` axis, microbatches stream through a shard_map loop and
activations hop stage-to-stage with ppermute over ICI.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply"]


def _pipeline_shard(stage_params, x, axis_name, stage_fn):
    """Per-device body.  stage_params: [1, ...] (this stage's slice of the
    leading stage axis); x: [M, mb, ...] microbatches (replicated)."""
    p = lax.psum(1, axis_name)
    i = lax.axis_index(axis_name)
    params = jax.tree.map(lambda a: a[0], stage_params)
    m = x.shape[0]
    ev = jax.eval_shape(stage_fn, params, x[0])
    # carries start as constants; mark them device-varying for the scan
    state = lax.pcast(jnp.zeros(ev.shape, ev.dtype), (axis_name,),
                      to="varying")
    out = lax.pcast(jnp.zeros((m,) + ev.shape, ev.dtype), (axis_name,),
                    to="varying")
    perm = [(s, (s + 1) % p) for s in range(p)]

    def tick(carry, t):
        state, out = carry
        inp = jnp.where(i == 0,
                        x[jnp.clip(t, 0, m - 1)].astype(state.dtype), state)
        y = stage_fn(params, inp)
        done_idx = t - (p - 1)  # microbatch finishing at the last stage
        write = (i == p - 1) & (done_idx >= 0) & (done_idx < m)
        upd = lax.dynamic_update_index_in_dim(
            out, y, jnp.clip(done_idx, 0, m - 1), 0)
        out = jnp.where(write, upd, out)
        state = lax.ppermute(y, axis_name, perm)
        return (state, out), None

    (state, out), _ = lax.scan(tick, (state, out), jnp.arange(m + p - 1))
    # all stages return the same result: broadcast last stage's buffer
    out = lax.psum(jnp.where(i == p - 1, out, jnp.zeros_like(out)),
                   axis_name)
    return out


def pipeline_apply(stage_params, microbatches, mesh, stage_fn,
                   axis_name="pp", batch_axis=None):
    """Run ``stage_fn(params_of_stage, x) -> y`` as a P-stage pipeline.

    stage_params: pytree whose leaves have leading dim P (one slice per
    stage), sharded over ``axis_name``.  microbatches: [M, mb, ...]
    replicated.  Returns [M, mb, ...] outputs (replicated).  All stages
    must map activations to the same shape/dtype.

    ``batch_axis``: optional second mesh axis carrying data parallelism
    — the microbatch dim (dim 1) shards over it, each dp slice runs its
    own pipeline over the shared (replicated-over-dp) stage weights,
    and the weight-gradient psum over dp is inserted by the shard_map
    transpose automatically.  The dp x pp composition the 8-device
    dryrun exercises (MESH_PROFILE r6)."""
    def leaf_spec(a):
        return P(axis_name, *([None] * (a.ndim - 1)))

    data_spec = P(None, batch_axis) if batch_axis else P()
    in_specs = (jax.tree.map(leaf_spec, stage_params), data_spec)
    fn = functools.partial(_pipeline_shard, axis_name=axis_name,
                           stage_fn=stage_fn)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=data_spec, check_vma=False)(
                             stage_params, microbatches)
