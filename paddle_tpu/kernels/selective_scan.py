"""A fresh prompt through one Mamba-1 layer's selective state space: the
tokens in order, the state carried from one to the next, the slot's
state written back IN PLACE.

For token ``t`` of the prompt, channel ``d`` and state index ``n``:

    S[n, d] <- exp(dt[t, d] A[n, d]) S[n, d] + dt[t, d] x[t, d] B[t, n]
    y[t, d]  = sum_n S[n, d] C[t, n]

The walk starts from the state of slot ``slot`` of layer ``layer`` in
``state`` ([L, slots, N, D] float32, the decode step's own array: see
``ssm_update.mamba1_state_update``) and leaves there the state after the
prompt.  A prompt is staged in a padded bucket: ``dt = 0`` on padding is
the identity (a decay of 1, nothing added), so the state left is the one
after ``length`` tokens; ``y`` is 0 past ``length``.

Mamba-1's decay differs from state index to state index, so the prompt
has no matrix (chunked) form as Mamba-2's has: the kernel walks the
tokens one by one.  Its grid goes over blocks of the channels (which
do not mix) and, inside one, over blocks of tokens; the block's state
``[N, block]`` stays in registers from the first token to the last and
goes back to the slot once.  A token block past ``length`` is not
walked.  All arithmetic is float32 on the vector unit.  Off the TPU (and
not ``interpret``) the identical-math ``lax.scan`` runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import take_pallas
from .ssm_update import column

__all__ = ["selective_scan"]

_BLOCK_D = 512      # channels a grid step walks (4 lanes-wide tiles)
_BLOCK_T = 256      # tokens a grid step holds
_GROUP = 8          # tokens read and written as one (8, lanes) tile


def _kernel(meta_ref, x_ref, dt_ref, a_ref, b_ref, c_ref, s_ref, y_ref,
            o_ref):
    """``meta_ref`` [layer, slot, length] in SMEM; ``x_ref`` /
    ``dt_ref`` / ``y_ref`` [T, Db], ``b_ref`` / ``c_ref`` [T, N],
    ``a_ref`` and ``s_ref`` / ``o_ref`` (the same HBM block) [N, Db]."""
    t_blk = pl.program_id(1)
    block_t = x_ref.shape[0]
    n = a_ref.shape[0]
    first = t_blk * block_t
    live = jnp.clip(meta_ref[2] - first, 0, block_t)

    @pl.when(t_blk == 0)
    def _():
        o_ref[...] = s_ref[...]

    a = a_ref[...]
    row = jax.lax.broadcasted_iota(jnp.int32, (_GROUP, x_ref.shape[1]), 0)

    def group(g, state):
        r0 = pl.multiple_of(g * _GROUP, _GROUP)
        xg = x_ref[pl.ds(r0, _GROUP), :]
        dtg = dt_ref[pl.ds(r0, _GROUP), :]
        bg = b_ref[pl.ds(r0, _GROUP), :]
        cg = c_ref[pl.ds(r0, _GROUP), :]
        yg = jnp.zeros_like(xg)
        for j in range(_GROUP):
            dt = dtg[j:j + 1]
            state = (jnp.exp(dt * a) * state
                     + column(bg[j:j + 1], n) * (dt * xg[j:j + 1]))
            yj = jnp.sum(state * column(cg[j:j + 1], n), axis=0,
                         keepdims=True)
            yg = jnp.where(row == j, yj, yg)
        y_ref[pl.ds(r0, _GROUP), :] = jnp.where(row + r0 < live, yg, 0.0)
        return state

    y_ref[...] = jnp.zeros_like(y_ref)
    o_ref[...] = jax.lax.fori_loop(0, (live + _GROUP - 1) // _GROUP, group,
                                   o_ref[...])


@functools.partial(jax.jit, inline=True, static_argnames=("interpret",))
def _scan_call(meta, x, dt, a, b, c, state, *, interpret):
    s_len, d = x.shape
    n = a.shape[0]
    bd = _BLOCK_D if d % _BLOCK_D == 0 else d
    bt = min(_BLOCK_T, s_len)
    tokens = lambda i, j, m: (j, i)
    slab = pl.BlockSpec((None, None, n, bd),
                        lambda i, j, m: (m[0], m[1], 0, i))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(d // bd, s_len // bt),
        in_specs=[
            pl.BlockSpec((bt, bd), tokens),
            pl.BlockSpec((bt, bd), tokens),
            pl.BlockSpec((n, bd), lambda i, j, m: (0, i)),
            pl.BlockSpec((bt, n), lambda i, j, m: (j, 0)),
            pl.BlockSpec((bt, n), lambda i, j, m: (j, 0)),
            slab,
        ],
        out_specs=[pl.BlockSpec((bt, bd), tokens), slab],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 6 (the prefetched scalars count) is the state
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="selective_scan",
        metadata={"kernel": "selective_scan"},
    )(meta, x, dt, a, b, c, state)


def selective_scan(state, slot, length, x, dt, a, b, c, layer=0,
                   force_xla=False, interpret=False):
    """``state`` [L, slots, N, D] float32; ``slot`` and ``length`` int32
    scalars; ``x`` / ``dt`` [S, D] (``dt`` 0 from ``length`` on), ``a``
    [N, D] (negative), ``b`` / ``c`` [S, N], all float32; ``layer`` an
    index, static or traced.  Returns ``(y [S, D], the updated
    state)``; ``y`` is ``S C`` before the model's ``D x`` skip."""
    n_l, _, n, d = state.shape
    s_len = x.shape[0]
    assert x.shape == dt.shape == (s_len, d) and a.shape == (n, d)
    assert b.shape == c.shape == (s_len, n)
    assert not isinstance(layer, int) or 0 <= layer < n_l, (layer, n_l)
    slot = jnp.asarray(slot, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    usable = s_len % min(_BLOCK_T, s_len) == 0 and s_len % _GROUP == 0
    if not take_pallas("selective_scan", usable, force_xla, interpret):
        def step(s, inp):
            xt, dtt, bt, ct = inp
            s = jnp.exp(dtt * a) * s + bt[:, None] * (dtt * xt)
            return s, jnp.sum(s * ct[:, None], axis=0)

        last, y = jax.lax.scan(step, state[layer, slot], (x, dt, b, c))
        y = jnp.where((jnp.arange(s_len) < length)[:, None], y, 0.0)
        return y, state.at[layer, slot].set(last)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32), slot, length])
    return _scan_call(meta, x, dt, a, b, c, state, interpret=interpret)
