"""The decode step's state update of a Mamba layer: one token a row
through the selective state space, the state updated IN PLACE.  Two
forms: ``ssm_state_update`` for Mamba-2 (one decay a head) and
``mamba1_state_update`` for Mamba-1 (a decay a channel and state index,
at the end of this file).

For row ``r`` with state slot ``s = slots[r]``, head ``h`` of group
``g = h // (H / G)``:

    S[s, h] <- exp(dt[r, h] A[h]) S[s, h] + dt[r, h] x[r, h] (x) B[r, g]
    y[r, h]  = S[s, h] C[r, g]

``state`` is every Mamba layer's state for every slot, ``[L, slots, H,
P, N]`` float32, handed over WHOLE with ``layer`` the index of the layer
that runs, and comes back as the same buffer (``input_output_aliases``:
the engine donates its cache tree through every step, so nothing is
copied).  On a TPU-placed computation (or under ``interpret``) a Pallas
kernel walks the rows: row ``r``'s block is ``(layer, slots[r])``, found
through scalar prefetch, so a step reads and writes exactly the state of
the slots its rows hold (padding rows aim at slot 0, the scratch slot)
and never gathers it.  All arithmetic is float32 on the vector unit.
Elsewhere the identical-math XLA gather / scatter runs.

The ``D x`` skip term, ``softplus`` and the gate are the model's
(serving/nemotron_h.py): this is the state's own arithmetic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import take_pallas

__all__ = ["ssm_state_update", "mamba1_state_update"]


def _kernel(layer_ref, slots_ref, decay_ref, xt_ref, b_ref, c_ref, s_ref,
            y_ref, o_ref, *, per):
    """One row: ``s_ref`` / ``o_ref`` [H, P, N] (the same HBM block),
    ``decay_ref`` [R, H] in SMEM (every row's), ``xt_ref`` [G, P, per] (dt x, heads of
    a group along the lanes), ``b_ref`` / ``c_ref`` [G, N]; ``y_ref``
    [G, P, per]."""
    n_groups = xt_ref.shape[0]
    row = pl.program_id(0)
    lane = jax.lax.broadcasted_iota(jnp.int32, xt_ref.shape[1:], 1)

    def group(g, carry):
        xg = xt_ref[g]                                   # [P, per]
        brow = b_ref[pl.ds(g, 1), :]                     # [1, N]
        crow = c_ref[pl.ds(g, 1), :]
        yg = jnp.zeros_like(xg)
        for j in range(per):
            h = g * per + j
            new = s_ref[h] * decay_ref[row, h] + xg[:, j:j + 1] * brow
            o_ref[h] = new
            col = jnp.sum(new * crow, axis=1, keepdims=True)     # [P, 1]
            yg = jnp.where(lane == j, col, yg)
        y_ref[g] = yg
        return carry

    jax.lax.fori_loop(0, n_groups, group, 0)


@functools.partial(jax.jit, inline=True, static_argnames=("interpret",))
def _update_call(layer, slots, decay, xt, b, c, state, *, interpret):
    """The kernel's call; ``layer`` [1] and ``slots`` [R] ride scalar
    prefetch.  Under an inline jit with the layer an operand: a step's
    Mamba layers share one trace and one Mosaic compile."""
    r, g, p, per = xt.shape
    _, _, h, _, n = state.shape
    row = lambda i, l, s: (i, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),     # decay, whole
            pl.BlockSpec((None, g, p, per), row),
            pl.BlockSpec((None, g, n), lambda i, l, s: (i, 0, 0)),
            pl.BlockSpec((None, g, n), lambda i, l, s: (i, 0, 0)),
            pl.BlockSpec((None, None, h, p, n),
                         lambda i, l, s: (l[0], s[i], 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, g, p, per), row),
            pl.BlockSpec((None, None, h, p, n),
                         lambda i, l, s: (l[0], s[i], 0, 0, 0)),
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, per=per),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(xt.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 6 (the two prefetched scalars count) is the state
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="ssm_state_update",
        metadata={"kernel": "ssm_state_update"},
    )(layer, slots, decay, xt, b, c, state)


def ssm_state_update(state, slots, x, dt, a, b, c, layer=0,
                     force_xla=False, interpret=False):
    """``state`` [L, slots, H, P, N] float32; ``slots`` [R] int32; ``x``
    [R, H, P], ``dt`` [R, H], ``a`` [H] (negative), ``b`` / ``c`` [R, G,
    N], all float32; ``layer`` a static index.  Returns ``(y [R, H, P],
    the updated state)``."""
    n_l, n_slots, h, p, n = state.shape
    r, g, _ = b.shape
    per = h // g
    assert x.shape == (r, h, p) and dt.shape == (r, h) and h % g == 0
    assert 0 <= layer < n_l, (layer, state.shape)
    slots = slots.astype(jnp.int32)
    decay = jnp.exp(dt * a)                                  # [R, H]
    dtx = dt[..., None] * x                                  # [R, H, P]
    if not take_pallas("ssm_state_update", True, force_xla, interpret):
        b_h, c_h = jnp.repeat(b, per, axis=1), jnp.repeat(c, per, axis=1)
        new = (state[layer, slots] * decay[..., None, None]
               + dtx[..., None] * b_h[:, :, None, :])
        y = jnp.sum(new * c_h[:, :, None, :], axis=-1)
        return y, state.at[layer, slots].set(new)
    # heads of a group along the lanes, the head's P along the sublanes:
    # a head's column of dt x is then a static lane of its group's tile
    xt = dtx.reshape(r, g, per, p).transpose(0, 1, 3, 2)
    yt, state = _update_call(jnp.full((1,), layer, jnp.int32), slots, decay,
                             xt, b, c, state, interpret=interpret)
    return yt.transpose(0, 1, 3, 2).reshape(r, h, p), state


# ---------------------------------------------------------------------------
# Mamba-1
# ---------------------------------------------------------------------------
#
# For row ``r`` with state slot ``s = slots[r]``, channel ``d`` and state
# index ``n``:
#
#     S[s, n, d] <- exp(dt[r, d] A[n, d]) S[s, n, d] + dt[r, d] x[r, d] B[r, n]
#     y[r, d]     = sum_n S[s, n, d] C[r, n]
#
# ``state`` is ``[L, slots, N, D]`` float32, the channels (thousands) on
# the lanes and the N (16) state indices on the sublanes, so that a row's
# state is whole (8, 128) tiles.  The kernel walks the rows as the Mamba-2
# one does (the row's block is ``(layer, slots[r])``, found through scalar
# prefetch) and computes the decay itself, on the vector unit, from the
# row's dt and the layer's ``A``: nothing of size ``[R, N, D]`` is written
# to memory but the state.  It also writes each row's new conv window
# (``window[r]``) into its slot of the model's window array ``conv``,
# ``[L, slots, W, D']``: a slot's window whole (8, 128) tiles, written
# by the same walk (a scatter of 128 rows of it by XLA took ~130 µs a
# layer on the v5e, serialised row by row; a slot's row of a flat
# ``[L, slots, (K-1) D]`` array is one sublane, which a DMA cannot
# address).


def column(row, n):
    """A ``[1, n]`` row as an ``[n, 1]`` column, inside a kernel: the
    diagonal of its broadcast, summed along the lanes."""
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _mamba1_kernel(layer_ref, slots_ref, x_ref, dt_ref, a_ref, b_ref, c_ref,
                   w_ref, s_ref, conv_ref, y_ref, o_ref, win_ref):
    """One row: ``s_ref`` / ``o_ref`` [N, D] (the same HBM block),
    ``x_ref`` / ``dt_ref`` / ``y_ref`` [1, D], ``a_ref`` [N, D],
    ``b_ref`` / ``c_ref`` [1, N]; ``w_ref`` the row's new window, which
    ``win_ref`` (its slot's block of ``conv_ref``, left in HBM and not
    read) takes."""
    n = a_ref.shape[0]
    dt = dt_ref[...]
    new = (jnp.exp(dt * a_ref[...]) * s_ref[...]
           + column(b_ref[...], n) * (dt * x_ref[...]))
    o_ref[...] = new
    y_ref[...] = jnp.sum(new * column(c_ref[...], n), axis=0, keepdims=True)
    win_ref[...] = w_ref[...]


@functools.partial(jax.jit, inline=True, static_argnames=("interpret",))
def _mamba1_call(layer, slots, x, dt, a, b, c, window, state, conv, *,
                 interpret):
    """``x`` / ``dt`` [R, 1, D], ``b`` / ``c`` [R, 1, N], ``window`` [R,
    W, D']; ``layer`` [1] and ``slots`` [R] ride scalar prefetch, as in
    ``_update_call``."""
    r, _, d = x.shape
    n = a.shape[0]
    _, rows, lanes = window.shape
    row = lambda i, l, s: (i, 0, 0)
    slab = pl.BlockSpec((None, None, n, d),
                        lambda i, l, s: (l[0], s[i], 0, 0))
    win = pl.BlockSpec((None, None, rows, lanes),
                       lambda i, l, s: (l[0], s[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r,),
        in_specs=[
            pl.BlockSpec((None, 1, d), row),
            pl.BlockSpec((None, 1, d), row),
            pl.BlockSpec((n, d), lambda i, l, s: (0, 0)),
            pl.BlockSpec((None, 1, n), row),
            pl.BlockSpec((None, 1, n), row),
            pl.BlockSpec((None, rows, lanes), row),
            slab,
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[pl.BlockSpec((None, 1, d), row), slab, win],
    )
    return pl.pallas_call(
        _mamba1_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(conv.shape, conv.dtype)],
        # operands 8 and 9 (the two prefetched scalars count): the state
        # and the windows
        input_output_aliases={8: 1, 9: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="mamba1_state_update",
        metadata={"kernel": "mamba1_state_update"},
    )(layer, slots, x, dt, a, b, c, window, state, conv)


def mamba1_state_update(state, conv, slots, x, dt, a, b, c, window, layer=0,
                        force_xla=False, interpret=False):
    """``state`` [L, slots, N, D] float32; ``conv`` [L, slots, W, D']
    float32, the conv windows; ``slots`` [R] int32; ``x`` / ``dt`` [R,
    D], ``a`` [N, D] (negative), ``b`` / ``c`` [R, N], ``window`` [R, W,
    D'] (each row's new window), all float32; ``layer`` an index, static
    or traced (a scan over the layers).  Returns ``(y [R, D], the
    updated state, the updated windows)``; ``y`` is ``S C`` before the
    model's ``D x`` skip."""
    n_l, _, n, d = state.shape
    r = x.shape[0]
    assert x.shape == dt.shape == (r, d) and a.shape == (n, d)
    assert b.shape == c.shape == (r, n)
    assert window.shape == (r,) + conv.shape[2:]
    assert not isinstance(layer, int) or 0 <= layer < n_l, (layer, n_l)
    slots = slots.astype(jnp.int32)
    if not take_pallas("mamba1_state_update", True, force_xla, interpret):
        new = (jnp.exp(dt[:, None, :] * a) * state[layer, slots]
               + b[:, :, None] * (dt * x)[:, None, :])
        y = jnp.sum(new * c[:, :, None], axis=1)
        return (y, state.at[layer, slots].set(new),
                conv.at[layer, slots].set(window))
    y, state, conv = _mamba1_call(
        jnp.full((1,), layer, jnp.int32), slots, x[:, None], dt[:, None], a,
        b[:, None], c[:, None], window, state, conv, interpret=interpret)
    return y[:, 0], state, conv
