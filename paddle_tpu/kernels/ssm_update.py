"""The decode step's state update of a Mamba layer: one token a row
through the selective state space, the state updated IN PLACE.  Two
forms: ``ssm_state_update`` for Mamba-2 (one decay a head) and
``mamba1_state_update`` for Mamba-1 (a decay a channel and state index,
at the end of this file).

For row ``r`` with state slot ``s = slots[r]``, head ``h`` of group
``g = h // (H / G)``:

    S[s, h] <- exp(dt[r, h] A[h]) S[s, h] + dt[r, h] x[r, h] (x) B[r, g]
    y[r, h]  = S[s, h] C[r, g]

``state`` is every Mamba layer's state for every slot, ``[L, slots, N,
H P]`` float32: the N state indices on the sublanes and the head
channels (head ``h``'s P at ``h P .. h P + P - 1``) on the lanes, so a
group's heads are ``H P / G`` contiguous lanes and a slot's state is
whole (8, 128) tiles.  It is handed over WHOLE with ``layer`` the index
of the layer that runs, and comes back as the same buffer
(``input_output_aliases``: the engine donates its cache tree through
every step, so nothing is copied).  On a TPU-placed computation (or
under ``interpret``) a Pallas kernel walks the rows: row ``r``'s block
is ``(layer, slots[r])``, found through scalar prefetch, so a step reads
and writes exactly the state of the slots its rows hold (padding rows
aim at slot 0, the scratch slot) and never gathers it.  In that layout
the decay and ``dt x`` are rows of the lanes, ``B`` and ``C`` a column
a group, and ``y`` a sum over the sublanes: no step of the update moves
data across lanes but the one broadcast of each group's columns.  All
arithmetic is float32 on the vector unit.  Elsewhere the identical-math
XLA gather / scatter runs.

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


def _kernel(layer_ref, slots_ref, decay_ref, dtx_ref, b_ref, c_ref, s_ref,
            y_ref, o_ref):
    """One row: ``s_ref`` / ``o_ref`` [N, H P] (the same HBM block),
    ``decay_ref`` / ``dtx_ref`` / ``y_ref`` [1, H P] (a head's decay
    repeated over its P channels; dt x), ``b_ref`` / ``c_ref`` [N, G]
    (a group's B and C a column).  A group's lanes are walked a lane
    tile at a time."""
    n, hp = s_ref.shape
    groups = b_ref.shape[1]
    width = hp // groups
    tile = 128 if width % 128 == 0 else width
    for g in range(groups):
        bb = jnp.broadcast_to(b_ref[:, g:g + 1], (n, tile))
        cc = jnp.broadcast_to(c_ref[:, g:g + 1], (n, tile))
        for t in range(g * width, (g + 1) * width, tile):
            new = (s_ref[:, t:t + tile] * decay_ref[:, t:t + tile]
                   + bb * dtx_ref[:, t:t + tile])
            o_ref[:, t:t + tile] = new
            y_ref[:, t:t + tile] = jnp.sum(new * cc, axis=0, keepdims=True)


@functools.partial(jax.jit, inline=True, static_argnames=("interpret",))
def _update_call(layer, slots, decay, dtx, b, c, state, *, interpret):
    """The kernel's call: ``decay`` / ``dtx`` [R, 1, H P], ``b`` / ``c``
    [R, N, G]; ``layer`` [1] and ``slots`` [R] ride scalar prefetch.
    Under an inline jit with the layer an operand: a step's Mamba layers
    share one trace and one Mosaic compile."""
    r, _, hp = dtx.shape
    n, g = b.shape[1:]
    row = lambda i, l, s: (i, 0, 0)
    slab = pl.BlockSpec((None, None, n, hp),
                        lambda i, l, s: (l[0], s[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r,),
        in_specs=[
            pl.BlockSpec((None, 1, hp), row),
            pl.BlockSpec((None, 1, hp), row),
            pl.BlockSpec((None, n, g), row),
            pl.BlockSpec((None, n, g), row),
            slab,
        ],
        out_specs=[pl.BlockSpec((None, 1, hp), row), slab],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(dtx.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 6 (the two prefetched scalars count) is the state
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name="ssm_state_update",
        metadata={"kernel": "ssm_state_update"},
    )(layer, slots, decay, dtx, b, c, state)


def ssm_state_update(state, slots, x, dt, a, b, c, layer=0,
                     force_xla=False, interpret=False):
    """``state`` [L, slots, N, H P] float32; ``slots`` [R] int32; ``x``
    [R, H, P], ``dt`` [R, H], ``a`` [H] (negative), ``b`` / ``c`` [R, G,
    N], all float32; ``layer`` a static index.  Returns ``(y [R, H, P],
    the updated state)``."""
    n_l, _, n, hp = state.shape
    r, h, p = x.shape
    g = b.shape[1]
    assert hp == h * p and dt.shape == (r, h) and h % g == 0
    assert b.shape == c.shape == (r, g, n), (b.shape, c.shape, state.shape)
    assert 0 <= layer < n_l, (layer, state.shape)
    slots = slots.astype(jnp.int32)
    # a head's decay repeated over its P lanes, and dt x, as lane rows
    decay = jnp.repeat(jnp.exp(dt * a), p, axis=1)           # [R, H P]
    dtx = (dt[..., None] * x).reshape(r, hp)                 # [R, H P]
    bt, ct = b.transpose(0, 2, 1), c.transpose(0, 2, 1)      # [R, N, G]
    if not take_pallas("ssm_state_update", True, force_xla, interpret):
        new = (state[layer, slots].reshape(r, n, g, -1)
               * decay.reshape(r, 1, g, -1)
               + bt[..., None] * dtx.reshape(r, 1, g, -1))
        y = jnp.sum(new * ct[..., None], axis=1)             # [R, G, H P / G]
        return (y.reshape(r, h, p),
                state.at[layer, slots].set(new.reshape(r, n, hp)))
    y, state = _update_call(jnp.full((1,), layer, jnp.int32), slots,
                            decay[:, None], dtx[:, None], bt, ct, state,
                            interpret=interpret)
    return y.reshape(r, h, p), state


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
