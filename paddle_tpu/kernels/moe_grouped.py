"""The grouped expert matmul: a mixture-of-experts layer's routed part
for the experts THIS chip holds, reading only the experts the step's
tokens hit.

``grouped_expert_ffn(x, expert_ids, gates, w1, w2, layer)`` computes for
every token ``t``

    r_t = sum_k gates[t, k] * W2[e] relu(W1[e] x_t)^2,   e = expert_ids[t, k]

over the pairs ``(t, k)`` whose expert is held here (``0 <= e < E``; a
pair routed to an expert another chip holds is marked ``-1`` and adds
nothing: expert parallelism's own contract, serving/nemotron_h.py).
``w1`` / ``w2`` are every expert layer's held experts stacked,
``[L, E, d_in, d_ff]`` / ``[L, E, d_ff, d_out]``, handed over WHOLE with
``layer`` the index of the layer that runs (a ``w[layer]`` in front of a
Mosaic call would copy the layer's experts: PR 26's lesson).

On a TPU-placed computation (or under ``interpret``) the pairs are
sorted by expert, each expert's tokens padded to whole row tiles, and a
Pallas kernel walks the tiles, ``d_ff`` chunk by chunk inside each: a
tile's weights are the blocks ``(layer, tile's expert, d_ff chunk)``,
found through scalar prefetch, so an expert nobody chose is never read;
tiles past the last real one repeat its block index and skip their
work.  Consecutive tiles of one expert share a fetch only where ``d_ff``
is one chunk: with more, the next tile starts again at chunk 0, so an
expert whose pairs span two tiles streams its weights twice.  One entry
serves a decode step, a decode step that carries a prompt and a prefill:
the row tile follows the pairs a held expert can expect (``row_tile``).
Elsewhere the identical-math XLA path runs, one expert at a time.

Activations enter each product in the weights' dtype (bfloat16) and
every product accumulates in float32; the result is float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import take_pallas

__all__ = ["grouped_expert_ffn", "row_tile"]

_W_BLOCK_BYTES = 2 << 20       # one weight block in VMEM, at most

# (row tile, the most pairs a held expert may expect for it): where a
# v5e sweep at 128 held experts of 1024 x 2688, top-22 of 512, found the
# next tile faster (between 2.75 and 5.5 pairs; between 13.75 and 22);
# the last doubles the one before: the sweep reached 24.75 pairs
_TILES = ((16, 4.0), (32, 16.0), (64, 32.0))
_TILE_MAX = 128                # the MXU's own


def row_tile(pairs, n_held, n_routed=None):
    """The row tile for ``pairs`` token-expert pairs routed over
    ``n_routed`` experts (default ``n_held``: every expert held here).

    A held expert expects ``pairs / n_routed`` of them.  A tile much
    larger than that is padding the MXU computes and the kernel writes
    out; one smaller splits an expert over two tiles, which streams its
    weights twice (the module's docstring); and every tile the worst
    case allows, ``ceil(pairs / tm) + n_held``, is a grid step even when
    it is empty, so a small tile also costs more empty steps.  Routing
    concentrates (a few experts get several times the mean), so the
    best tile is two to six times the expectation: the smallest a
    bfloat16 operand fills, 16 rows, at a decode step's few pairs, 64
    rows over a 512-token prompt."""
    expect = pairs / float(n_routed or n_held)
    for tm, most in _TILES:
        if expect <= most:
            return tm
    return _TILE_MAX


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0))


def _ff_chunks(d_in, d_ff, itemsize):
    """How many chunks the d_ff axis is cut into: the fewest whose weight
    block fits ``_W_BLOCK_BYTES`` with 128-lane chunks; 1 where d_ff has
    no such divisor (small test sizes)."""
    if d_ff % 128:
        return 1
    lanes = d_ff // 128
    for n in range(1, lanes + 1):
        if lanes % n == 0 and d_in * (d_ff // n) * itemsize <= _W_BLOCK_BYTES:
            return n
    return lanes


def _kernel(layer_ref, tile_expert_ref, n_tiles_ref, x_ref, w1_ref, w2_ref,
            o_ref):
    t, f = pl.program_id(0), pl.program_id(1)

    @pl.when(f == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(t < n_tiles_ref[0])
    def _():
        h = jnp.dot(x_ref[...], w1_ref[...],
                    preferred_element_type=jnp.float32)
        h = _relu2(h).astype(w2_ref.dtype)
        o_ref[...] += jnp.dot(h, w2_ref[...],
                              preferred_element_type=jnp.float32)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("tm", "interpret"))
def _grouped_call(layer, tile_expert, n_tiles, rows, w1, w2, *, tm,
                  interpret):
    """The kernel's call over the sorted, padded rows ``[n_tiles_max *
    tm, d_in]``; ``layer`` [1], ``tile_expert`` [n_tiles_max] and
    ``n_tiles`` [1] ride scalar prefetch.  Under an inline jit with the
    layer an operand: a step's expert layers share one trace and one
    Mosaic compile."""
    n_rows, d_in = rows.shape
    _, _, _, d_ff = w1.shape
    d_out = w2.shape[-1]
    n_f = _ff_chunks(max(d_in, d_out), d_ff, w1.dtype.itemsize)
    fc = d_ff // n_f

    def chunk(t, f, nt):
        # a tile past the last real one keeps the block of the step
        # before it: nothing is fetched for it
        return jnp.where(t < nt[0], f, n_f - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_rows // tm, n_f),
        in_specs=[
            pl.BlockSpec((tm, d_in), lambda t, f, l, te, nt: (t, 0)),
            pl.BlockSpec((None, None, d_in, fc),
                         lambda t, f, l, te, nt: (l[0], te[t], 0,
                                                  chunk(t, f, nt))),
            pl.BlockSpec((None, None, fc, d_out),
                         lambda t, f, l, te, nt: (l[0], te[t],
                                                  chunk(t, f, nt), 0)),
        ],
        out_specs=pl.BlockSpec((tm, d_out), lambda t, f, l, te, nt: (t, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, d_out), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        interpret=interpret,
        name="grouped_expert_ffn",
        metadata={"kernel": "grouped_expert_ffn"},
    )(layer, tile_expert, n_tiles, rows, w1, w2)


def _plan(expert_ids, n_experts, tm):
    """Where each pair's row lies once the held pairs are sorted by
    expert and every expert's group is padded to whole tiles of ``tm``
    rows.  Returns (tile_expert [n_tiles_max], n_tiles [1], pair of each
    row [n_rows] or -1, row of each pair [P] or -1, tokens per expert
    [E])."""
    p = expert_ids.size
    flat = expert_ids.reshape(p)
    held = (flat >= 0) & (flat < n_experts)
    flat = jnp.where(held, flat, n_experts)            # not held: last
    counts = jnp.zeros(n_experts + 1, jnp.int32).at[flat].add(1)[:n_experts]
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    tiles_e = (counts + tm - 1) // tm
    tile_end = jnp.cumsum(tiles_e)
    tile_start = tile_end - tiles_e
    pair_start = jnp.cumsum(counts) - counts           # in sorted order
    n_tiles = tile_end[-1]
    n_tiles_max = -(-p // tm) + n_experts
    t = jnp.arange(n_tiles_max, dtype=jnp.int32)
    tile_expert = jnp.searchsorted(tile_end, t, side="right").astype(
        jnp.int32)
    last = tile_expert[jnp.maximum(n_tiles - 1, 0)]
    tile_expert = jnp.minimum(jnp.where(t < n_tiles, tile_expert, last),
                              n_experts - 1)
    # rows -> pairs
    row_t = jnp.repeat(t, tm)
    row_e = tile_expert[row_t]
    rank = (row_t - tile_start[row_e]) * tm + jnp.tile(
        jnp.arange(tm, dtype=jnp.int32), n_tiles_max)
    live = (row_t < n_tiles) & (rank < counts[row_e])
    src = jnp.clip(pair_start[row_e] + rank, 0, p - 1)
    row_pair = jnp.where(live, order[src], -1)
    # pairs -> rows
    inv = jnp.zeros(p, jnp.int32).at[order].set(
        jnp.arange(p, dtype=jnp.int32))
    e_of = jnp.minimum(flat, n_experts - 1)
    pair_row = jnp.where(
        held, tile_start[e_of] * tm + inv - pair_start[e_of], -1)
    return tile_expert, n_tiles.reshape(1), row_pair, pair_row, counts


def _xla(x, expert_ids, gates, w1, w2):
    """One held expert at a time over all tokens, weighted by what the
    router gave it (0 where it was not chosen): the same products in the
    same dtypes as the kernel."""
    def one(total, args):
        e, a, b = args
        g = jnp.sum(jnp.where(expert_ids == e, gates, 0.0), axis=-1)
        h = jnp.dot(x, a, preferred_element_type=jnp.float32)
        y = jnp.dot(_relu2(h).astype(b.dtype), b,
                    preferred_element_type=jnp.float32)
        return total + g[:, None] * y, None

    total, _ = jax.lax.scan(
        one, jnp.zeros((x.shape[0], w2.shape[-1]), jnp.float32),
        (jnp.arange(w1.shape[0]), w1, w2))
    return total


def grouped_expert_ffn(x, expert_ids, gates, w1, w2, layer=0, n_routed=None,
                       force_xla=False, interpret=False):
    """``x`` [T, d_in] (the weights' dtype); ``expert_ids`` [T, K] int32,
    the held expert's local index or -1; ``gates`` [T, K] float32;
    ``w1`` [L, E, d_in, d_ff], ``w2`` [L, E, d_ff, d_out], ``layer`` a
    static index; ``n_routed`` how many experts the router chose among
    (default E: every one is held here).  Returns ``(r [T, d_out]
    float32, experts hit: how many held experts got a token, rows
    computed: the kernel's real tiles x their rows, or E x T on the XLA
    path)``, the two counts int32 scalars."""
    n_tok, k = expert_ids.shape
    n_l, n_e, d_in, d_ff = w1.shape
    assert w2.shape[:3] == (n_l, n_e, d_ff) and x.shape == (n_tok, d_in)
    assert 0 <= layer < n_l, (layer, w1.shape)
    x = x.astype(w1.dtype)
    expert_ids = expert_ids.astype(jnp.int32)
    held = (expert_ids >= 0) & (expert_ids < n_e)
    if not take_pallas("grouped_expert_ffn", True, force_xla, interpret):
        hit = jnp.sum(jnp.zeros(n_e + 1, jnp.int32).at[
            jnp.where(held, expert_ids, n_e)].max(1)[:n_e])
        return (_xla(x, jnp.where(held, expert_ids, -1), gates, w1[layer],
                     w2[layer]), hit, jnp.int32(n_e * n_tok))
    tm = row_tile(n_tok * k, n_e, n_routed)
    tile_expert, n_tiles, row_pair, pair_row, counts = _plan(
        expert_ids, n_e, tm)
    rows = jnp.where((row_pair >= 0)[:, None],
                     x[jnp.maximum(row_pair, 0) // k], 0)
    y = _grouped_call(jnp.full((1,), layer, jnp.int32), tile_expert, n_tiles,
                      rows, w1, w2, tm=tm, interpret=interpret)
    picked = y[jnp.maximum(pair_row, 0)].reshape(n_tok, k, -1)
    gates = jnp.where(held, gates, 0.0)
    return (jnp.sum(gates[..., None] * picked, axis=1),
            jnp.sum(counts > 0).astype(jnp.int32), n_tiles[0] * tm)
