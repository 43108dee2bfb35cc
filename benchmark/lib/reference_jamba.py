"""The plain model the ``jamba`` cell is held to: AI21-Jamba2-3B's decoder
as its ``config.json`` describes it (HF ``JambaForCausalLM`` with one
expert, so every feed-forward is the dense MLP), in straightforward
``jax.numpy``: float32, every product at
``jax.default_matmul_precision("highest")``, the state space as a plain
sequential loop over the tokens, no kernels, no cache, no batching
tricks.  It imports nothing of the program and takes nothing the program
made: the weights come from ``init_leaf`` (the seed), which the driver
also uses to fill the program, rounded to bfloat16 where the
configuration stores bfloat16, as the program's are.

``sizes``: ``vocab``, ``hidden``, ``pattern`` (``M`` Mamba-1, ``*``
attention, one letter a layer), ``n_heads``, ``n_kv_heads``,
``d_inner``, ``state``, ``dt_rank``, ``conv_kernel``, ``ff``, ``eps``
and the Δ initialisation's ``time_step_min`` / ``_max`` / ``_floor``.
Every layer is pre-norm twice, ``h <- h + mixer(RMSNorm(h))`` then ``h
<- h + down(silu(gate u) * up u)``, ``u = RMSNorm(h)``, with

- ``M``: ``[x | z] = W_in u``; ``x <- silu(causal_conv1d(x) + b)``;
  ``[delta | B | C] = W_x x`` (R, N, N), each through its own RMSNorm;
  ``dt = softplus(W_dt delta + dt_bias)`` [D]; ``A = -exp(A_log)`` [N,
  D]; per token ``S[n, d] <- exp(dt_d A[n, d]) S[n, d] + dt_d x_d B_n``,
  ``y_d = sum_n S[n, d] C_n + D_d x_d``; ``out = W_out (y * silu(z))``;
- ``*``: ``q, k, v`` without bias, one K/V head shared by every query
  head, no positional encoding, causal softmax at ``1/sqrt(head_dim)``,
  ``W_o``;

and after the last layer an RMSNorm and the head, which is the
embedding's transpose (``tie_word_embeddings``).

On the chip the model is walked layer by layer: one layer's weights are
made from the seed, widened to float32, applied to every request, and
dropped, so that it fits beside nothing else after ``release()``.

``control`` puts something else in the program's place (never a
benchmark run; ``controls.py`` reads what the comparison makes of it):
``bf16`` carries everything the configuration states as float32 in
bfloat16 (products' results, norm statistics, decay, conv, state,
softmax, logits): the nearest precision below the stated one;
``bf16_state`` rounds the SSM state alone to bfloat16 after every token;
``int8_products`` rounds both operands of every product against a
weight matrix to int8 under a per-tensor scale; ``altered_token`` is no
precision: the served tokens as they are, but one a request stepped to
its neighbour in the vocabulary, in the comparison alone.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

CONTROLS = ("bf16", "bf16_state", "int8_products", "altered_token")
bf16, f32 = jnp.bfloat16, jnp.float32
KINDS = ("top", "M", "*", "F")      # F: the feed-forward every layer has


def leaf_specs(sizes):
    """{kind: {leaf: (shape, draw, dtype)}}.  Matrices are bfloat16; the
    small vectors the family keeps in float32 (norm weights, conv,
    dt_bias, A_log, D) are float32.  ``A_log`` is stored [N, D], the
    state's own layout; ``x_proj`` holds delta, B and C side by side and
    ``gate_up`` the gate and the up projection.  The order of a kind's
    leaves is part of the seed's meaning."""
    s = sizes
    d, di, n, r = s["hidden"], s["d_inner"], s["state"], s["dt_rank"]
    hd = d // s["n_heads"]
    return {
        "top": {"embed": ((s["vocab"], d), "normal", bf16),
                "norm_f": ((d,), "gain", f32)},
        "M": {"norm": ((d,), "gain", f32),
              "in_proj": ((d, 2 * di), "normal", bf16),
              "conv_w": ((s["conv_kernel"], di), "conv", f32),
              "conv_b": ((di,), "conv", f32),
              "x_proj": ((di, r + 2 * n), "normal", bf16),
              "dt_norm": ((r,), "gain", f32),
              "b_norm": ((n,), "gain", f32),
              "c_norm": ((n,), "gain", f32),
              "dt_proj": ((r, di), "dtw", bf16),
              "dt_bias": ((di,), "dt", f32),
              "A_log": ((n, di), "A", f32),
              "D": ((di,), "one", f32),
              "out_proj": ((di, d), "normal", bf16)},
        "*": {"norm": ((d,), "gain", f32),
              "wqkv": ((d, (s["n_heads"] + 2 * s["n_kv_heads"]) * hd),
                       "normal", bf16),
              "wo": ((s["n_heads"] * hd, d), "normal", bf16)},
        "F": {"norm": ((d,), "gain", f32),
              "gate_up": ((d, 2 * s["ff"]), "normal", bf16),
              "down": ((s["ff"], d), "normal", bf16)},
    }


def seed_key(seed):
    """A PRNG key from any whole number up to 2**63."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _draw(key, shape, how, dtype, sizes):
    """One leaf as the family initialises it: normal 0.02; ``gain`` 1 +
    0.1 n (off its trivial value, so that a dropped one shows); the
    conv's filter and bias uniform in +-1/sqrt(kernel) (the default of
    the layer it is); ``dtw`` uniform in +-1/sqrt(dt_rank) and ``dt``
    the inverse softplus of a step log-uniform in [time_step_min,
    time_step_max], floored (Mamba-1's Δ initialisation); ``A`` =
    log(1..N) in every channel (Mamba-1's ``A_log``); ``one``: D."""
    if how == "one":
        return jnp.ones(shape, dtype)
    if how == "A":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[0] + 1, dtype=f32))[:, None], shape)
    if how == "dt":
        lo, hi = math.log(sizes["time_step_min"]), math.log(
            sizes["time_step_max"])
        dt = jnp.exp(jax.random.uniform(key, shape, f32) * (hi - lo) + lo)
        dt = jnp.maximum(dt, sizes["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))
    if how in ("conv", "dtw"):
        bound = 1.0 / math.sqrt(sizes["conv_kernel"] if how == "conv"
                                else sizes["dt_rank"])
        return jax.random.uniform(key, shape, f32, -bound, bound).astype(
            dtype)
    n = jax.random.normal(key, shape, f32)
    return (1.0 + 0.1 * n if how == "gain" else 0.02 * n).astype(dtype)


@functools.partial(jax.jit, static_argnames=("kind", "leaf", "frozen"))
def _init_leaf(key, layers, kind, leaf, frozen):
    sizes = dict(frozen)
    specs = leaf_specs(sizes)[kind]
    shape, how, dtype = specs[leaf]
    key = jax.random.fold_in(key, KINDS.index(kind))
    index = list(specs).index(leaf)
    return lax.map(lambda layer: _draw(
        jax.random.fold_in(jax.random.fold_in(key, layer), index), shape,
        how, dtype, sizes), layers)


def _frozen(sizes):
    return tuple(sorted(sizes.items()))


def init_leaf(seed, sizes, kind, leaf, layers):
    """Leaf ``leaf`` of kind ``kind`` for the layers ``layers`` (their
    indices in the pattern; ``[0]`` for kind ``top``), stacked on a
    leading axis, on the device, made in place."""
    return _init_leaf(seed_key(seed), jnp.asarray(layers, jnp.int32), kind,
                      leaf, _frozen(sizes))


def init_layer(seed, sizes, kind, i):
    """{leaf: array} of kind ``kind`` at layer ``i``."""
    return {leaf: init_leaf(seed, sizes, kind, leaf, [i])[0]
            for leaf in leaf_specs(sizes)[kind]}


def stacked_params(seed, sizes):
    """Every leaf of the model, the layers of one kind stacked on a
    leading axis under ``<kind>.<leaf>`` (what no layer owns under its
    own name): the layout the served model takes.  Made leaf by leaf on
    the device, nothing twice."""
    pattern = sizes["pattern"]
    out = {leaf: init_leaf(seed, sizes, "top", leaf, [0])[0]
           for leaf in leaf_specs(sizes)["top"]}
    for kind in KINDS[1:]:
        layers = [i for i, k in enumerate(pattern) if kind in ("F", k)]
        for leaf in leaf_specs(sizes)[kind]:
            out["%s.%s" % (kind, leaf)] = init_leaf(seed, sizes, kind, leaf,
                                                    layers)
    return out


# ---------------------------------------------------------------------------
# the layers
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * w


def _round_int8(w):
    amax = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30)
    return jnp.round(w * (127.0 / amax)) * (amax / 127.0)


def _mm(x, w, control):
    """A product against a weight matrix."""
    if control == "int8_products":
        x, w = _round_int8(x), _round_int8(w)
    return x @ w


def mamba_block(sizes, lp, u, control=None):
    """u [B, T, d] (normed) -> the mixer's output [B, T, d]."""
    s, eps = sizes, sizes["eps"]
    b, t, _ = u.shape
    di, n, r, k = s["d_inner"], s["state"], s["dt_rank"], s["conv_kernel"]
    xz = _mm(u, lp["in_proj"], control)
    x, z = xz[..., :di], xz[..., di:]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + t] * lp["conv_w"][j] for j in range(k))
    x = jax.nn.silu(conv + lp["conv_b"])
    dbc = _mm(x, lp["x_proj"], control)
    delta = _rms(dbc[..., :r], lp["dt_norm"], eps)
    bs = _rms(dbc[..., r:r + n], lp["b_norm"], eps)
    cs = _rms(dbc[..., r + n:], lp["c_norm"], eps)
    dt = jax.nn.softplus(_mm(delta, lp["dt_proj"], control) + lp["dt_bias"])
    a = -jnp.exp(lp["A_log"])                                  # [N, D]
    kept = bf16 if control == "bf16_state" else u.dtype

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp                  # [B, D] [B, D] [B, N]
        state = (jnp.exp(dt_t[:, None, :] * a) * state.astype(u.dtype)
                 + b_t[:, :, None] * (dt_t * x_t)[:, None, :]).astype(kept)
        return state, jnp.sum(state.astype(u.dtype) * c_t[:, :, None],
                              axis=1)

    _, ys = lax.scan(step, jnp.zeros((b, n, di), kept),
                     (x.swapaxes(0, 1), dt.swapaxes(0, 1),
                      bs.swapaxes(0, 1), cs.swapaxes(0, 1)))
    y = (ys.swapaxes(0, 1) + lp["D"] * x) * jax.nn.silu(z)
    return _mm(y, lp["out_proj"], control)


def attention_block(sizes, lp, u, control=None):
    s = sizes
    b, t, d = u.shape
    nh, nkv = s["n_heads"], s["n_kv_heads"]
    hd = d // nh
    qkv = _mm(u, lp["wqkv"], control)
    q = qkv[..., :nh * hd].reshape(b, t, nkv, nh // nkv, hd)
    k = qkv[..., nh * hd:(nh + nkv) * hd].reshape(b, t, nkv, hd)
    v = qkv[..., (nh + nkv) * hd:].reshape(b, t, nkv, hd)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
    return _mm(att.reshape(b, t, nh * hd), lp["wo"], control)


def mlp_block(sizes, lp, u, control=None):
    gu = _mm(u, lp["gate_up"], control)
    ff = sizes["ff"]
    return _mm(jax.nn.silu(gu[..., :ff]) * gu[..., ff:], lp["down"], control)


@functools.partial(jax.jit, static_argnames=("kind", "frozen", "control"),
                   donate_argnums=(0,))
def _layer(h, lp, fp, kind, frozen, control):
    sizes = dict(frozen)
    eps = sizes["eps"]
    # every array in the stream's dtype: float32, or ``bf16``'s
    lp = {k: v.astype(h.dtype) for k, v in lp.items()}
    fp = {k: v.astype(h.dtype) for k, v in fp.items()}
    with jax.default_matmul_precision("highest"):
        u = _rms(h, lp["norm"], eps)
        if kind == "M":
            h = h + mamba_block(sizes, lp, u, control)
        else:
            h = h + attention_block(sizes, lp, u, control)
        return h + mlp_block(sizes, fp, _rms(h, fp["norm"], eps), control)


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _head(h_rows, top, eps, control):
    top = {k: v.astype(h_rows.dtype) for k, v in top.items()}
    with jax.default_matmul_precision("highest"):
        return _mm(_rms(h_rows, top["norm_f"], eps), top["embed"].T,
                   control).astype(f32)


CHUNK = 2      # requests that go through a layer at once


def hidden_at(seed, sizes, tokens, rows, control=None):
    """The last layer's output [B, n, d] at positions ``rows`` [B, n] of
    the sequences ``tokens`` [B, S] (right-padded): the whole model, one
    layer at a time over every request, ``CHUNK`` requests a call."""
    tokens = jnp.asarray(tokens, jnp.int32)
    frozen = _frozen(sizes)
    top = init_layer(seed, sizes, "top", 0)
    dtype = bf16 if control == "bf16" else f32
    hs = [top["embed"][tokens[i:i + CHUNK]].astype(dtype)
          for i in range(0, tokens.shape[0], CHUNK)]
    for i, kind in enumerate(sizes["pattern"]):
        lp = init_layer(seed, sizes, kind, i)
        fp = init_layer(seed, sizes, "F", i)
        hs = [_layer(h, lp, fp, kind, frozen, control) for h in hs]
        del lp, fp
    h = jnp.concatenate(hs, axis=0)
    del hs
    return jnp.take_along_axis(h, jnp.asarray(rows)[..., None], axis=1), top


def logits_at(seed, sizes, tokens, rows, control=None):
    """Float32 logits [B, n, vocab] at positions ``rows`` [B, n] of the
    sequences ``tokens`` [B, S] (right-padded)."""
    h, top = hidden_at(seed, sizes, tokens, rows, control)
    return _head(h, top, sizes["eps"], control)


PAD = 256      # the batch of requests is padded to a multiple of it


def served_gaps(seed, sizes, requests, control=None):
    """For each finished request ``(prompt, served tokens)`` the gaps by
    which each served token's logit lies below the reference's best at
    its position (host arrays, one a request).  With ``control`` the same
    for the token that the lower precision puts first (or the altered
    one), in the served token's place.  The logits are made a request
    at a time (16 requests' at 2,048 positions over 65,536 ids would be
    8.6 GB)."""
    longest = max(len(p) + len(o) for p, o in requests)
    n_out = max(len(o) for _, o in requests)
    s_len = -(-longest // PAD) * PAD
    toks = np.zeros((len(requests), s_len), np.int32)
    rows = np.zeros((len(requests), n_out), np.int32)
    for i, (p, o) in enumerate(requests):
        seq = list(p) + list(o)
        toks[i, :len(seq)] = seq
        # the served token at output position j was predicted from
        # position len(p) + j - 1
        rows[i, :len(o)] = len(p) - 1 + np.arange(len(o))
    h, top = hidden_at(seed, sizes, toks, rows)
    if control not in (None, "altered_token"):
        h_low, top_low = hidden_at(seed, sizes, toks, rows, control)
    gaps = []
    for i, (_, o) in enumerate(requests):
        lg = _head(h[i], top, sizes["eps"], None)           # [n_out, V]
        served = np.zeros(n_out, np.int32)
        served[:len(o)] = o
        if control == "altered_token":
            served[len(o) // 2] = (o[len(o) // 2] + 1) % sizes["vocab"]
        elif control:
            served = np.asarray(jnp.argmax(_head(
                h_low[i], top_low, sizes["eps"], control), axis=-1))
        gap = jnp.max(lg, axis=-1) - jnp.take_along_axis(
            lg, jnp.asarray(served)[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(gap)[:len(o)])
        del lg
    return gaps
