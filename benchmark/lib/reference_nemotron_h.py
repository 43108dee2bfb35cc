"""The plain model the ``nemotron_h`` cell is held to: a hybrid decoder of
Mamba-2 (``M``), latent mixture-of-experts (``E``) and grouped-query
attention (``*``) blocks, as NVIDIA-Nemotron-3-Super-120B-A12B's
``config.json`` describes them, in straightforward ``jax.numpy``:
float32, every product at ``jax.default_matmul_precision("highest")``,
a sequential scan for the SSM, no kernels, no cache, no batching tricks.
It imports nothing of the program and takes nothing the program made:
the weights come from ``init_leaf`` (the seed), which the driver also
uses to fill the program, and are rounded to bfloat16 where the
configuration stores bfloat16, as the program's are.

``sizes`` is the configuration's ``sizes`` object (``configs/*.json``
through its ``keys``) plus ``pattern`` and ``experts_held``.  Block ``i``
of kind ``pattern[i]`` computes ``h <- h + mixer_i(RMSNorm_i(h))``:

- ``M``: ``[z | xBC | dt] = W_in u``; ``xBC <- silu(causal_conv1d(xBC) +
  b)``; ``xBC -> x [H, P], B [G, N], C [G, N]``; ``dt <- softplus(dt +
  dt_bias)``; per head ``k`` of group ``g``: ``S_t = exp(dt_t A_k) S_{t-1}
  + dt_t x_t (x) B_{g,t}``, ``y_t = S_t C_{g,t} + D_k x_t``; ``y <-
  RMSNorm_grouped(y * silu(z))``; ``out = W_out y``.
- ``*``: ``q, k, v`` without bias, causal softmax attention with each KV
  head shared by ``n_heads / n_kv_heads`` query heads, ``W_o``; no
  rotary embedding (``assumed`` in the configuration's file).
- ``E``: ``s = sigmoid(W_g u)`` over all experts; the top ``top_k`` of
  ``s + bias``; ``w = scaling * s_chosen / (sum s_chosen + 1e-20)``;
  ``lat = W_down u``; ``r = sum_k w_k W2_k relu(W1_k lat)^2`` **over
  the chosen experts that ``experts_held`` names** (what the others
  would add is left out, as on the chip that does not hold them);
  ``out = W_up r + Ws2 relu(Ws1 u)^2``.

On the chip the model is walked layer by layer: one layer's weights are
made from the seed, widened to float32, applied to every request, and
dropped, so that it fits beside nothing else after ``release()``.

``control`` puts something else in the program's place (never a
benchmark run; ``controls.py`` reads what the comparison makes of it):
``bf16`` carries everything the configuration states as float32 in
bfloat16 (products' results, norm statistics, router, decay, conv, SSM
state, softmax, logits): the nearest precision below the stated one;
``int8_products`` rounds both operands of every product against a
weight matrix (not the router's) to int8 under a per-tensor scale;
``int8_experts`` rounds only the routed experts' two matrices so (the
comparison cannot see it: the routed sum is a tenth of an expert layer's
output at seeded weights); ``altered_token`` is no precision: the served
tokens as they are, but one a request stepped to its neighbour in the
vocabulary, in the comparison alone.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

EPS = 1e-5
CONTROLS = ("bf16", "int8_products", "int8_experts", "altered_token")

# leaves of each kind of block: name -> (shape from sizes, how it is
# drawn, stored dtype).  Matrices are bfloat16; the small vectors that
# the family keeps in float32 (norm weights, conv, dt_bias, A_log, D, the
# router's bias) are float32.
bf16, f32 = jnp.bfloat16, jnp.float32


def dims(sizes):
    """Derived sizes."""
    d_inner = sizes["mamba_heads"] * sizes["mamba_head_dim"]
    conv_dim = d_inner + 2 * sizes["n_groups"] * sizes["state"]
    return {"d_inner": d_inner, "conv_dim": conv_dim,
            "in_proj": 2 * d_inner + 2 * sizes["n_groups"] * sizes["state"]
            + sizes["mamba_heads"],
            "qkv": (sizes["n_heads"] + 2 * sizes["n_kv_heads"])
            * sizes["head_dim"],
            "attn": sizes["n_heads"] * sizes["head_dim"]}


def leaf_specs(sizes):
    """{kind: {leaf: (shape, draw, dtype)}}; kind ``top`` is what no
    block owns.  The order of a kind's leaves is part of the seed's
    meaning."""
    s, x = sizes, dims(sizes)
    d, held = s["hidden"], len(s["experts_held"])
    return {
        "top": {"embed": ((s["vocab"], d), "normal", bf16),
                "norm_f": ((d,), "gain", f32),
                "head": ((d, s["vocab"]), "normal", bf16)},
        "M": {"norm": ((d,), "gain", f32),
              "in_proj": ((d, x["in_proj"]), "normal", bf16),
              "conv_w": ((s["conv_kernel"], x["conv_dim"]), "conv", f32),
              "conv_b": ((x["conv_dim"],), "normal", f32),
              "dt_bias": ((s["mamba_heads"],), "dt", f32),
              "A_log": ((s["mamba_heads"],), "A", f32),
              "D": ((s["mamba_heads"],), "one", f32),
              "gnorm": ((x["d_inner"],), "gain", f32),
              "out_proj": ((x["d_inner"], d), "normal", bf16)},
        "*": {"norm": ((d,), "gain", f32),
              "wqkv": ((d, x["qkv"]), "normal", bf16),
              "wo": ((x["attn"], d), "normal", bf16)},
        "E": {"norm": ((d,), "gain", f32),
              "router": ((d, s["n_experts"]), "normal", bf16),
              "bias": ((s["n_experts"],), "zero", f32),
              "down": ((d, s["latent"]), "normal", bf16),
              "up": ((s["latent"], d), "normal", bf16),
              "w1": ((held, s["latent"], s["expert_ff"]), "normal", bf16),
              "w2": ((held, s["expert_ff"], s["latent"]), "normal", bf16),
              "s1": ((d, s["shared_ff"]), "normal", bf16),
              "s2": ((s["shared_ff"], d), "normal", bf16)},
    }


def seed_key(seed):
    """A PRNG key from any whole number up to 2**63."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _draw(key, shape, how, dtype, sizes):
    """One leaf as the family trains it: normal 0.02; ``gain`` 1 + 0.1 n
    (off its trivial value, so that a dropped one shows); the conv
    filter uniform in +-1/sqrt(kernel) (the default of the layer it is);
    ``A`` = log U[1, 16]; ``dt`` the inverse softplus of a step
    log-uniform in [time_step_min, time_step_max], floored."""
    if how == "one":
        return jnp.ones(shape, dtype)
    if how == "zero":
        return jnp.zeros(shape, dtype)
    if how == "A":
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    if how == "dt":
        lo, hi = math.log(sizes["time_step_min"]), math.log(
            sizes["time_step_max"])
        dt = jnp.exp(jax.random.uniform(key, shape, f32) * (hi - lo) + lo)
        dt = jnp.maximum(dt, sizes["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))
    if how == "conv":
        bound = 1.0 / math.sqrt(shape[0])
        return jax.random.uniform(key, shape, f32, -bound, bound)
    n = jax.random.normal(key, shape, f32)
    return (1.0 + 0.1 * n if how == "gain" else 0.02 * n).astype(dtype)


@functools.partial(jax.jit, static_argnames=("kind", "leaf", "frozen"))
def _init_leaf(key, layers, kind, leaf, frozen):
    sizes = dict(frozen)
    sizes["experts_held"] = range(*sizes["experts_held"])
    specs = leaf_specs(sizes)[kind]
    shape, how, dtype = specs[leaf]
    index = list(specs).index(leaf)

    def one(layer):
        k = jax.random.fold_in(jax.random.fold_in(key, layer), index)
        if leaf in ("w1", "w2"):
            # an expert's matrices are drawn from the expert's own
            # number, so that every chip's share of a layer is a slice
            # of the same layer
            first = sizes["experts_held"][0]
            return jax.vmap(lambda e: _draw(
                jax.random.fold_in(k, e), shape[1:], how, dtype, sizes))(
                    first + jnp.arange(shape[0]))
        return _draw(k, shape, how, dtype, sizes)

    return lax.map(one, layers)


def _frozen(sizes):
    out = dict(sizes)
    held = sizes["experts_held"]
    out["experts_held"] = (held[0], held[-1] + 1)
    return tuple(sorted(out.items()))


def init_leaf(seed, sizes, kind, leaf, layers):
    """Leaf ``leaf`` of the blocks ``layers`` (their indices in the
    pattern, all of kind ``kind``; ``[TOP]`` for kind ``top``), stacked
    on a leading axis, on the device, made in place."""
    return _init_leaf(seed_key(seed), jnp.asarray(layers, jnp.int32), kind,
                      leaf, _frozen(sizes))


TOP = 1 << 20          # the "layer" of what no block owns


def init_layer(seed, sizes, i):
    """{leaf: array} of block ``i`` (``TOP``: the embedding, the final
    norm and the head)."""
    kind = "top" if i == TOP else sizes["pattern"][i]
    return {leaf: init_leaf(seed, sizes, kind, leaf, [i])[0]
            for leaf in leaf_specs(sizes)[kind]}


# ---------------------------------------------------------------------------
# the blocks
# ---------------------------------------------------------------------------

def _rms(x, w, groups=1):
    shape = x.shape
    x = x.reshape(shape[:-1] + (groups, shape[-1] // groups))
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + EPS)
    return x.reshape(shape) * w


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0))


def _round_int8(w):
    amax = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30)
    return jnp.round(w * (127.0 / amax)) * (amax / 127.0)


def _mm(x, w, control):
    """A product against a weight matrix."""
    if control == "int8_products":
        x, w = _round_int8(x), _round_int8(w)
    return x @ w


def mamba_block(sizes, lp, u, control=None):
    """u [B, S, d] (normed) -> the mixer's output [B, S, d]."""
    s, x = sizes, dims(sizes)
    b, t, _ = u.shape
    heads, p, g, n = (s["mamba_heads"], s["mamba_head_dim"], s["n_groups"],
                      s["state"])
    zxbcdt = _mm(u, lp["in_proj"], control)
    z = zxbcdt[..., :x["d_inner"]]
    xbc = zxbcdt[..., x["d_inner"]:x["d_inner"] + x["conv_dim"]]
    dt = zxbcdt[..., x["d_inner"] + x["conv_dim"]:]
    k = s["conv_kernel"]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + t] * lp["conv_w"][j] for j in range(k))
    xbc = jax.nn.silu(conv + lp["conv_b"])
    xs = xbc[..., :x["d_inner"]].reshape(b, t, heads, p)
    bs = xbc[..., x["d_inner"]:x["d_inner"] + g * n].reshape(b, t, g, n)
    cs = xbc[..., x["d_inner"] + g * n:].reshape(b, t, g, n)
    dt = jax.nn.softplus(dt + lp["dt_bias"])                 # [B, T, H]
    a = -jnp.exp(lp["A_log"])
    per = heads // g

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp           # [B,H,P] [B,G,N] [B,G,N] [B,H]
        b_h = jnp.repeat(b_t, per, axis=1)                   # [B, H, N]
        c_h = jnp.repeat(c_t, per, axis=1)
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_h)

    _, ys = lax.scan(step, jnp.zeros((b, heads, p, n), u.dtype),
                     (xs.swapaxes(0, 1), bs.swapaxes(0, 1),
                      cs.swapaxes(0, 1), dt.swapaxes(0, 1)))
    y = ys.swapaxes(0, 1) + lp["D"][:, None] * xs            # [B,T,H,P]
    y = y.reshape(b, t, x["d_inner"]) * jax.nn.silu(z)
    return _mm(_rms(y, lp["gnorm"], groups=g), lp["out_proj"], control)


def attention_block(sizes, lp, u, control=None):
    s = sizes
    b, t, _ = u.shape
    nh, nkv, hd = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    qkv = _mm(u, lp["wqkv"], control)
    q = qkv[..., :nh * hd].reshape(b, t, nkv, nh // nkv, hd)
    k = qkv[..., nh * hd:(nh + nkv) * hd].reshape(b, t, nkv, hd)
    v = qkv[..., (nh + nkv) * hd:].reshape(b, t, nkv, hd)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v)
    return _mm(att.reshape(b, t, nh * hd), lp["wo"], control)


def route(sizes, lp, u):
    """(chosen expert ids [.., top_k], their weights [.., top_k])."""
    score = jax.nn.sigmoid(u @ lp["router"])
    _, chosen = lax.top_k(score + lp["bias"], sizes["top_k"])
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    return chosen, sizes["scaling"] * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


def routed_part(sizes, lp, lat, chosen, w, control=None):
    """sum over the chosen experts that are held of w_k * expert_k(lat):
    lat [T, latent] -> [T, latent].  One held expert at a time over all
    tokens, weighted by what the router gave it (0 where not chosen)."""
    first = sizes["experts_held"][0]

    def one(total, args):
        e, w1, w2 = args
        if control == "int8_experts":
            w1, w2 = _round_int8(w1), _round_int8(w2)
        weight = jnp.sum(jnp.where(chosen == first + e, w, 0), axis=-1)
        out = _mm(_relu2(_mm(lat, w1, control)), w2, control)
        return total + weight[:, None] * out, None

    held = len(sizes["experts_held"])
    total, _ = lax.scan(one, jnp.zeros_like(lat),
                        (jnp.arange(held), lp["w1"], lp["w2"]))
    return total


def moe_block(sizes, lp, u, control=None, shared=True):
    b, t, d = u.shape
    flat = u.reshape(b * t, d)
    chosen, w = route(sizes, lp, flat)
    r = routed_part(sizes, lp, _mm(flat, lp["down"], control), chosen, w,
                    control)
    out = _mm(r, lp["up"], control)
    if shared:
        out = out + _mm(_relu2(_mm(flat, lp["s1"], control)), lp["s2"],
                        control)
    return out.reshape(b, t, d)


@functools.partial(jax.jit, static_argnames=("kind", "frozen", "control"),
                   donate_argnums=(0,))
def _block(h, lp, kind, frozen, control):
    sizes = dict(frozen)
    sizes["experts_held"] = range(*sizes["experts_held"])
    # every array in the stream's dtype: float32, or ``bf16``'s
    lp = {k: v.astype(h.dtype) for k, v in lp.items()}
    with jax.default_matmul_precision("highest"):
        u = _rms(h, lp["norm"])
        if kind == "M":
            return h + mamba_block(sizes, lp, u, control)
        if kind == "*":
            return h + attention_block(sizes, lp, u, control)
        return h + moe_block(sizes, lp, u, control)


@functools.partial(jax.jit, static_argnames=("control",))
def _head(h_rows, top, control):
    top = {k: v.astype(h_rows.dtype) for k, v in top.items()}
    with jax.default_matmul_precision("highest"):
        return _mm(_rms(h_rows, top["norm_f"]), top["head"], control).astype(
            f32)


CHUNK = 4      # requests that go through a block at once


def logits_at(seed, sizes, tokens, rows, control=None):
    """Float32 logits [B, n, vocab] at positions ``rows`` [B, n] of the
    sequences ``tokens`` [B, S] (right-padded): the whole model, one
    block at a time over every request, ``CHUNK`` requests a call."""
    tokens = jnp.asarray(tokens, jnp.int32)
    frozen = _frozen(sizes)
    top = init_layer(seed, sizes, TOP)
    dtype = bf16 if control == "bf16" else f32
    hs = [top["embed"][tokens[i:i + CHUNK]].astype(dtype)
          for i in range(0, tokens.shape[0], CHUNK)]
    for i, kind in enumerate(sizes["pattern"]):
        lp = init_layer(seed, sizes, i)
        hs = [_block(h, lp, kind, frozen, control) for h in hs]
        del lp
    h = jnp.concatenate(hs, axis=0)
    del hs
    picked = jnp.take_along_axis(h, jnp.asarray(rows)[..., None], axis=1)
    return _head(picked, {k: top[k] for k in ("norm_f", "head")}, control)


PAD = 256      # the batch of requests is padded to a multiple of it


def served_gaps(seed, sizes, requests, control=None):
    """For each finished request ``(prompt, served tokens)`` the gaps by
    which each served token's logit lies below the reference's best at
    its position (host arrays, one a request).  With ``control`` the same
    for the token that the lower precision puts first (or the altered
    one), in the served token's place."""
    longest = max(len(p) + len(o) for p, o in requests)
    n_out = max(len(o) for _, o in requests)
    s_len = -(-longest // PAD) * PAD
    toks = np.zeros((len(requests), s_len), np.int32)
    rows = np.zeros((len(requests), n_out), np.int32)
    served = np.zeros((len(requests), n_out), np.int32)
    for i, (p, o) in enumerate(requests):
        seq = list(p) + list(o)
        toks[i, :len(seq)] = seq
        # the served token at output position j was predicted from
        # position len(p) + j - 1
        rows[i, :len(o)] = len(p) - 1 + np.arange(len(o))
        served[i, :len(o)] = o
    lg = logits_at(seed, sizes, toks, rows)
    if control == "altered_token":
        for i, (_, o) in enumerate(requests):
            served[i, len(o) // 2] = (o[len(o) // 2] + 1) % sizes["vocab"]
    elif control:
        served = np.asarray(jnp.argmax(
            logits_at(seed, sizes, toks, rows, control), axis=-1))
    best = jnp.max(lg, axis=-1)
    gap = np.asarray(best - jnp.take_along_axis(
        lg, jnp.asarray(served)[..., None], axis=-1)[..., 0])
    return [gap[i, :len(o)] for i, (_, o) in enumerate(requests)]


def stacked_params(seed, sizes):
    """Every leaf of the model, the blocks of one kind stacked on a
    leading axis under ``<kind>.<leaf>`` (what no block owns under its
    own name): the layout a served model that walks its blocks by kind
    takes.  Made leaf by leaf on the device, nothing twice."""
    pattern = sizes["pattern"]
    out = {leaf: init_leaf(seed, sizes, "top", leaf, [TOP])[0]
           for leaf in leaf_specs(sizes)["top"]}
    for kind in sorted(set(pattern)):
        layers = [i for i, k in enumerate(pattern) if k == kind]
        for leaf in leaf_specs(sizes)[kind]:
            out["%s.%s" % (kind, leaf)] = init_leaf(seed, sizes, kind, leaf,
                                                    layers)
    return out
