"""The plain model both cells are held to: a pre-LN decoder (learned
positions, multi-head attention, two-matrix FFN, separate output head)
in straightforward ``jax.numpy`` — float32, every product at
``precision=HIGHEST``, no kernels, no cache, no batching tricks.  It
imports nothing of the program and takes nothing the program made: the
weights come from ``init_params`` (the seed), which the drivers also use
to fill the program.

Layers are stacked on a leading axis and scanned, so one layer body is
compiled whatever the depth; the head and the loss run over blocks of
rows, and each layer is rematerialised in the backward pass, so the
whole fits beside nothing else on one chip.

``precision`` selects the arithmetic: ``"highest"`` is the reference;
``"bf16"``, ``"fp8"`` and ``"int8"`` are the lower-precision CONTROLS that the
comparison must reject (contract, "How `correct` is decided" item 2) —
the benchmark's own runs never use them.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

LN_EPS = 1e-5
ADAM = {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}
LAYER_MATS = ("wq", "wk", "wv", "wo", "w1", "w2")
HEAD_BLOCK = 1024      # rows of logits alive at once


def seed_key(seed):
    """A PRNG key from any whole number up to 2**63 (the driver's seeds
    pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def param_shapes(sizes, biases):
    d, ff, L = sizes["d_model"], sizes["d_ff"], sizes["n_layers"]
    v, pos = sizes["vocab_size"], sizes["n_positions"]
    shapes = {"embed": (v, d), "pos": (pos, d),
              "lnf_scale": (d,), "lnf_bias": (d,), "head": (d, v),
              "ln1_scale": (L, d), "ln1_bias": (L, d),
              "ln2_scale": (L, d), "ln2_bias": (L, d),
              "wq": (L, d, d), "wk": (L, d, d), "wv": (L, d, d),
              "wo": (L, d, d), "w1": (L, d, ff), "w2": (L, ff, d)}
    if biases:
        shapes.update({"bo": (L, d), "b1": (L, ff), "b2": (L, d),
                       "head_b": (v,)})
    return shapes


@functools.partial(jax.jit, static_argnames=("shapes",))
def _init(key, shapes):
    out = {}
    for i, (name, shape) in enumerate(shapes):
        x = jax.random.normal(jax.random.fold_in(key, i), shape,
                              jnp.float32)
        if name.endswith("_scale"):
            out[name] = 1.0 + 0.1 * x
        elif name in ("wo", "w2"):
            # GPT-2's scaled residual projections: 0.02 / sqrt(2 L)
            out[name] = x * (0.02 / math.sqrt(2 * shape[0]))
        else:
            out[name] = x * 0.02
    return out


def init_params(seed, sizes, biases):
    """Every leaf from the seed, on the device, in one jitted call:
    GPT-2's initialisation (normal 0.02, residual projections scaled
    down by depth), with layer-norm gains and all biases off their
    trivial values so that a dropped one shows."""
    shapes = tuple(sorted(param_shapes(sizes, biases).items()))
    return _init(seed_key(seed), shapes)


# ---------------------------------------------------------------------------
# arithmetic policies
# ---------------------------------------------------------------------------

def _round_int8(x):
    """``x`` rounded to 255 levels under a per-tensor scale (symmetric,
    the tensor's largest magnitude on 127), in x's dtype; the gradient
    goes straight through."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)).astype(jnp.float32), 1e-30)
    q = jnp.round(x.astype(jnp.float32) * (127.0 / amax)) * (amax / 127.0)
    return x + lax.stop_gradient(q.astype(x.dtype) - x)


def _round_fp8(x, fmt):
    """``x`` rounded to an 8-bit float under a per-tensor scale (the
    tensor's largest magnitude lands on the format's), in x's dtype."""
    top = float(jnp.finfo(fmt).max)
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)).astype(jnp.float32), 1e-30)
    q = (x.astype(jnp.float32) * scale).astype(fmt).astype(jnp.float32)
    return (q / scale).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec, a, b):
    """A product by the fp8 training recipe (Micikevicius et al.,
    arXiv:2209.05433): both operands in E4M3; in the backward pass the
    incoming gradient in E5M2 against the E4M3 operand that was kept."""
    return _fp8_fwd(spec, a, b)[0]


def _fp8_fwd(spec, a, b):
    qa = _round_fp8(a, jnp.float8_e4m3fn)
    qb = _round_fp8(b, jnp.float8_e4m3fn)
    return jnp.einsum(spec, qa, qb, precision=lax.Precision.HIGHEST), (qa, qb)


def _fp8_bwd(spec, kept, g):
    qa, qb = kept
    ins, out = spec.split("->")
    ia, ib = ins.split(",")
    qg = _round_fp8(g, jnp.float8_e5m2)
    da = jnp.einsum("%s,%s->%s" % (out, ib, ia), qg, qb,
                    precision=lax.Precision.HIGHEST)
    db = jnp.einsum("%s,%s->%s" % (ia, out, ib), qa, qg,
                    precision=lax.Precision.HIGHEST)
    return da.astype(qa.dtype), db.astype(qb.dtype)


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


class _Policy:
    """``highest``: float32 throughout.  ``bf16``: everything in
    bfloat16.  ``fp8`` and ``int8``: bfloat16, with every product
    against a weight matrix (q, k, v, o, the FFN, the head) by the fp8
    training recipe, or with both its operands rounded to int8 in the
    forward pass; attention's own two products stay in bfloat16."""

    def __init__(self, precision):
        if precision not in ("highest", "bf16", "fp8", "int8"):
            raise ValueError("unknown precision %r" % (precision,))
        self.name = precision
        self.dtype = jnp.float32 if precision == "highest" else jnp.bfloat16

    def cast(self, x):
        return x.astype(self.dtype)

    def attn(self, spec, a, b):
        return jnp.einsum(spec, self.cast(a), self.cast(b),
                          precision=lax.Precision.HIGHEST)

    def dot(self, spec, a, b):
        a, b = self.cast(a), self.cast(b)
        if self.name == "fp8":
            return _fp8_einsum(spec, a, b)
        if self.name == "int8":
            a, b = _round_int8(a), _round_int8(b)
        return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)


def _layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + LN_EPS) * scale + bias


def _act(name, x):
    if name == "relu":
        return jnp.maximum(x, 0)
    if name == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError("unknown activation %r" % (name,))


def _layer(pol, n_heads, act, x, lp):
    """One block on x [B, S, d]."""
    b, s, d = x.shape
    dh = d // n_heads
    c = pol.cast
    a = _layer_norm(x, c(lp["ln1_scale"]), c(lp["ln1_bias"]))
    q = pol.dot("bsd,de->bse", a, lp["wq"]).reshape(b, s, n_heads, dh)
    k = pol.dot("bsd,de->bse", a, lp["wk"]).reshape(b, s, n_heads, dh)
    v = pol.dot("bsd,de->bse", a, lp["wv"]).reshape(b, s, n_heads, dh)
    scores = pol.attn("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = pol.attn("bhqk,bkhd->bqhd", probs, v).reshape(b, s, d)
    o = pol.dot("bsd,de->bse", att, lp["wo"])
    if "bo" in lp:
        o = o + c(lp["bo"])
    x = x + o
    m = _layer_norm(x, c(lp["ln2_scale"]), c(lp["ln2_bias"]))
    h = pol.dot("bsd,df->bsf", m, lp["w1"])
    if "b1" in lp:
        h = h + c(lp["b1"])
    h = pol.dot("bsf,fd->bsd", _act(act, h), lp["w2"])
    if "b2" in lp:
        h = h + c(lp["b2"])
    return x + h


_PER_LAYER = ("ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias", "bo", "b1",
              "b2") + LAYER_MATS
_LEAVES = ("embed", "pos", "lnf_scale", "lnf_bias", "head",
           "head_b") + _PER_LAYER


def hidden(params, tokens, n_heads, act, precision="highest"):
    """Final-layer-norm output [B, S, d] for token ids [B, S]."""
    pol = _Policy(precision)
    s = tokens.shape[1]
    x = pol.cast(params["embed"][tokens] + params["pos"][:s])
    stacked = {k: params[k] for k in _PER_LAYER if k in params}

    @jax.checkpoint
    def body(x, lp):
        return _layer(pol, n_heads, act, x, lp), None

    x, _ = lax.scan(body, x, stacked)
    return _layer_norm(x, pol.cast(params["lnf_scale"]),
                       pol.cast(params["lnf_bias"]))


def logits_of(params, h, precision="highest"):
    """Head on rows h [N, d] -> float32 logits [N, vocab]."""
    pol = _Policy(precision)
    out = pol.dot("nd,dv->nv", h, params["head"])
    if "head_b" in params:
        out = out + pol.cast(params["head_b"])
    return out.astype(jnp.float32)


def lm_loss(params, tokens, labels, n_heads, act, precision="highest"):
    """Mean next-token cross entropy over all positions, the head taken
    HEAD_BLOCK rows at a time."""
    h = hidden(params, tokens, n_heads, act, precision)
    h = h.reshape(-1, h.shape[-1])
    y = labels.reshape(-1)
    n = h.shape[0]

    head = {k: params[k] for k in ("head", "head_b") if k in params}

    @jax.checkpoint
    def block(head, hb, yb):
        lg = logits_of(head, hb, precision)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, yb[:, None], 1)[:, 0])

    total = 0.0
    for i in range(0, n, HEAD_BLOCK):
        total = total + block(head, h[i:i + HEAD_BLOCK],
                              y[i:i + HEAD_BLOCK])
    return total / n


# ---------------------------------------------------------------------------
# training: Adam as Fluid defines it, and the norms the check compares
# ---------------------------------------------------------------------------

def leaf_norms(tree):
    """{leaf: norms}: one norm for a whole leaf, one per layer for a
    stacked leaf (so a 'leaf' is what the program holds as one
    parameter)."""
    out = {}
    for k, v in tree.items():
        v = v.astype(jnp.float32)
        if k in _PER_LAYER:
            out[k] = jnp.sqrt(jnp.sum(jnp.square(v),
                                      axis=tuple(range(1, v.ndim))))
        else:
            out[k] = jnp.sqrt(jnp.sum(jnp.square(v)))
    return out


SAMPLE = 4096          # elements of a leaf that grad_samples reads


def sample_index(seed, leaf, layer, size):
    """The flat positions (``min(size, SAMPLE)`` of them, from the seed)
    at which a leaf's first gradient is read element by element."""
    rng = np.random.default_rng(
        [int(seed), 5, _LEAVES.index(leaf),
         0 if layer is None else layer + 1])
    return rng.integers(0, size, min(size, SAMPLE)).astype(np.int32)


def sample_indices(seed, shapes):
    """{leaf: [k] or [L, k] int32 positions} for leaves of ``shapes``
    ({leaf: shape}; a stacked leaf is sampled layer by layer)."""
    out = {}
    for k, shape in shapes.items():
        if k in _PER_LAYER:
            size = int(np.prod(shape[1:]))
            out[k] = np.stack([sample_index(seed, k, l, size)
                               for l in range(shape[0])])
        else:
            out[k] = sample_index(seed, k, None, int(np.prod(shape)))
    return out


def grad_samples(index, tree):
    """{leaf: [k] or [L, k]}: the elements of every leaf (of every layer
    of a stacked leaf) at ``index``'s positions, in float32."""
    out = {}
    for k, v in tree.items():
        v = v.astype(jnp.float32)
        if k in _PER_LAYER:
            out[k] = jnp.take_along_axis(v.reshape(v.shape[0], -1),
                                         index[k], axis=1)
        else:
            out[k] = jnp.take(v.reshape(-1), index[k])
    return out


@functools.partial(jax.jit, static_argnames=("n_heads", "act", "precision",
                                             "lr"), donate_argnums=(0, 1, 2))
def adam_step(params, m, v, t, tokens, labels, n_heads, act, lr,
              precision="highest", index=None):
    """One step: loss, the gradient's leaf norms (with ``index``, its
    elements at those positions beside them), and the new state.
    Fluid's Adam: lr_t = lr * sqrt(1 - b2^t) / (1 - b1^t);
    p -= lr_t * m / (sqrt(v) + eps)."""
    loss, g = jax.value_and_grad(lm_loss)(params, tokens, labels, n_heads,
                                          act, precision)
    b1, b2, eps = ADAM["beta1"], ADAM["beta2"], ADAM["epsilon"]
    lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    m = {k: b1 * m[k] + (1 - b1) * g[k] for k in g}
    v = {k: b2 * v[k] + (1 - b2) * jnp.square(g[k]) for k in g}
    params = {k: params[k] - lr_t * m[k] / (jnp.sqrt(v[k]) + eps)
              for k in g}
    read = leaf_norms(g)
    if index is not None:
        read = (read, grad_samples(index, g))
    return loss, read, params, m, v


def train_readings(seed, sizes, biases, tokens, labels, act, lr, steps,
                   precision="highest"):
    """What the training check compares, from the plain model: each
    step's loss, the first gradient's leaf norms and sampled elements,
    and the leaf norms of the parameters' change after ``steps``
    steps."""
    params = init_params(seed, sizes, biases)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    index = sample_indices(seed, {k: x.shape for k, x in params.items()})
    losses = []
    for t in range(1, steps + 1):
        loss, read, params, m, v = adam_step(
            params, m, v, jnp.float32(t), tokens, labels,
            n_heads=sizes["n_heads"], act=act, lr=lr, precision=precision,
            index=index if t == 1 else None)
        losses.append(float(loss))
        if t == 1:
            grad_norms, samples = jax.device_get(read)
    del m, v
    start = init_params(seed, sizes, biases)       # the same leaves again
    change = jax.device_get(jax.jit(leaf_norms)(
        {k: params[k] - start[k] for k in params}))
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_samples": samples, "change_norms": change}


# ---------------------------------------------------------------------------
# serving: how far below the reference's best each served token lies
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("n_heads", "act", "n_out",
                                             "control"))
def _served_gaps(params, tokens, first, n_heads, act, n_out, control):
    """tokens [S]: a prompt followed by its served tokens, padded.  The
    served token at output position j is tokens[first + j] and was
    predicted from position first + j - 1.  Returns for j < n_out the
    gap max(logits) - logits[served]; with ``control`` the same gap for
    the token that the lower precision ``control`` puts first."""
    rows = first - 1 + jnp.arange(n_out)
    h = hidden(params, tokens[None], n_heads, act)[0]
    lg = logits_of(params, jnp.take(h, rows, axis=0, mode="clip"))
    if control:
        hc = hidden(params, tokens[None], n_heads, act, control)[0]
        picked = jnp.argmax(
            logits_of(params, jnp.take(hc, rows, axis=0, mode="clip"), control), axis=-1)
    else:
        picked = jnp.take(tokens, rows + 1, mode="clip")
    best = jnp.max(lg, axis=-1)
    return best - jnp.take_along_axis(lg, picked[:, None], 1)[:, 0]


PAD = 256              # a served request is padded to a multiple of it


def padded(length, longest):
    """The padded length of a request of ``length`` tokens: the next
    multiple of PAD, at most ``longest`` (so few shapes compile, and a
    short request does not pay for the longest)."""
    return min(int(longest), -(-int(length) // PAD) * PAD)


def served_gaps(params, prompt, served, pad_to, n_out, n_heads, act,
                control=None):
    """Gaps for one finished request (host lists in, host array out)."""
    seq = list(prompt) + list(served)
    if len(seq) > pad_to or len(served) > n_out:
        raise ValueError("request of %d+%d tokens exceeds the padded "
                         "reference shape" % (len(prompt), len(served)))
    toks = np.zeros(pad_to, np.int32)
    toks[:len(seq)] = seq
    gaps = _served_gaps(params, jnp.asarray(toks), len(prompt),
                        n_heads, act, n_out, control)
    return np.asarray(gaps)[:len(served)]
