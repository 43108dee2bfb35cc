"""Operations and bytes a ``jamba`` model with dense feed-forwards needs,
from shapes alone.

``cfg`` is the configuration's file (``configs/*.json``): the published
keys.  Lookups multiply nothing and are not counted.  The head is the
embedding's transpose: a matrix a token is multiplied with all the
same.
"""


def shape(cfg):
    """The sizes the counts use, from the file's own keys."""
    d = cfg["hidden_size"]
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return {
        "d": d, "d_inner": cfg["mamba_expand"] * d,
        "state": cfg["mamba_d_state"], "dt_rank": cfg["mamba_dt_rank"],
        "conv_kernel": cfg["mamba_d_conv"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": d // cfg["num_attention_heads"],
        "ff": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
        "pattern": "".join("*" if i % period == offset else "M"
                           for i in range(cfg["num_hidden_layers"])),
    }


def mamba_params(s):
    """in_proj, x_proj, dt_proj and out_proj (the conv and the state
    space multiply no matrix)."""
    di = s["d_inner"]
    return (s["d"] * 2 * di + di * (s["dt_rank"] + 2 * s["state"])
            + s["dt_rank"] * di + di * s["d"])


def mamba_vectors(s):
    """What else a Mamba-1 mixer holds: the conv's filter and bias,
    dt_bias, A_log, D and the three inner norms' weights."""
    di = s["d_inner"]
    return (s["conv_kernel"] * di + di + di + s["state"] * di + di
            + s["dt_rank"] + 2 * s["state"])


def attention_params(s):
    hd = s["head_dim"]
    return (s["d"] * (s["n_heads"] + 2 * s["n_kv_heads"]) * hd
            + s["n_heads"] * hd * s["d"])


def mlp_params(s):
    return 3 * s["d"] * s["ff"]


def layer_params(s, kind):
    """Every parameter of one layer of ``kind``, its two norms
    included."""
    mixer = (mamba_params(s) + mamba_vectors(s) if kind == "M"
             else attention_params(s))
    return mixer + mlp_params(s) + 2 * s["d"]


def total_params(s):
    """The whole model: layers, the embedding (the head is tied to it)
    and the final norm."""
    return (sum(layer_params(s, k) for k in s["pattern"])
            + s["vocab"] * s["d"] + s["d"])


def matmul_params_per_token(s):
    n = {k: s["pattern"].count(k) for k in "M*"}
    return (n["M"] * mamba_params(s) + n["*"] * attention_params(s)
            + len(s["pattern"]) * mlp_params(s))


def scan_flops_per_token(s):
    """The state space of one token in every Mamba layer: the decay's
    product and exponential, its product with the state, the input's
    product with B and the add, the product with C and the sum: 7 flops
    an element of the state."""
    return s["pattern"].count("M") * 7 * s["d_inner"] * s["state"]


def decode_flops(s, context):
    """One generated token that attends to ``context`` keys: the
    layers, the state space, attention (QK^T and PV) and the head."""
    attn = 4 * s["n_heads"] * s["head_dim"] * context * s["pattern"].count(
        "*")
    return (2 * matmul_params_per_token(s) + scan_flops_per_token(s) + attn
            + 2 * s["d"] * s["vocab"])


def prefill_flops(s, n):
    """One prompt of ``n`` tokens: the layers and the state space for
    every token, causal attention over positions 0..n-1, the head
    once."""
    attn = 4 * s["n_heads"] * s["head_dim"] * s["pattern"].count(
        "*") * n * (n + 1) // 2
    return (n * (2 * matmul_params_per_token(s) + scan_flops_per_token(s))
            + attn + 2 * s["d"] * s["vocab"])


def state_bytes_per_row(s, itemsize=4):
    """What the decode state update moves for one row, in every Mamba
    layer: the state read and written, the row's x, dt, B, C in and y
    out, and its new conv window in and into its slot."""
    di, n = s["d_inner"], s["state"]
    return s["pattern"].count("M") * itemsize * (
        2 * n * di + 3 * di + 2 * n + 2 * (s["conv_kernel"] - 1) * di)


def scan_bytes_per_token(s, itemsize=4):
    """What the prompt scan moves for one token, in every Mamba layer:
    its x, dt, B, C in and y out (the slot's state, read and written once
    a prompt, is left out: a lower bound)."""
    di, n = s["d_inner"], s["state"]
    return s["pattern"].count("M") * itemsize * (3 * di + 2 * n)
