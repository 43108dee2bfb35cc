"""Operations and bytes a ``nemotron_h`` stage needs, from shapes alone.

``cfg`` is the configuration's file (``configs/*.json``): the published
keys, with ``stage`` saying which blocks and which experts are here.
Lookups multiply nothing and are not counted.  A token meets ``top_k``
of ``n_experts`` routed experts, of which this chip holds ``held``: it
computes ``top_k * held / n_experts`` of them on average (the router of
seeded weights routes evenly), and that expectation is what is counted.
"""


def shape(cfg):
    """The sizes the counts use, from the file's own keys."""
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    d_inner = heads * p
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    held = cfg["stage"]["experts_held"]
    return {
        "d": cfg["hidden_size"], "pattern": cfg["stage"]["pattern"],
        "d_inner": d_inner, "conv_dim": d_inner + 2 * gn,
        "in_proj": 2 * d_inner + 2 * gn + heads,
        "mamba_heads": heads, "mamba_head_dim": p,
        "state": cfg["ssm_state_size"],
        "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "n_experts": cfg["published"]["n_routed_experts"],
        "held": held[1] - held[0] + 1, "top_k": cfg["num_experts_per_tok"],
        "latent": cfg["moe_latent_size"],
        "expert_ff": cfg["moe_intermediate_size"],
        "shared_ff": cfg["moe_shared_expert_intermediate_size"],
        "vocab": cfg["vocab_size"],
    }


def mamba_params(s):
    """in_proj and out_proj (the conv and the scan multiply no matrix)."""
    return s["d"] * s["in_proj"] + s["d_inner"] * s["d"]


def attention_params(s):
    hd = s["head_dim"]
    return (s["d"] * (s["n_heads"] + 2 * s["n_kv_heads"]) * hd
            + s["n_heads"] * hd * s["d"])


def expert_params(s):
    """One routed expert's two matrices."""
    return 2 * s["latent"] * s["expert_ff"]


def moe_params_per_token(s):
    """What a token is multiplied with in an expert layer here: router,
    the two latent projections, the shared expert, and its expected
    share of routed experts."""
    return (s["d"] * s["n_experts"] + 2 * s["d"] * s["latent"]
            + 2 * s["d"] * s["shared_ff"]
            + s["top_k"] * s["held"] / s["n_experts"] * expert_params(s))


def matmul_params_per_token(s):
    n = {k: s["pattern"].count(k) for k in "ME*"}
    return (n["M"] * mamba_params(s) + n["*"] * attention_params(s)
            + n["E"] * moe_params_per_token(s))


def scan_flops_per_token(s):
    """The SSM recurrence of one token in every Mamba layer: decay,
    outer product and add, and the contraction with C, 2 flops each an
    element of the state."""
    return s["pattern"].count("M") * 6 * (
        s["mamba_heads"] * s["mamba_head_dim"] * s["state"])


def decode_flops(s, context):
    """One generated token that attends to ``context`` keys: the blocks,
    the scan, attention (QK^T and PV) and the head."""
    attn = 4 * s["n_heads"] * s["head_dim"] * context * s["pattern"].count(
        "*")
    return (2 * matmul_params_per_token(s) + scan_flops_per_token(s) + attn
            + 2 * s["d"] * s["vocab"])


def prefill_flops(s, n):
    """One prompt of ``n`` tokens: the blocks and the scan for every
    token, causal attention over positions 0..n-1, the head once."""
    attn = 4 * s["n_heads"] * s["head_dim"] * s["pattern"].count(
        "*") * n * (n + 1) // 2
    return (n * (2 * matmul_params_per_token(s) + scan_flops_per_token(s))
            + attn + 2 * s["d"] * s["vocab"])


def expert_bytes(s, itemsize=2):
    """What reading one routed expert costs: its two matrices."""
    return expert_params(s) * itemsize


def expert_flops(s):
    """One token through one routed expert."""
    return 2 * expert_params(s)


def state_bytes_per_row(s, itemsize=4):
    """A decode row's SSM state, read and written, in every Mamba
    layer."""
    return s["pattern"].count("M") * 2 * itemsize * (
        s["mamba_heads"] * s["mamba_head_dim"] * s["state"])
