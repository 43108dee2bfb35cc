"""Operations and bytes the algorithms need, from shapes alone.

``sizes`` is a configuration's ``config`` object: ``d_model``, ``n_heads``,
``n_layers``, ``d_ff``, ``vocab_size``.  Lookups (token and position
tables) multiply nothing and are not counted; recomputed work is not
counted either.
"""


def matmul_params(sizes):
    """Parameters that a token is multiplied with: per layer q, k, v, o
    (4 d^2) and the two FFN matrices (2 d ff), plus the output head."""
    d, ff = sizes["d_model"], sizes["d_ff"]
    return (sizes["n_layers"] * (4 * d * d + 2 * d * ff)
            + d * sizes["vocab_size"])


def layer_params(sizes):
    d, ff = sizes["d_model"], sizes["d_ff"]
    return sizes["n_layers"] * (4 * d * d + 2 * d * ff)


def train_flops_per_token(sizes, seq):
    """Forward and backward: 6 per matmul parameter, plus causal
    attention 6 * L * seq * d (QK^T and PV, 2 flops a multiply-add, x3
    for forward + backward, halved by the causal mask)."""
    return (6 * matmul_params(sizes)
            + 6 * sizes["n_layers"] * seq * sizes["d_model"])


def prefill_flops(sizes, n):
    """One prompt of ``n`` tokens: the layers for every token, causal
    attention over positions 0..n-1, the head for the last position."""
    d, L = sizes["d_model"], sizes["n_layers"]
    return (2 * layer_params(sizes) * n
            + 4 * d * L * n * (n + 1) // 2
            + 2 * d * sizes["vocab_size"])


def decode_flops(sizes, context):
    """One generated token that attends to ``context`` keys (its own
    included): layers, head, attention."""
    d, L = sizes["d_model"], sizes["n_layers"]
    return 2 * matmul_params(sizes) + 4 * d * L * context


def flash_train_work(sizes, batch, seq):
    """(flops, bytes) of one step's causal flash attention over all
    layers: forward (QK^T, PV) and backward (P again, dV, dP, dQ, dK),
    7 * B * H * S^2 * Dh multiply-adds' worth of flops halved by the
    mask already; q, k, v, o in bf16 and the row statistics in f32 read
    or written once per pass."""
    d, L, h = sizes["d_model"], sizes["n_layers"], sizes["n_heads"]
    bhs2d = batch * seq * seq * d
    flops = L * 7 * bhs2d
    tensor = batch * seq * d * 2            # one bf16 [B,H,S,Dh]
    stats = batch * h * seq * 4
    fwd = 4 * tensor + stats                # q k v -> o, lse
    bwd = 8 * tensor + 2 * stats            # q k v o do lse -> dq dk dv
    return flops, L * (fwd + bwd)


def paged_decode_bytes(sizes, context, page_bytes=4):
    """K and V bytes one decode row's attention has to read: ``context``
    positions of d_model in every layer."""
    return 2 * context * sizes["d_model"] * page_bytes * sizes["n_layers"]


def least_time(flops, nbytes, peaks):
    """Roofline floor in seconds and which side bounds it."""
    tc = flops / peaks["bf16_flops"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "memory")
