"""Flash attention (Pallas TPU kernel).

The long-context hot path: computes softmax(QK^T * scale [+ causal
mask]) V without materializing the [T, T] score matrix in HBM.  Q is
tiled over the grid; K/V stream through VMEM tiles with the online-
softmax running max/sum rescale (Dao et al. 2022; same math as
parallel/ring.py's per-chunk accumulator, here per-tile inside one
chip).

Role parity: reference operators fuse nothing here — attention in the
reference book models is separate matmul/softmax ops; this kernel is
the TPU-native replacement for that op chain at long sequence length.

Interface: [B, H, T, D] (batch, heads, time, head_dim).  Falls back to
the identical-math XLA implementation off-TPU (or under
``force_xla=True``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.observability.trace import traced as _traced

from .dispatch import take_pallas

__all__ = ["flash_attention", "flash_attention_fwd_lse",
           "flash_attention_bwd", "paged_attention",
           "flash_attention_chunk", "flash_attention_chunk_bwd",
           "chunk_finalize"]

NEG_INF = -1e30

# Stable names in a device trace: every pallas_call here carries
# metadata={"kernel": <name>}, which lands in the custom call's
# frontend_attributes (kernel_metadata) — part of the HLO instruction
# text, and that text is the device event's name.  The flash kernels
# also pass name=<name>: the instruction itself is then %<name>.N.


def _attention_xla(q, k, v, scale, causal):
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * scale
    if causal:
        t, srcs = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((t, srcs), bool))
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhts,bhsd->bhtd", p, v)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                  acc_ref, *, scale, causal, block_q, block_k, n_k):
    # grid (bh, qi, ki); ki is the innermost SEQUENTIAL axis, so the
    # VMEM scratch (running max/sum/accumulator) carries across K tiles
    # while K/V stream block_k rows at a time.
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if causal:
        # whole K tile above the diagonal: nothing to add
        live = ki * block_k <= qi * block_q + block_q - 1
    else:
        live = True

    @pl.when(live)
    def _step():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = q @ k.T                                   # [bq, bk]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[...][:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = (l_ref[...][:, 0] * alpha +
                      p.sum(axis=1))[:, None]
        acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
        m_ref[...] = m_new[:, None]

    @pl.when(ki == n_k - 1)
    def _done():
        l = l_ref[...][:, 0]
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        # log-sum-exp per query row: all the softmax state the backward
        # kernels need to rebuild P tile-by-tile
        lse_ref[...] = (m_ref[...][:, 0] + jnp.log(l))[:, None]


def _fit_block(block, size):
    """Largest power-of-two tile <= requested that divides the dim, so
    raising a default never demotes a previously-kernel-eligible length
    (e.g. T=7680: 1024 fails, 512 divides)."""
    block = min(block, size)
    while block > 8 and size % block:
        block //= 2
    return block


DEF_BLOCK_Q = 1024
DEF_BLOCK_K = 1024


def _tuned_config(q_shape, kv_len, dtype):
    """Autotune-cache hit for this attention shape ({} on miss) — the
    persistent form of a flash_tune.py sweep (ISSUE 7).  Keyed on
    (B, H, T, D, T_kv) + dtype + backend; consulted at trace time."""
    from paddle_tpu import tuning

    cfg = tuning.lookup("flash_attention",
                        tuple(q_shape) + (int(kv_len),),
                        jnp.dtype(dtype).name)
    return cfg or {}


# launch-site span (FLAGS_telemetry): trace/lowering-time cost; the
# device-side kernel time lives in the xplane capture
@_traced("pallas.flash_attention",
         lambda q, *a, **kw: {"q": str(q.shape)})
def flash_attention(q, k, v, scale=None, causal=False, block_q=None,
                    block_k=None, force_xla=False, interpret=False,
                    block_q_bwd=None, block_k_bwd=None,
                    block_q_dkv=None, block_k_dkv=None):
    """softmax(QK^T scale) V, [B,H,T,D] in/out.

    Uses the Pallas kernel on TPU when T divides into the block sizes;
    anything else takes the XLA path (same math, fp32 accumulation).
    Differentiable end-to-end in O(T) memory: the forward saves the
    per-row log-sum-exp and the backward is two Pallas kernels (dQ;
    dK/dV) that rebuild P tile-by-tile — no [T, T] materialization in
    either direction (Dao et al. 2022 alg. 2).

    ``block_q_bwd``/``block_k_bwd`` tile both backward kernels;
    ``block_q_dkv``/``block_k_dkv`` override the dK/dV kernel alone —
    its transpose-free [bk, bq] tile orientation (``_dkv_kernel``) has a
    different optimum than dQ's, so tools/flash_tune.py sweeps them
    independently.

    Tile arguments left as None resolve through the persistent autotune
    cache (paddle_tpu/tuning, written by flash_tune.py) and fall back to
    the built-in defaults on a miss; an explicit argument always wins."""
    b, h, t, d = q.shape
    tk = k.shape[2]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    cfg = _tuned_config(q.shape, tk, q.dtype)
    if block_q is None:
        block_q = int(cfg.get("block_q", DEF_BLOCK_Q))
    if block_k is None:
        block_k = int(cfg.get("block_k", DEF_BLOCK_K))
    if block_q_bwd is None:
        block_q_bwd = cfg.get("block_q_bwd")
    if block_k_bwd is None:
        block_k_bwd = cfg.get("block_k_bwd")
    if block_q_dkv is None:
        block_q_dkv = cfg.get("block_q_dkv")
    if block_k_dkv is None:
        block_k_dkv = cfg.get("block_k_dkv")
    block_q = _fit_block(block_q, t)
    block_k = _fit_block(block_k, tk)
    usable = (t % block_q == 0 and tk % block_k == 0)
    if not take_pallas("flash_attention", usable, force_xla, interpret):
        return _attention_xla(q, k, v, scale, causal)
    return _flash_diff(q, k, v, scale, causal, block_q, block_k,
                       block_q_bwd, block_k_bwd, block_q_dkv,
                       block_k_dkv, interpret)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10, 11))
def _flash_diff(q, k, v, scale, causal, block_q, block_k, block_q_bwd,
                block_k_bwd, block_q_dkv, block_k_dkv, interpret):
    out, _ = _flash_pallas(q, k, v, scale, causal, block_q, block_k,
                           interpret)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, block_q_bwd,
               block_k_bwd, block_q_dkv, block_k_dkv, interpret):
    out, lse = _flash_pallas(q, k, v, scale, causal, block_q, block_k,
                             interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, block_q_bwd, block_k_bwd,
               block_q_dkv, block_k_dkv, interpret, res, g):
    """Flash backward (Dao et al. 2022, alg. 2): with the forward's
    per-row log-sum-exp saved, P rebuilds tile-by-tile as
    exp(scale*QK^T - lse), so the backward never materializes [T, T]
    in HBM either — dQ streams K/V per Q tile, dK/dV stream Q/dO per
    K tile, and D = rowsum(dO*O) replaces the softmax-jacobian term."""
    q, k, v, out, lse = res
    do = g.astype(out.dtype)
    delta = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)

    # The backward kernels keep several [block_q, block_k] f32
    # intermediates (p, ds + operand tiles) live in VMEM per grid step —
    # at 1024x1024 that flirts with the ~16MB/core budget at d=128, so
    # cap the backward Q tile at 512 while K/V tiles follow the forward:
    # xplane-measured at B16 H8 T2048 D128 (2026-07, before PR 1, on a
    # configuration that no longer exists; not re-measured), (512, 1024)
    # ran the dq+dkv pair 10% faster than (512, 512) caps; K-tile
    # streaming amortizes better than square tiles.
    bq = _fit_block(block_q_bwd or min(block_q, 512), q.shape[2])
    bk = _fit_block(block_k_bwd or block_k, k.shape[2])
    # _fit_block stops halving at 8 even when 8 doesn't divide (e.g.
    # T=1002): a non-dividing tile would silently drop the tail rows of
    # the grid, so fall back to the forward's blocks, which divide by
    # construction (the kernel path was only taken because they do)
    if q.shape[2] % bq:
        bq = block_q
    if k.shape[2] % bk:
        bk = block_k
    # dK/dV-specific tiles: the [bk, bq] tile orientation means its
    # streaming axis is Q, so its sweet spot need not match dQ's
    bq_dkv = _fit_block(block_q_dkv or bq, q.shape[2])
    bk_dkv = _fit_block(block_k_dkv or bk, k.shape[2])
    if q.shape[2] % bq_dkv:
        bq_dkv = bq
    if k.shape[2] % bk_dkv:
        bk_dkv = bk
    dq = _flash_bwd_dq(q, k, v, do, lse, delta, scale, causal, bq,
                       bk, interpret)
    dk, dv = _flash_bwd_dkv(q, k, v, do, lse, delta, scale, causal,
                            bq_dkv, bk_dkv, interpret)
    return dq, dk, dv


_flash_diff.defvjp(_flash_fwd, _flash_bwd)


def _rebuild_p_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
                  scale, causal, block_q, block_k):
    """Shared backward tile math: rebuild the probability tile from the
    saved LSE and form dS = P*(dO V^T - D).  Returns (q, k, p, ds) as
    f32 — everything either backward kernel contracts with."""
    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    s = (q @ k.T) * scale                             # [bq, bk]
    p = jnp.exp(s - lse_ref[...][:, 0][:, None])
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, p.shape, 0)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, p.shape, 1)
        p = jnp.where(q_pos >= k_pos, p, 0.0)
    dp = do @ v.T                                     # [bq, bk]
    ds = p * (dp - delta_ref[...][:, 0][:, None])
    return q, k, do, p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, causal, block_q, block_k, n_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _step():
        _, k, _, _, ds = _rebuild_p_ds(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
            scale, causal, block_q, block_k)
        acc_ref[...] += (ds @ k) * scale

    @pl.when(ki == n_k - 1)
    def _done():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, scale, causal, block_q,
                block_k, n_q):
    """dK/dV tile step, TRANSPOSE-FREE: the probability tile is built
    directly as pT [bk, bq] (scores from k @ q.T), so every contraction
    is a plain a@b / a@b.T MXU dot — the earlier p.T @ do / ds.T @ q
    forms contracted dim-0 of both operands, which Mosaic serves with
    an extra in-VMEM transpose (measured 2026-07, before PR 1: that
    dkv kernel ran at 52% executed-MXU vs the structurally-identical dq
    kernel's 71%; this rewrite compiles and trains on the v5e since
    PR 21, its own MXU share is not measured yet — ROADMAP S4)."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live = (ki * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        do = do_ref[...].astype(jnp.float32)
        sT = (k @ q.T) * scale                         # [bk, bq]
        pT = jnp.exp(sT - lse_ref[...][:, 0][None, :])
        if causal:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, pT.shape, 0)
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, pT.shape, 1)
            pT = jnp.where(q_pos >= k_pos, pT, 0.0)
        dpT = v @ do.T                                 # [bk, bq]
        dsT = pT * (dpT - delta_ref[...][:, 0][None, :])
        dv_acc[...] += pT @ do                         # [bk, d]
        dk_acc[...] += (dsT @ q) * scale

    @pl.when(qi == n_q - 1)
    def _done():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_operands(q, k, v, do, lse, delta):
    b, h, t, d = q.shape
    tk = k.shape[2]
    return (q.reshape(b * h, t, d), k.reshape(b * h, tk, d),
            v.reshape(b * h, tk, d), do.reshape(b * h, t, d),
            lse.reshape(b * h, t, 1),
            delta.astype(jnp.float32).reshape(b * h, t, 1))


@_traced("pallas.flash_bwd_dq")
def _flash_bwd_dq(q, k, v, do, lse, delta, scale, causal, block_q,
                  block_k, interpret):
    b, h, t, d = q.shape
    tk = k.shape[2]
    n_k = tk // block_k
    kernel = functools.partial(_dq_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, n_k=n_k)
    qf, kf, vf, dof, lsef, deltaf = _bwd_operands(q, k, v, do, lse, delta)
    dq = pl.pallas_call(
        kernel,
        grid=(b * h, t // block_q, n_k),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((None, block_k, d), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((None, block_q, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, block_q, 1), lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq", metadata={"kernel": "flash_bwd_dq"},
    )(qf, kf, vf, dof, lsef, deltaf)
    return dq.reshape(b, h, t, d)


@_traced("pallas.flash_bwd_dkv")
def _flash_bwd_dkv(q, k, v, do, lse, delta, scale, causal, block_q,
                   block_k, interpret):
    b, h, t, d = q.shape
    tk = k.shape[2]
    n_q = t // block_q
    kernel = functools.partial(_dkv_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, n_q=n_q)
    qf, kf, vf, dof, lsef, deltaf = _bwd_operands(q, k, v, do, lse, delta)
    dk, dv = pl.pallas_call(
        kernel,
        grid=(b * h, tk // block_k, n_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((None, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((None, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((None, block_q, d), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((None, block_q, 1), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((None, block_q, 1), lambda bh, ki, qi: (bh, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((None, block_k, d), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b * h, tk, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, tk, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv", metadata={"kernel": "flash_bwd_dkv"},
    )(qf, kf, vf, dof, lsef, deltaf)
    return dk.reshape(*k.shape), dv.reshape(*v.shape)


def _flash_pallas(q, k, v, scale, causal, block_q, block_k, interpret):
    b, h, t, d = q.shape
    tk = k.shape[2]            # K/V may be longer/shorter than Q
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, tk, d)
    vf = v.reshape(b * h, tk, d)
    n_k = tk // block_k
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, n_k=n_k)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, t // block_q, n_k),
        in_specs=[
            pl.BlockSpec((None, block_q, d),
                         lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, block_k, d),
                         lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((None, block_k, d),
                         lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d),
                         lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, block_q, 1),
                         lambda bh, qi, ki: (bh, qi, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, t, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd", metadata={"kernel": "flash_fwd"},
    )(qf, kf, vf)
    return out.reshape(b, h, t, d), lse.reshape(b, h, t)


# ---------------------------------------------------------------------------
# Decode-mode paged attention (ISSUE 11): one query token per sequence
# attends over K/V gathered THROUGH a block table from a paged pool.
# ---------------------------------------------------------------------------

def _paged_attention_xla(q, k_pages, v_pages, layer, block_tables,
                         context_lens, scale):
    """Identical-math XLA path: gather the pages, mask past the context
    length, softmax, weighted sum.  The gather materializes the
    per-sequence context [B, NB*bs, H, D] — fine off-TPU; the Pallas
    kernel below streams pages through VMEM instead."""
    k_ctx = k_pages[layer, block_tables]     # [B, NB, bs, H, D]
    b, nb, bs, h, d = k_ctx.shape
    k_ctx = k_ctx.reshape(b, nb * bs, h, d)
    v_ctx = v_pages[layer, block_tables].reshape(b, nb * bs, h, d)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   k_ctx.astype(jnp.float32)) * scale
    pos = jnp.arange(nb * bs, dtype=jnp.int32)
    live = pos[None, None, :] < context_lens[:, None, None]
    s = jnp.where(live, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", p, v_ctx.astype(jnp.float32))
    return out.astype(q.dtype)


# VMEM the paged kernel's page buffers may take: two slots of K and of V
_PAGED_VMEM_BUDGET = 2 << 20
# and the most pages a chunk holds, however small a page is
_PAGED_MAX_PAGES = 8


def _paged_kernel(layer_ref, tables_ref, lens_ref, q_ref, k_hbm, v_hbm,
                  o_ref, kbuf, vbuf, sems, slot_ref, *, scale, block_size,
                  pages):
    """One sequence a grid step.  The pools stay in HBM; the row's live
    pages, ``cdiv(lens[bi], block_size)`` of them, come into VMEM
    ``pages`` at a time by manual copies ``pool[layer, tables[bi, j]] ->
    buf[slot, p]``, double-buffered over two slots: the next chunk (at a
    row's end the next row's first) is in flight while this one is
    reduced, as one [pages * block_size, H, D] block with one
    online-softmax rescale.  The trip count is the row's own, so a
    call's work follows the pages held, not the table's width; and the
    order of a row's sums is fixed by ``pages`` alone, so its result
    does not depend on the batch or block-count bucket around it."""
    bi = pl.program_id(0)
    layer = layer_ref[0]
    chunk = pages * block_size

    def chunk_dma(row, c, slot, wait=False):
        """Start, or wait for, the copies of chunk ``c`` of ``row``: one
        a live page of K and of V.  A page past the row's length is not
        copied: its slot keeps stale bits, masked below."""
        n_pages = pl.cdiv(lens_ref[row], block_size)

        def page_dma(p, _):
            page = tables_ref[row, c * pages + p]
            for hbm, buf, s in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                dma = pltpu.make_async_copy(
                    hbm.at[layer, page], buf.at[slot, p], sems.at[s, slot])
                dma.wait() if wait else dma.start()

        jax.lax.fori_loop(0, jnp.clip(n_pages - c * pages, 0, pages),
                          page_dma, None)

    @pl.when(bi == 0)
    def _first():
        slot_ref[0] = 0
        chunk_dma(0, 0, 0)

    slot0 = slot_ref[0]
    ctx = lens_ref[bi]
    # at least one chunk, pages or not: each row's last chunk hands the
    # next row its first
    n_c = jnp.maximum(pl.cdiv(ctx, chunk), 1)
    q = q_ref[0].astype(jnp.float32) * scale           # [H, D]
    h, d = q.shape

    def reduce_chunk(c, carry):
        m_prev, l_prev, acc = carry
        slot = (slot0 + c) & 1
        more = c + 1 < n_c

        @pl.when(jnp.logical_or(more, bi + 1 < pl.num_programs(0)))
        def _next():    # this row's next chunk, else the next row's first
            chunk_dma(jnp.where(more, bi, bi + 1),
                      jnp.where(more, c + 1, 0), 1 - slot)

        chunk_dma(bi, c, slot, wait=True)
        k = kbuf[slot].astype(jnp.float32).reshape(chunk, h, d)
        v = vbuf[slot].astype(jnp.float32).reshape(chunk, h, d)
        # per-head mat-vec as multiply + lane reduce: Mosaic's dot has no
        # head-batched [H,D] x [S,H,D] form
        s = (k * q[None]).sum(axis=-1, keepdims=True)  # [S, H, 1]
        live = c * chunk + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) < ctx
        s = jnp.where(live, s, NEG_INF)
        # V too: p is 0 there, but 0 x (stale NaN) is NaN
        v = jnp.where(live, v, 0.0)
        m_new = jnp.maximum(m_prev, s.max(axis=0))     # [H, 1]
        p = jnp.exp(s - m_new[None])                   # [S, H, 1]
        alpha = jnp.exp(m_prev - m_new)
        return (m_new, l_prev * alpha + p.sum(axis=0),
                acc * alpha + (p * v).sum(axis=0))

    _, l_sum, acc = jax.lax.fori_loop(
        0, n_c, reduce_chunk,
        (jnp.full((h, 1), NEG_INF, jnp.float32),
         jnp.zeros((h, 1), jnp.float32),
         jnp.zeros((h, d), jnp.float32)))
    slot_ref[0] = (slot0 + n_c) & 1
    # a row of no pages: every p was exp(0), acc is 0, l is not
    o_ref[0] = (acc / l_sum).astype(o_ref.dtype)


@_traced("pallas.paged_attention",
         lambda q, *a, **kw: {"q": str(q.shape)})
def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    scale=None, layer=None, force_xla=False,
                    interpret=False):
    """Decode-mode attention through a paged KV cache (ISSUE 11; the
    vLLM/PagedAttention access pattern, TPU-native).

    ``q`` [B, H, D] — ONE query token per sequence (the decode step);
    ``k_pages``/``v_pages`` [L, N, bs, H, D] — the shared block pool of
    every layer, handed over WHOLE, with ``layer`` the static index of
    the layer that attends (a ``pool[layer]`` in front of the call is a
    copy of that layer's pool: a Mosaic call takes whole buffers); a
    one-layer pool [N, bs, H, D] goes without ``layer``;
    ``block_tables`` [B, NB] int32 — per-sequence page indices (slots
    past a row's length may point anywhere; they are never read);
    ``context_lens`` [B] int32 — tokens of real context per sequence
    (positions >= context_len are masked; a padding row may give 0 or 1:
    its output is finite either way).

    On TPU (or under ``interpret``) runs the Pallas kernel: the grid is
    the sequences, the pools stay in HBM and the block table rides
    scalar prefetch, so each row copies exactly the pages ``(layer,
    table entry)`` that hold its context, several a step — the gathered
    [B, S] context never materializes in HBM, and a call's time follows
    the pages held, not the table's width.  Elsewhere the
    identical-math XLA gather path runs."""
    if k_pages.ndim == 4:
        # the same code with a unit leading dimension (a bitcast)
        assert layer is None, "layer= indexes a [L, N, bs, H, D] pool"
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    b, h, d = q.shape
    n_l, n, bs, hp, dp = k_pages.shape
    assert (hp, dp) == (h, d), (q.shape, k_pages.shape)
    assert layer is not None and 0 <= layer < n_l, (layer, k_pages.shape)
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    block_tables = block_tables.astype(jnp.int32)
    context_lens = context_lens.astype(jnp.int32)
    # Mosaic copies a page [bs, H, D] out of the HBM pool only where D
    # fills whole lanes: a narrower head is padded to 128 there, and the
    # slice is refused as unaligned
    usable = interpret or d % 128 == 0
    if not take_pallas("paged_attention", usable, force_xla, interpret):
        return _paged_attention_xla(q, k_pages, v_pages, layer,
                                    block_tables, context_lens, scale)
    # pages a chunk, from the page's bytes alone: never from the table's
    # width, or a row's sums would change order with the bucket
    page_bytes = bs * h * d * k_pages.dtype.itemsize
    pages = max(1, min(_PAGED_MAX_PAGES,
                       _PAGED_VMEM_BUDGET // (4 * page_bytes)))
    return _paged_call(jnp.full((1,), layer, jnp.int32), block_tables,
                       context_lens, q, k_pages, v_pages, scale=float(scale),
                       pages=pages, interpret=interpret)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("scale", "pages", "interpret"))
def _paged_call(layer, block_tables, context_lens, q, k_pages, v_pages, *,
                scale, pages, interpret):
    """The kernel's call, with the layer as an operand ([1] int32, scalar
    prefetch) under an inline jit: a step's 24 layers then share one
    trace of the kernel and one Mosaic compile (a layer closed over made
    24 of each, seconds of every bucket's set-up), and the inlined call
    leaves no jit of its own in the caller's program."""
    b, h, d = q.shape
    bs = k_pages.shape[2]
    kernel = functools.partial(_paged_kernel, scale=scale, block_size=bs,
                               pages=pages)
    row = pl.BlockSpec((1, h, d), lambda bi, *prefetched: (bi, 0, 0))
    pool = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[row, pool, pool],
        out_specs=row,
        scratch_shapes=[pltpu.VMEM((2, pages, bs, h, d), k_pages.dtype),
                        pltpu.VMEM((2, pages, bs, h, d), v_pages.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        # rows in order: each hands the next its first chunk
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # no name=: pallas would open a name scope of it, XLA names the
        # custom call after its innermost scope, and the benchmark's
        # paged_attn_roofline.serve finds this kernel as %serve_decode.N
        metadata={"kernel": "paged_attention"},
    )(layer, block_tables, context_lens, q, k_pages, v_pages)


def flash_attention_fwd_lse(q, k, v, scale=None, causal=False,
                            block_q=None, block_k=None, force_xla=False,
                            interpret=False):
    """Forward returning ``(out, lse)`` — the op-level residual form.

    The fluid autodiff is op-granular: without the saved per-row
    log-sum-exp, the ``ring_attention_grad`` op's generic vjp must
    re-execute the forward kernel inside the backward (XLA cannot CSE
    opaque custom-calls), measured at ~2.5 ms/layer on the secondary
    bench.  Exposing lse as an op output turns the backward into the
    two flash kernels alone (flash_attention_bwd)."""
    b, h, t, d = q.shape
    tk = k.shape[2]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    cfg = _tuned_config(q.shape, tk, q.dtype)
    if block_q is None:
        block_q = int(cfg.get("block_q", DEF_BLOCK_Q))
    if block_k is None:
        block_k = int(cfg.get("block_k", DEF_BLOCK_K))
    block_q = _fit_block(block_q, t)
    block_k = _fit_block(block_k, tk)
    usable = (t % block_q == 0 and tk % block_k == 0)
    if not take_pallas("flash_attention_fwd_lse", usable, force_xla,
                       interpret):
        s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                       k.astype(jnp.float32)) * scale
        if causal:
            mask = jnp.tril(jnp.ones((t, tk), bool))
            s = jnp.where(mask, s, NEG_INF)
        lse = jax.nn.logsumexp(s, axis=-1)
        p = jnp.exp(s - lse[..., None])
        out = jnp.einsum("bhts,bhsd->bhtd", p,
                         v.astype(jnp.float32)).astype(q.dtype)
        return out, lse
    return _flash_pallas(q, k, v, scale, causal, block_q, block_k,
                         interpret)


def flash_attention_bwd(q, k, v, out, lse, do, scale=None, causal=False,
                        block_q=None, block_k=None, force_xla=False,
                        interpret=False):
    """Backward from op-level residuals: rebuilds P tile-by-tile from
    the saved lse (Dao et al. 2022 alg. 2) — no forward re-execution,
    no [T, T] materialization.  Returns (dq, dk, dv)."""
    b, h, t, d = q.shape
    tk = k.shape[2]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    cfg = _tuned_config(q.shape, tk, q.dtype)
    if block_q is None:
        block_q = int(cfg.get("block_q", DEF_BLOCK_Q))
    if block_k is None:
        block_k = int(cfg.get("block_k", DEF_BLOCK_K))
    block_q = _fit_block(block_q, t)
    block_k = _fit_block(block_k, tk)
    usable = (t % block_q == 0 and tk % block_k == 0)
    if not take_pallas("flash_attention_bwd", usable, force_xla,
                       interpret):
        qf = q.astype(jnp.float32)
        kf = k.astype(jnp.float32)
        vf = v.astype(jnp.float32)
        dof = do.astype(jnp.float32)
        s = jnp.einsum("bhtd,bhsd->bhts", qf, kf) * scale
        if causal:
            mask = jnp.tril(jnp.ones((t, tk), bool))
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[..., None])
        dv = jnp.einsum("bhts,bhtd->bhsd", p, dof)
        dp = jnp.einsum("bhtd,bhsd->bhts", dof, vf)
        delta = (dof * out.astype(jnp.float32)).sum(-1)
        ds = p * (dp - delta[..., None]) * scale
        dq = jnp.einsum("bhts,bhsd->bhtd", ds, kf)
        dk = jnp.einsum("bhts,bhtd->bhsd", ds, qf)
        return (dq.astype(q.dtype), dk.astype(k.dtype),
                dv.astype(v.dtype))
    do = do.astype(out.dtype)
    delta = (do.astype(jnp.float32) * out.astype(jnp.float32)).sum(-1)
    bq = _fit_block(cfg.get("block_q_bwd") or min(block_q, 512), t)
    bk = _fit_block(cfg.get("block_k_bwd") or block_k, tk)
    if t % bq:                       # K tile follows the forward (see
        bq = block_q                 # the cap note in _flash_bwd)
    if tk % bk:
        bk = block_k
    # dK/dV-specific tiles: tuned independently of dQ's (the [bk, bq]
    # orientation streams the Q axis — see _dkv_kernel)
    bq_dkv = _fit_block(cfg.get("block_q_dkv") or bq, t)
    bk_dkv = _fit_block(cfg.get("block_k_dkv") or bk, tk)
    if t % bq_dkv:
        bq_dkv = bq
    if tk % bk_dkv:
        bk_dkv = bk
    dq = _flash_bwd_dq(q, k, v, do, lse, delta, scale, causal, bq, bk,
                       interpret)
    dk, dv = _flash_bwd_dkv(q, k, v, do, lse, delta, scale, causal,
                            bq_dkv, bk_dkv, interpret)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Chunk-carry form (ISSUE 15): one online-softmax accumulator update of a
# Q shard against ONE K/V block, with the (m, l, acc) carry explicit so
# parallel/ring.py can thread it across ring steps — the tiled kernel
# replaces the ring's dense per-step einsum and no [Sq, Sk] score block
# ever lands in HBM, in either framework path.
# ---------------------------------------------------------------------------

def _tuned_ring_config(q_shape, kv_len, dtype):
    """Autotune-cache hit for a ring chunk shape ({} on miss): keyed
    'ring_attention' | (B, H, Sq_local, D, Sk_local) | dtype | backend,
    written by tools/flash_tune.py --ring (and any future longctx
    sweep); consulted at trace time, shard-local shapes."""
    from paddle_tpu import tuning

    cfg = tuning.lookup("ring_attention",
                        tuple(q_shape) + (int(kv_len),),
                        jnp.dtype(dtype).name)
    return cfg or {}


def resolve_chunk_blocks(q_shape, kv_len, dtype, block_q=None,
                         block_k=None, cfg=None):
    """(block_q, block_k) for a ring chunk: explicit args win, then the
    'ring_attention' autotune-cache entry, then the flash defaults —
    always fitted to the local shard lengths.  ``cfg`` lets a caller
    that already looked the entry up (chunk_bwd needs its *_bwd keys
    too) pass it through instead of paying a second lookup."""
    if cfg is None:
        cfg = _tuned_ring_config(q_shape, kv_len, dtype)
    if block_q is None:
        block_q = int(cfg.get("block_q", DEF_BLOCK_Q))
    if block_k is None:
        block_k = int(cfg.get("block_k", DEF_BLOCK_K))
    t, tk = q_shape[2], int(kv_len)
    block_q = _fit_block(block_q, t)
    block_k = _fit_block(block_k, tk)
    if t % block_q:
        block_q = t
    if tk % block_k:
        block_k = tk
    return block_q, block_k


def _chunk_update_xla(q, k, v, m, l, acc, scale, causal, block_k,
                      k_offset=0):
    """Blockwise XLA chunk update — identical math to the Pallas chunk
    kernel, K/V streamed ``block_k`` rows at a time through a scan so
    even the fallback never materializes the [Sq, Sk] score block."""
    b, h, t, d = q.shape
    tk = k.shape[2]
    qs = q.astype(jnp.float32) * scale
    nk = tk // block_k
    kb = jnp.moveaxis(k.reshape(b, h, nk, block_k, d), 2, 0)
    vb = jnp.moveaxis(v.reshape(b, h, nk, block_k, d), 2, 0)

    def step(carry, xs):
        m, l, acc = carry
        kj, vj, j = xs
        s = jnp.einsum("bhtd,bhkd->bhtk", qs, kj.astype(jnp.float32))
        if causal:
            q_pos = jnp.arange(t, dtype=jnp.int32)[:, None]
            k_pos = k_offset + j * block_k + jnp.arange(
                block_k, dtype=jnp.int32)[None, :]
            s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        # fully-masked guard (the ISSUE 15 hazard): when a block's rows
        # are ALL masked and no prior mass exists, m_new stays NEG_INF
        # and exp(s - m_new) == exp(0) == 1 — spurious probability mass
        # (or NaN with a true -inf sentinel).  Masked entries must
        # contribute exactly zero regardless of the running max.
        p = jnp.where(s <= 0.5 * NEG_INF, 0.0,
                      jnp.exp(s - m_new[..., None]))
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhtk,bhkd->bhtd", p, vj.astype(jnp.float32))
        return (m_new, l, acc), None

    (m, l, acc), _ = jax.lax.scan(
        step, (m, l, acc), (kb, vb, jnp.arange(nk, dtype=jnp.int32)))
    return m, l, acc


def _chunk_kernel(q_ref, k_ref, v_ref, m_in, l_in, acc_in, m_out,
                  l_out, acc_out, m_s, l_s, acc_s, *, scale, causal,
                  block_q, block_k, n_k, k_offset):
    # grid (bh, qi, ki); ki innermost SEQUENTIAL so the VMEM scratch
    # carries across K tiles — _flash_kernel's loop, but seeded from
    # the ring carry instead of (-inf, 0, 0) and written back out.
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = m_in[...]
        l_s[...] = l_in[...]
        acc_s[...] = acc_in[...]

    if causal:
        live = k_offset + ki * block_k <= qi * block_q + block_q - 1
    else:
        live = True

    @pl.when(live)
    def _step():
        q = q_ref[...].astype(jnp.float32) * scale
        k = k_ref[...].astype(jnp.float32)
        v = v_ref[...].astype(jnp.float32)
        s = q @ k.T                                   # [bq, bk]
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = k_offset + ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_s[...][:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        # fully-masked guard — see _chunk_update_xla
        p = jnp.where(s <= 0.5 * NEG_INF, 0.0,
                      jnp.exp(s - m_new[:, None]))
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = (l_s[...][:, 0] * alpha + p.sum(axis=1))[:, None]
        acc_s[...] = acc_s[...] * alpha[:, None] + p @ v
        m_s[...] = m_new[:, None]

    @pl.when(ki == n_k - 1)
    def _done():
        m_out[...] = m_s[...]
        l_out[...] = l_s[...]
        acc_out[...] = acc_s[...]


def _chunk_pallas(q, k, v, m, l, acc, scale, causal, block_q, block_k,
                  interpret, k_offset=0):
    b, h, t, d = q.shape
    tk = k.shape[2]
    n_k = tk // block_k
    kernel = functools.partial(_chunk_kernel, scale=scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, n_k=n_k,
                               k_offset=int(k_offset))
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, tk, d)
    vf = v.reshape(b * h, tk, d)
    mf = m.reshape(b * h, t, 1)
    lf = l.reshape(b * h, t, 1)
    af = acc.reshape(b * h, t, d)
    qspec = pl.BlockSpec((None, block_q, d), lambda bh, qi, ki: (bh, qi, 0))
    kspec = pl.BlockSpec((None, block_k, d), lambda bh, qi, ki: (bh, ki, 0))
    rspec = pl.BlockSpec((None, block_q, 1), lambda bh, qi, ki: (bh, qi, 0))
    m2, l2, a2 = pl.pallas_call(
        kernel,
        grid=(b * h, t // block_q, n_k),
        in_specs=[qspec, kspec, kspec, rspec, rspec, qspec],
        out_specs=[rspec, rspec, qspec],
        out_shape=[jax.ShapeDtypeStruct((b * h, t, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b * h, t, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b * h, t, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf, mf, lf, af)
    return (m2.reshape(b, h, t), l2.reshape(b, h, t),
            a2.reshape(b, h, t, d))


def flash_attention_chunk(q, k, v, m, l, acc, scale=None, causal=False,
                          block_q=None, block_k=None, force_xla=False,
                          interpret=False, k_offset=0):
    """One ring-step accumulator update: fold the K/V block into the
    online-softmax carry.

    ``q`` [B, H, Sq, D]; ``k``/``v`` [B, H, Sk, D] (ONE ring block);
    carry ``m``/``l`` [B, H, Sq] f32 (init NEG_INF / 0) and ``acc``
    [B, H, Sq, D] f32 (init 0; the UNNORMALIZED numerator).  Returns
    the updated ``(m, l, acc)``.

    ``causal=True`` means q and this K/V block share the same global
    sequence offset (the ring's diagonal chunk); off-diagonal live
    blocks are entirely in the past and take ``causal=False``.  A
    fully-masked block leaves the carry bit-identically unchanged —
    masked entries are forced to zero mass before they can poison the
    running max (the ISSUE 15 numerics hazard; pinned in
    tests/test_ring_longctx.py).  ``k_offset`` (static int) shifts the
    K block's global positions under the causal mask — 0 is the ring's
    diagonal chunk; ``k_offset >= Sq`` makes the whole block future
    (fully masked), the shard-boundary case the guard exists for.

    Tile sizes resolve through the 'ring_attention' autotune-cache
    entry (tools/flash_tune.py --ring); on the TPU/interpret path they
    shape the Pallas grid, elsewhere the blockwise-scan XLA fallback's
    K streaming, so the fallback is memory-bounded too."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    block_q, block_k = resolve_chunk_blocks(q.shape, k.shape[2],
                                            q.dtype, block_q, block_k)
    if not take_pallas("flash_attention_chunk", True, force_xla,
                       interpret):
        return _chunk_update_xla(q, k, v, m, l, acc, scale, causal,
                                 block_k, k_offset=int(k_offset))
    return _chunk_pallas(q, k, v, m, l, acc, scale, causal, block_q,
                         block_k, interpret, k_offset=int(k_offset))


def chunk_finalize(m, l, acc, dtype):
    """(out, lse) from a finished chunk carry: normalize the numerator
    and fold the running max into the per-row log-sum-exp (the residual
    the ring backward replays P from).  Rows that never saw a live key
    yield 0 output and an lse of NEG_INF, not NaN."""
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe[..., None]).astype(dtype)
    lse = jnp.where(l > 0, m + jnp.log(l_safe), NEG_INF)
    return out, lse


def _chunk_bwd_xla(q, k, v, do, lse, delta, scale, causal, block_k,
                   k_offset=0):
    """Blockwise XLA chunk backward: P rebuilt tile-by-tile from the
    saved lse (Dao et al. 2022 alg. 2), K/V streamed ``block_k`` rows
    at a time — the [Sq, Sk] probability block never materializes even
    off-TPU."""
    b, h, t, d = q.shape
    tk = k.shape[2]
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    dead = lse <= 0.5 * NEG_INF            # rows with no live key
    nk = tk // block_k
    kb = jnp.moveaxis(k.reshape(b, h, nk, block_k, d), 2, 0)
    vb = jnp.moveaxis(v.reshape(b, h, nk, block_k, d), 2, 0)

    def step(dq, xs):
        kj, vj, j = xs
        kjf = kj.astype(jnp.float32)
        vjf = vj.astype(jnp.float32)
        s = jnp.einsum("bhtd,bhkd->bhtk", qf, kjf) * scale
        if causal:
            q_pos = jnp.arange(t, dtype=jnp.int32)[:, None]
            k_pos = k_offset + j * block_k + jnp.arange(
                block_k, dtype=jnp.int32)[None, :]
            s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
        p = jnp.where((s <= 0.5 * NEG_INF) | dead[..., None], 0.0,
                      jnp.exp(s - lse[..., None]))
        dv_j = jnp.einsum("bhtk,bhtd->bhkd", p, dof)
        dp = jnp.einsum("bhtd,bhkd->bhtk", dof, vjf)
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhtk,bhkd->bhtd", ds, kjf)
        dk_j = jnp.einsum("bhtk,bhtd->bhkd", ds, qf)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(
        step, dq0, (kb, vb, jnp.arange(nk, dtype=jnp.int32)))
    dk = jnp.moveaxis(dks, 0, 2).reshape(b, h, tk, d)
    dv = jnp.moveaxis(dvs, 0, 2).reshape(b, h, tk, d)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


def flash_attention_chunk_bwd(q, k, v, do, lse, delta, scale=None,
                              causal=False, block_q=None, block_k=None,
                              force_xla=False, interpret=False,
                              k_offset=0):
    """Per-ring-step backward: (dq, dk, dv) of ONE Q shard against ONE
    K/V block, from the forward's saved per-shard lse and the
    precomputed ``delta`` = rowsum(dO * O) — no forward recompute.

    Same chunk-offset contract as ``flash_attention_chunk``: causal
    with the same static ``k_offset`` the forward used (the ring's
    diagonal chunk is offset 0).  TPU/interpret runs the two flash
    backward kernels; elsewhere — and for any causal off-diagonal
    offset, which those kernels' masks do not express — the
    blockwise-scan fallback."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    cfg = _tuned_ring_config(q.shape, k.shape[2], q.dtype)
    block_q, block_k = resolve_chunk_blocks(q.shape, k.shape[2],
                                            q.dtype, block_q, block_k,
                                            cfg=cfg)
    # the flash backward kernels' masks do not express a causal
    # off-diagonal offset: that case is a shape fall-back like any other
    if not take_pallas("flash_attention_chunk_bwd",
                       not (causal and k_offset), force_xla, interpret):
        return _chunk_bwd_xla(q, k, v, do, lse, delta, scale, causal,
                              block_k, k_offset=int(k_offset))
    t, tk = q.shape[2], k.shape[2]
    do = do.astype(q.dtype)
    delta = delta.astype(jnp.float32)
    bq = _fit_block(int(cfg.get("block_q_bwd") or min(block_q, 512)), t)
    bk = _fit_block(int(cfg.get("block_k_bwd") or block_k), tk)
    if t % bq:
        bq = block_q
    if tk % bk:
        bk = block_k
    bq_dkv = _fit_block(int(cfg.get("block_q_dkv") or bq), t)
    bk_dkv = _fit_block(int(cfg.get("block_k_dkv") or bk), tk)
    if t % bq_dkv:
        bq_dkv = bq
    if tk % bk_dkv:
        bk_dkv = bk
    dq = _flash_bwd_dq(q, k, v, do, lse, delta, scale, causal, bq, bk,
                       interpret)
    dk, dv = _flash_bwd_dkv(q, k, v, do, lse, delta, scale, causal,
                            bq_dkv, bk_dkv, interpret)
    return dq, dk, dv
