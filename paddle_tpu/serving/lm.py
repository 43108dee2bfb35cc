"""The served model: a pre-LN multi-head-attention transformer LM,
pure jnp.

serving/generative.py is the engine: pages, buckets, donation, the
scheduler.  Everything that is the MODEL's is here, behind the
things the engine asks of the config object it was given:

- ``cache_spec(n_blocks)``: the cache as a tree of
  ``jax.ShapeDtypeStruct``, which the engine allocates, donates whole
  through every step and re-binds; this model's is the K/V pair.  The
  forwards take the tree in ONE argument and hand it back.  (A model
  with per-sequence state also says ``state_slots`` and takes
  ``slots`` / ``slot`` in its forwards: serving/nemotron_h.py.)
- ``page_spec(n_blocks)``: shape and dtype of each of the two page
  arrays for ``n_blocks`` blocks, for a model whose cache is K/V pages
  alone: ``import_blocks`` checks migrated pages against it and the
  fleet's MigrateKV handshake is filled from it.
- ``stage(params, quant, device)``: the parameters as the forwards
  take them, put on ``device``.
- ``paged_forward``: R rows, each a token, a position and a block
  table; K/V written through the tables, attention through the pages.
  Decode is R = batch, a speculative verify R = batch x (k+1), the
  draft's proposal k of them chained, a prefix-cache hit's suffix
  prefill R rows of one sequence.
- ``riding_forward``: a decode step that carries a fresh prompt:
  ``paged_forward``'s R rows and the S rows of one prompt through every
  weight product together, parted only at the attention (the R rows
  through the pages, the S rows through causal flash attention over
  the in-flight K/V), the K/V of both written in the same call; hidden
  ``[R + S, D]``.  The engine compiles it by the prompt's bucket with
  the decode half at its top bucket, under the decode step's name, and
  serves every fresh prompt with it: riding the step of the running
  rows (``DecodeLoop``), or with no live row, a prefill alone.  A model
  may leave it out and offer ``prompt_forward(p, cache, toks, length,
  block_ids)``, a fresh prompt whole, instead: the engine then compiles
  that as its prefill and the loop waits every prefill out
  (tests/test_serving_model_seam.py serves such a model).
- ``head``: hidden rows to logits.

and the paging geometry the engine reads as plain attributes
(``vocab``, ``block_size``, ``max_blocks``, ``max_batch``,
``max_seq``).  No forward is a ``jax.jit`` or a ``named_scope`` of
its own: they trace into the engine's step, whose name the executable
and every Mosaic call in it carry (the benchmark's
``paged_attn_roofline.serve`` finds the decode kernel as
``%serve_decode.N``, in the plain step and in the one that carries a
prompt; the flash kernel has its own name, ``%flash_fwd.N``).

**int8 weight-quantized decode** is gated per tenant at load
(``quant='int8'``): the projection and MLP weights (``_QUANT_SLOTS``)
are quantized per chunk with distributed/compress.py's symmetric
quantizer and dequantized inside the matmul kernel's VMEM epilogue
(kernels/matmul_fused.matmul_int8_dequant).  Embeddings, norms and the
logit layer stay fp32 (the logit layer is where quantization noise
turns into token flips).
"""
from __future__ import annotations

import numpy as np

from paddle_tpu.core.flags import FLAGS

__all__ = ["LMConfig", "tiny_lm"]

# weights quantized under quant='int8' (embed/pos/lm_head/LN stay fp32)
_QUANT_SLOTS = ("wqkv", "wo", "w1", "w2")


def _mm(p, name, x):
    """x @ W with the tenant's quantization gate: int8 weights (staged
    as ``(q, scales)``) run the epilogue-dequant kernel (XLA-identical
    fallback off-TPU).

    Rows are padded to a multiple of 8 (one f32 sublane tile — free
    on the chip) so a row's result does not depend on the batch
    bucket it rode in: XLA answers a ONE-row f32 dot exactly on the
    vector unit but rounds the operands of a many-row dot to bf16
    for the MXU (measured on the v5e: 0.03 absolute on O(10) values,
    enough to flip greedy tokens between the batch-1 bucket and the
    rest), and the int8 kernel only tiles from 8 rows up."""
    import jax.numpy as jnp

    from paddle_tpu.kernels.matmul_fused import matmul_int8_dequant

    m = x.shape[0]
    pad = -m % 8
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    w = p[name]
    if isinstance(w, tuple):
        q, s = w
        # scales are [K // chunk, N] (matmul_fused.quantize_weight)
        y = matmul_int8_dequant(x, q, s, q.shape[0] // s.shape[0])
    else:
        y = jnp.dot(x, w)
    return y[:m] if pad else y


def _layer_norm(x, scale, bias, eps=1e-5):
    import jax.numpy as jnp
    from jax import lax

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * scale + bias


def _block_fwd(p, l, h, attend):
    """One transformer block: pre-LN attention (via ``attend``, which
    owns the K/V cache writes + attention for its mode) then the pre-LN
    gelu MLP.

    The projections and the MLP sit in ``layer/attention`` and
    ``layer/ffn`` name scopes; ``attend`` is called outside every
    scope: XLA names a Mosaic custom call after the innermost scope
    around it, and the benchmark's paged_attn_roofline.serve finds
    the decode kernel as ``%serve_decode.N`` (the kernel's own name
    rides its ``kernel_metadata``, kernels/flash_attention.py)."""
    import jax

    pre = "l%d." % l
    with jax.named_scope("layer"), jax.named_scope("attention"):
        a = _layer_norm(h, p[pre + "ln1.scale"], p[pre + "ln1.bias"])
        qkv = _mm(p, pre + "wqkv", a)
    att = attend(l, qkv)
    with jax.named_scope("layer"):
        with jax.named_scope("attention"):
            h = h + _mm(p, pre + "wo", att)
        with jax.named_scope("ffn"):
            m = _layer_norm(h, p[pre + "ln2.scale"], p[pre + "ln2.bias"])
            h = h + _mm(p, pre + "w2",
                        jax.nn.gelu(_mm(p, pre + "w1", m),
                                    approximate=True))
    return h


class LMConfig:
    """Static model/runtime shape of one generative tenant, and the
    model the engine serves under it."""

    def __init__(self, vocab, d_model, n_heads, n_layers, d_ff,
                 block_size=None, max_blocks=8, max_batch=None):
        self.vocab = int(vocab)
        self.d_model = int(d_model)
        self.n_heads = int(n_heads)
        self.n_layers = int(n_layers)
        self.d_ff = int(d_ff)
        self.block_size = int(block_size or FLAGS.serve_kv_block_size)
        self.max_blocks = int(max_blocks)
        self.max_batch = int(max_batch or FLAGS.serve_max_batch)
        if self.d_model % self.n_heads:
            raise ValueError("d_model %% n_heads != 0")
        if self.block_size < 1 or \
                self.block_size & (self.block_size - 1):
            raise ValueError("block_size must be a positive power of "
                             "two (got %d)" % self.block_size)
        self.head_dim = self.d_model // self.n_heads
        self.max_seq = self.max_blocks * self.block_size

    def todict(self):
        return {k: getattr(self, k) for k in
                ("vocab", "d_model", "n_heads", "n_layers", "d_ff",
                 "block_size", "max_blocks", "max_batch")}

    # -- what the engine asks of a served model -------------------------

    def page_spec(self, n_blocks):
        """``(shape, dtype)`` of the K pool and of the V pool for
        ``n_blocks`` blocks: layers x blocks x block_size x heads x
        head_dim."""
        return ((self.n_layers, int(n_blocks), self.block_size,
                 self.n_heads, self.head_dim), np.float32)

    def cache_spec(self, n_blocks):
        """The cache the engine holds for this model: the K pool and
        the V pool."""
        import jax

        shape, dtype = self.page_spec(n_blocks)
        return (jax.ShapeDtypeStruct(shape, dtype),) * 2

    def stage(self, params, quant, device):
        """``params`` on ``device`` as the forwards take them:
        projection/MLP weights per-chunk int8 ``(q, scales)`` under
        ``quant='int8'`` (compress.quantize_symmetric via
        matmul_fused.quantize_weight), everything else fp32."""
        import jax

        staged = {}
        for k, v in params.items():
            if quant == "int8" and k.split(".")[-1] in _QUANT_SLOTS:
                from paddle_tpu.kernels.matmul_fused import quantize_weight
                q, s, _ = quantize_weight(v)
                staged[k] = (jax.device_put(q, device),
                             jax.device_put(s, device))
            else:
                staged[k] = jax.device_put(
                    np.asarray(v, np.float32), device)
        return staged

    def _split_heads(self, qkv):
        import jax.numpy as jnp

        r = qkv.shape[0]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        return (q.reshape(r, self.n_heads, self.head_dim),
                k.reshape(r, self.n_heads, self.head_dim),
                v.reshape(r, self.n_heads, self.head_dim))

    def paged_forward(self, p, cache, toks, pos, tables, live=None):
        """R rows through the pages: row i feeds token ``toks[i]`` at
        position ``pos[i]`` of the sequence whose block table is
        ``tables[i]`` [R, NB]; a layer writes its K/V at ``(table[pos
        // bs], pos % bs)`` and attends over the ``pos + 1`` positions
        the table holds — rows of one sequence at consecutive
        positions see each other's K/V, written this same call, so
        causality falls out of the page gather.  ``live`` [R] bool
        (None: every row) sends the other rows to position 0 and their
        writes to the reserved scratch block.  Returns hidden [R, D]
        and the cache."""
        import jax.numpy as jnp

        from paddle_tpu.kernels.flash_attention import paged_attention

        kp, vp = cache
        if live is not None:
            pos = jnp.where(live, pos, 0)
        h = p["embed"][toks] + p["pos"][pos]               # [R, D]
        lens = pos + 1
        rows = jnp.arange(toks.shape[0])
        blk = tables[rows, pos // self.block_size]         # [R]
        if live is not None:
            blk = jnp.where(live, blk, 0)
        off = pos % self.block_size

        def attend(l, qkv):
            nonlocal kp, vp
            q, k, v = self._split_heads(qkv)
            kp = kp.at[l, blk, off].set(k)
            vp = vp.at[l, blk, off].set(v)
            att = paged_attention(q, kp, vp, tables, lens, layer=l)
            return att.reshape(-1, self.d_model)

        for l in range(self.n_layers):
            h = _block_fwd(p, l, h, attend)
        return h, (kp, vp)

    def riding_forward(self, p, cache, toks, pos, tables, prompt, length,
                       block_ids):
        """A decode step that carries a fresh prompt: the R rows of
        ``paged_forward`` (``toks``, ``pos``, ``tables``; a dead row
        sits at position 0 of a table of scratch blocks) and the S rows
        of one (padded) prompt, the first ``length`` real, whose K/V
        goes into the blocks ``block_ids`` [S // bs] (pad positions to
        the scratch block).  Every weight product runs once over the
        ``R + S`` rows; they part only at the attention: the R rows go
        through the pages, the S rows through causal flash attention
        over the in-flight K/V, both written in the same scatter.
        Returns hidden [R + S, D], the rows' then the prompt's, and the
        cache."""
        import jax.numpy as jnp

        from paddle_tpu.kernels.flash_attention import (flash_attention,
                                                        paged_attention)

        kp, vp = cache
        r, s_len = toks.shape[0], prompt.shape[0]
        at = jnp.arange(s_len, dtype=jnp.int32)
        both = jnp.concatenate([pos, at])
        h = p["embed"][jnp.concatenate([toks, prompt])] + p["pos"][both]
        blk = jnp.concatenate([
            tables[jnp.arange(r), pos // self.block_size],
            jnp.where(at < length, block_ids[at // self.block_size], 0)])
        off = both % self.block_size

        def attend(l, qkv):
            nonlocal kp, vp
            q, k, v = self._split_heads(qkv)
            kp = kp.at[l, blk, off].set(k)
            vp = vp.at[l, blk, off].set(v)
            paged = paged_attention(q[:r], kp, vp, tables, pos + 1, layer=l)
            # the prompt's rows see the columns up to their own, in
            # flight: the values just written to its pages
            q4, k4, v4 = (x[r:].transpose(1, 0, 2)[None] for x in (q, k, v))
            fresh = flash_attention(q4, k4, v4, causal=True)[0]
            return jnp.concatenate(
                [paged, fresh.transpose(1, 0, 2)]).reshape(-1, self.d_model)

        for l in range(self.n_layers):
            h = _block_fwd(p, l, h, attend)
        return h, (kp, vp)

    def head(self, p, h, n_live=None):
        """Final norm + logit layer: hidden [R, D] to fp32 logits
        [R, V]; with ``n_live`` (a traced scalar: the first ``n_live``
        rows are one sequence's real positions) to the last live row's
        [V]."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope("head"):
            h = _layer_norm(h, p["lnf.scale"], p["lnf.bias"])
            if n_live is None:
                return _mm(p, "lm_head", h)
            return jnp.dot(jnp.take(h, n_live - 1, axis=0), p["lm_head"])


def tiny_lm(seed, vocab=256, d_model=64, n_heads=4, n_layers=2,
            d_ff=128, **cfg_kw):
    """(config, params) of a seeded little LM — the serve_bench/test
    model family.  Different seeds draw different parameters, so two
    'versions' answer distinguishably (the serve_bench classification
    trick)."""
    cfg = LMConfig(vocab, d_model, n_heads, n_layers, d_ff, **cfg_kw)
    rng = np.random.RandomState(seed)

    def w(*shape):
        return (rng.randn(*shape) * 0.1).astype(np.float32)

    params = {"embed": w(cfg.vocab, cfg.d_model),
              "pos": w(cfg.max_seq, cfg.d_model),
              "lnf.scale": np.ones(cfg.d_model, np.float32),
              "lnf.bias": np.zeros(cfg.d_model, np.float32),
              "lm_head": w(cfg.d_model, cfg.vocab)}
    for l in range(cfg.n_layers):
        p = "l%d." % l
        params[p + "wqkv"] = w(cfg.d_model, 3 * cfg.d_model)
        params[p + "wo"] = w(cfg.d_model, cfg.d_model)
        params[p + "w1"] = w(cfg.d_model, cfg.d_ff)
        params[p + "w2"] = w(cfg.d_ff, cfg.d_model)
        for ln in ("ln1", "ln2"):
            params[p + ln + ".scale"] = np.ones(cfg.d_model, np.float32)
            params[p + ln + ".bias"] = np.zeros(cfg.d_model, np.float32)
    return cfg, params
