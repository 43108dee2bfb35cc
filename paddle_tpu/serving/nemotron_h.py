"""A served hybrid model: Mamba-2, latent mixture-of-experts and
grouped-query attention blocks (``model_type`` ``nemotron_h``), pure jnp
around two kernels.

serving/generative.py is the engine; serving/lm.py says what it asks of
a served model.  This one has **three kinds of state**, so it names its
cache as a tree (``cache_spec``) and says it keeps per-sequence state
(``state_slots``):

- ``conv`` [Lm, slots, (K-1) * conv_dim] float32: a sequence's last K-1
  rows of ``xBC`` (the causal conv's window) end to end, per Mamba
  layer;
- ``ssm`` [Lm, slots, N, H * P] float32: its SSM state, per Mamba
  layer, the state index on the sublanes and the head channels on the
  lanes (kernels/ssm_update.py);
- ``k`` / ``v`` [La, blocks, block_size, kv_heads * head_dim] bfloat16:
  paged K/V of the attention layers, a position's heads side by side in
  one row.

No array has a small axis between the one a step indexes and the one it
writes (the K-1 window rows, the 2 K/V heads): XLA lays such an array
out one way for the chip and another for the step's scatter, and copies
the whole of it between the two on every step.

A sequence holds ONE slot from admission to finish or preemption
(kv_cache.BlockPool hands it out); the engine passes the rows' slots to
``paged_forward`` and, with them, the newcomer's to ``riding_forward``
(the decode step that carries a fresh prompt: one ``in_proj`` and one
``out_proj`` a Mamba layer, one router, one pair of latent projections,
one grouped-expert call and one shared expert an expert layer, one
``wqkv`` and ``wo`` an attention layer, over the rows of both kinds;
they part between ``_mamba_in`` and ``_mamba_out`` and at the
attention).  Slot 0 is scratch, as block 0 is: padding rows aim at it.

Block ``i`` of kind ``pattern[i]``: ``h <- h + mixer_i(RMSNorm_i(h))``
(benchmark/lib/reference_nemotron_h.py has the equations in plain
form).  The Mamba-2 mixer runs in both forms: over a prompt as a chunked
scan (``chunk`` positions at a time) that leaves exactly the state after
``length`` tokens under a padded bucket (dt = 0 on padding is the
identity; the conv window is read at ``length``), and one token a row in
decode (kernels/ssm_update.py updates the state in place).  The expert
layer is **told which experts it holds** (``experts_held``, a range of
the ``n_experts`` the router scores): it routes over all of them with
the published top-k and computes the routed sum over the chosen experts
it holds (kernels/moe_grouped.py reads only the experts that were hit);
what the absent ones would add is left out, and no code stands in for
the chips that hold them or for their exchange.  The shared expert and
the latent projections run whole.

Precision: bfloat16 weights, residual stream and K/V pages; float32
accumulation in every product; float32 norm statistics, router (logits,
sigmoid, top-k, weights), softplus / exp / decay, conv window, SSM state
and its update, softmax and logits.

Counters, a decode step, a carried prompt's rows with the others (counted
on the device, returned beside the
step's tokens and read with them, so that dispatch-ahead keeps running):
``serve_moe_pairs_total`` (token-expert pairs computed here),
``serve_moe_experts_hit_total`` (held experts that got a token, summed
over the expert layers: what the grouped kernel read),
``serve_moe_expert_slots_total`` (held experts x expert layers),
``serve_moe_tile_rows_total`` (rows the grouped kernel computed in its
real tiles, padding included: pairs / tile rows is the tiles' fill).
"""
from __future__ import annotations

import math

import numpy as np

from paddle_tpu.observability import metrics as _metrics

__all__ = ["NemotronHConfig", "tiny_nemotron_h"]

_M_PAIRS = _metrics.counter(
    "serve_moe_pairs_total",
    "token-expert pairs routed to an expert this chip holds, summed over "
    "the expert layers of decode steps (padding rows included: the "
    "device computed them)")
_M_HIT = _metrics.counter(
    "serve_moe_experts_hit_total",
    "held experts that got at least one token, summed over the expert "
    "layers of decode steps: the experts whose weights a step read")
_M_SLOTS = _metrics.counter(
    "serve_moe_expert_slots_total",
    "held experts x expert layers, a decode step: hit / slots is the "
    "share of the held experts' weights a step streams")
_M_TILE_ROWS = _metrics.counter(
    "serve_moe_tile_rows_total",
    "rows the grouped expert kernel computed in its real tiles, padding "
    "included, summed over the expert layers of decode steps: pairs / "
    "tile rows is the share of the computed rows that were pairs")

_EPS = 1e-5
_TABLE_STEP = 16       # block-table slots a decode step's gather grows by


def _rms(x, w, groups=1, eps=_EPS):
    """RMSNorm with float32 statistics; ``groups`` > 1 normalises each
    group of the last axis on its own.  Returns float32."""
    import jax.numpy as jnp
    from jax import lax

    x = x.astype(jnp.float32)
    shape = x.shape
    x = x.reshape(shape[:-1] + (groups, shape[-1] // groups))
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return x.reshape(shape) * w


def _mm(x, w):
    """x @ w, operands in the weight's dtype, float32 out."""
    import jax.numpy as jnp

    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _relu2(x):
    import jax.numpy as jnp

    return jnp.square(jnp.maximum(x, 0))


class NemotronHConfig:
    """Static shape of one ``nemotron_h`` tenant and the model the engine
    serves under it.  ``pattern`` is the blocks in order (``M`` Mamba-2,
    ``E`` experts, ``*`` attention); ``experts_held`` the range of
    routed experts this chip holds."""

    def __init__(self, vocab, hidden, pattern, n_heads, n_kv_heads, head_dim,
                 mamba_heads, mamba_head_dim, n_groups, state, conv_kernel,
                 chunk, n_experts, top_k, experts_held, latent, expert_ff,
                 shared_ff, scaling, block_size, max_blocks, max_batch):
        self.vocab, self.hidden, self.pattern = int(vocab), int(hidden), str(
            pattern)
        self.n_heads, self.n_kv_heads = int(n_heads), int(n_kv_heads)
        self.head_dim = int(head_dim)
        self.mamba_heads, self.mamba_head_dim = int(mamba_heads), int(
            mamba_head_dim)
        self.n_groups, self.state = int(n_groups), int(state)
        self.conv_kernel, self.chunk = int(conv_kernel), int(chunk)
        self.n_experts, self.top_k = int(n_experts), int(top_k)
        self.experts_held = range(experts_held[0], experts_held[-1] + 1)
        self.latent, self.expert_ff = int(latent), int(expert_ff)
        self.shared_ff, self.scaling = int(shared_ff), float(scaling)
        self.block_size, self.max_blocks = int(block_size), int(max_blocks)
        self.max_batch = int(max_batch)
        if set(self.pattern) - set("ME*"):
            raise ValueError("pattern %r: M, E and * only" % (pattern,))
        if self.n_heads % self.n_kv_heads or \
                self.mamba_heads % self.n_groups:
            raise ValueError("heads do not divide into their groups")
        if not 0 <= self.experts_held[0] <= self.experts_held[-1] \
                < self.n_experts:
            raise ValueError("experts_held %r outside the %d experts"
                             % (self.experts_held, self.n_experts))
        if self.block_size & (self.block_size - 1):
            raise ValueError("block_size must be a power of two")
        self.max_seq = self.max_blocks * self.block_size
        self.d_inner = self.mamba_heads * self.mamba_head_dim
        self.conv_dim = self.d_inner + 2 * self.n_groups * self.state
        # the index of each block among the blocks of its kind
        self.nth = [self.pattern[:i].count(k)
                    for i, k in enumerate(self.pattern)]
        # per-sequence state: one slot a row and the scratch slot
        self.state_slots = self.max_batch + 1

    # -- what the engine asks of a served model -------------------------

    def cache_spec(self, n_blocks):
        import jax
        import jax.numpy as jnp

        n_m, n_a = self.pattern.count("M"), self.pattern.count("*")
        sds = jax.ShapeDtypeStruct
        page = sds((n_a, int(n_blocks), self.block_size,
                    self.n_kv_heads * self.head_dim), jnp.bfloat16)
        return {"conv": sds((n_m, self.state_slots,
                             (self.conv_kernel - 1) * self.conv_dim),
                            jnp.float32),
                "ssm": sds((n_m, self.state_slots, self.state,
                            self.d_inner), jnp.float32),
                "k": page, "v": page}

    def stage(self, params, quant, device):
        """``params`` ({kind.leaf: the kind's blocks stacked}) on
        ``device`` as they are: arrays already there are not copied."""
        import jax

        if quant:
            raise ValueError("nemotron_h has no %r staging" % (quant,))
        return {k: jax.device_put(v, device) for k, v in params.items()}

    def note_decode(self, stats, metrics_on):
        """Count what a decode step's ``stats`` ([pairs, experts hit,
        tile rows]) say; returns the ``serve.decode.wait`` span's
        arguments."""
        pairs, hit, rows = (int(v) for v in stats)
        if metrics_on:
            _M_PAIRS.inc(pairs)
            _M_HIT.inc(hit)
            _M_SLOTS.inc(len(self.experts_held) * self.pattern.count("E"))
            _M_TILE_ROWS.inc(rows)
        return {"experts_hit": hit, "tile_rows": rows}

    # -- blocks ---------------------------------------------------------

    def _mamba_in(self, p, l, u):
        """in_proj and the split: z [T, d_inner], xBC [T, conv_dim]
        (before the conv), dt [T, H] (before its bias), float32."""
        zxbcdt = _mm(u, p["M.in_proj"][l])
        return (zxbcdt[:, :self.d_inner],
                zxbcdt[:, self.d_inner:self.d_inner + self.conv_dim],
                zxbcdt[:, self.d_inner + self.conv_dim:])

    def _split_xbc(self, xbc):
        t = xbc.shape[0]
        gn = self.n_groups * self.state
        return (xbc[:, :self.d_inner].reshape(t, self.mamba_heads,
                                              self.mamba_head_dim),
                xbc[:, self.d_inner:self.d_inner + gn].reshape(
                    t, self.n_groups, self.state),
                xbc[:, self.d_inner + gn:].reshape(
                    t, self.n_groups, self.state))

    def _mamba_out(self, p, l, y, x, z):
        """Skip term, gate, grouped norm, out_proj."""
        import jax

        y = y + p["M.D"][l][:, None] * x
        y = y.reshape(y.shape[0], self.d_inner)
        y = _rms(y * jax.nn.silu(z), p["M.gnorm"][l], groups=self.n_groups)
        return _mm(y, p["M.out_proj"][l])

    def _mamba_step(self, p, l, xbc, dt, cache, slots):
        """One token a row: the window and the state of each row's slot
        read, advanced and written back.  Returns (y, x) [R, H, P]."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu.kernels.ssm_update import ssm_state_update

        r = xbc.shape[0]
        win = cache["conv"][l, slots].reshape(r, -1, self.conv_dim)
        w = p["M.conv_w"][l]
        conv = (jnp.einsum("rkc,kc->rc", win, w[:-1])
                + xbc * w[-1] + p["M.conv_b"][l])
        cache["conv"] = cache["conv"].at[l, slots].set(
            jnp.concatenate([win[:, 1:], xbc[:, None]], axis=1).reshape(
                r, -1))
        x, b, c = self._split_xbc(jax.nn.silu(conv))
        dt = jax.nn.softplus(dt + p["M.dt_bias"][l])
        y, cache["ssm"] = ssm_state_update(
            cache["ssm"], slots, x, dt, -jnp.exp(p["M.A_log"][l]), b, c,
            layer=l)
        return y, x

    def _mamba_scan(self, p, l, xbc, dt, cache, length, slot):
        """A fresh prompt [S] as a chunked scan; the slot gets the window
        and the state after ``length`` tokens.  Returns (y, x)
        [S, H, P]."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        hi = lax.Precision.HIGHEST
        s_len = xbc.shape[0]
        k = self.conv_kernel
        padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
        w = p["M.conv_w"][l]
        conv = sum(padded[j:j + s_len] * w[j] for j in range(k))
        # rows length-(K-1) .. length-1 of xBC: what decode's conv reads
        window = lax.dynamic_slice_in_dim(padded, length, k - 1, axis=0)
        cache["conv"] = cache["conv"].at[l, slot].set(window.reshape(-1))
        x, b, c = self._split_xbc(jax.nn.silu(conv + p["M.conv_b"][l]))
        dt = jax.nn.softplus(dt + p["M.dt_bias"][l])
        # no step on padding: its decay is 1 and it adds nothing
        dt = jnp.where((jnp.arange(s_len) < length)[:, None], dt, 0.0)
        a = -jnp.exp(p["M.A_log"][l])
        q = min(self.chunk, s_len)
        n_c, h, g = s_len // q, self.mamba_heads, self.n_groups
        per = h // g
        xc = (dt[..., None] * x).reshape(n_c, q, h, self.mamba_head_dim)
        bc = b.reshape(n_c, q, g, self.state)
        cc = c.reshape(n_c, q, g, self.state)
        cum = jnp.cumsum((dt * a).reshape(n_c, q, h), axis=1)  # [c, q, H]
        # inside a chunk: y_i += sum_{j<=i} (C_i . B_j) exp(cum_i-cum_j) dtx_j
        seg = cum[:, :, None, :] - cum[:, None, :, :]          # [c, i, j, H]
        tri = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None]
        decay = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)), 0.0)
        cb = jnp.einsum("cign,cjgn->cijg", cc, bc, precision=hi)
        m = jnp.repeat(cb, per, axis=-1) * decay               # [c, i, j, H]
        y = jnp.einsum("cijh,cjhp->cihp", m, xc, precision=hi)
        # what a chunk leaves: sum_j exp(cum_end - cum_j) dtx_j (x) B_j
        to_end = jnp.exp(cum[:, -1:, :] - cum)                 # [c, q, H]
        left = jnp.einsum("cjh,cjhp,cjhn->chpn", to_end, xc,
                          jnp.repeat(bc, per, axis=2), precision=hi)
        total = jnp.exp(cum[:, -1, :])                         # [c, H]

        def carry(state, inp):
            left_c, total_c = inp
            return total_c[:, None, None] * state + left_c, state

        last, before = lax.scan(
            carry, jnp.zeros((h, self.mamba_head_dim, self.state),
                             jnp.float32), (left, total))
        # across chunks: y_i += exp(cum_i) C_i . (state before the chunk)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "cihn,chpn->cihp", jnp.repeat(cc, per, axis=2), before,
            precision=hi)
        # [H, P, N] into the slot's layout: N on the sublanes, H P lanes
        cache["ssm"] = cache["ssm"].at[l, slot].set(
            last.transpose(2, 0, 1).reshape(self.state, self.d_inner))
        return y.reshape(s_len, h, self.mamba_head_dim), x

    def _qkv(self, p, l, u):
        t = u.shape[0]
        nh, nkv, hd = self.n_heads, self.n_kv_heads, self.head_dim
        qkv = _mm(u, p["*.wqkv"][l])
        return (qkv[:, :nh * hd].reshape(t, nh, hd),
                qkv[:, nh * hd:(nh + nkv) * hd].reshape(t, nkv, hd),
                qkv[:, (nh + nkv) * hd:].reshape(t, nkv, hd))

    def _moe(self, p, l, u):
        """The expert layer on rows u [T, d]: (out [T, d] float32, pairs
        computed here, held experts hit, rows the grouped kernel
        computed)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from paddle_tpu.kernels.moe_grouped import grouped_expert_ffn

        score = jax.nn.sigmoid(_mm(u, p["E.router"][l]))       # [T, n_e]
        _, chosen = lax.top_k(score + p["E.bias"][l], self.top_k)
        picked = jnp.take_along_axis(score, chosen, axis=-1)
        gates = self.scaling * picked / (
            jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
        first, n_held = self.experts_held[0], len(self.experts_held)
        local = chosen - first
        held = (local >= 0) & (local < n_held)
        lat = _mm(u, p["E.down"][l]).astype(u.dtype)
        r, hit, rows = grouped_expert_ffn(
            lat, jnp.where(held, local, -1), gates, p["E.w1"], p["E.w2"],
            layer=l, n_routed=self.n_experts)
        out = _mm(r, p["E.up"][l]) + _mm(_relu2(_mm(u, p["E.s1"][l])),
                                         p["E.s2"][l])
        return out, jnp.sum(held).astype(jnp.int32), hit, rows

    def _blocks(self, p, h, cache, mix, attend):
        """Every block in order on the residual stream ``h`` [T, d]
        (bfloat16).  The weight products run over all T rows; what lies
        between them is the mode's own: ``mix(l, xbc, dt, cache)`` ->
        (y, x) between a Mamba layer's in_proj and out_proj, ``attend(l,
        q, k, v, cache)`` between ``wqkv`` and ``wo``.  Returns (h,
        cache, [pairs, experts hit, tile rows])."""
        import jax
        import jax.numpy as jnp

        pairs = hit = rows = jnp.int32(0)
        for kind, l in zip(self.pattern, self.nth):
            u = _rms(h, p[kind + ".norm"][l]).astype(h.dtype)
            if kind == "M":
                with jax.named_scope("layer"), jax.named_scope("mamba"):
                    z, xbc, dt = self._mamba_in(p, l, u)
                    y, x = mix(l, xbc, dt, cache)
                    out = self._mamba_out(p, l, y, x, z)
            elif kind == "*":
                with jax.named_scope("layer"), jax.named_scope("attention"):
                    q, k, v = self._qkv(p, l, u)
                    att = attend(l, q, k, v, cache)
                    out = _mm(att.reshape(att.shape[0], -1), p["*.wo"][l])
            else:
                with jax.named_scope("layer"), jax.named_scope("moe"):
                    out, n, e, t = self._moe(p, l, u)
                    pairs, hit, rows = pairs + n, hit + e, rows + t
            h = (h.astype(jnp.float32) + out).astype(h.dtype)
        return h, cache, jnp.stack([pairs, hit, rows])

    def _write_kv(self, cache, l, blk, off, k, v):
        """Rows' K/V [T, kv, hd] into the pages at ``(blk, off)``, a
        position's heads side by side; returns them in the pages'
        dtype."""
        pages = cache["k"].dtype
        k, v = k.astype(pages), v.astype(pages)
        cache["k"] = cache["k"].at[l, blk, off].set(k.reshape(len(blk), -1))
        cache["v"] = cache["v"].at[l, blk, off].set(v.reshape(len(blk), -1))
        return k, v

    def _attend_pages(self, l, q, cache, tables, pos):
        """Rows ``q`` [R, nh, hd], each over the ``pos + 1`` positions
        its table holds, the pages gathered by XLA: [R, nh * hd]."""
        import jax.numpy as jnp
        from jax import lax

        bs, r = self.block_size, q.shape[0]
        pages = cache["k"].dtype
        rep = self.n_heads // self.n_kv_heads
        scale = 1.0 / math.sqrt(self.head_dim)
        qg = q.reshape(r, self.n_kv_heads, rep, self.head_dim).astype(pages)

        def over(width):
            """Attention through the first ``width`` table slots."""
            def run(kp, vp):
                t = tables[:, :width]
                # each row's pages, gathered: [R, width * bs, kv, hd]
                kk = kp[l][t].reshape(r, -1, self.n_kv_heads, self.head_dim)
                vv = vp[l][t].reshape(r, -1, self.n_kv_heads, self.head_dim)
                s = jnp.einsum("rgid,rsgd->rgis", qg, kk,
                               preferred_element_type=jnp.float32) * scale
                seen = jnp.arange(kk.shape[1])[None, :] <= pos[:, None]
                s = jnp.where(seen[:, None, None, :], s, -jnp.inf)
                w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
                w = w / jnp.sum(w, axis=-1, keepdims=True)
                return jnp.einsum("rgis,rsgd->rgid", w.astype(pages), vv,
                                  preferred_element_type=jnp.float32)
            return run

        # the gather follows the pages the longest row holds, in
        # steps of _TABLE_STEP, not the bucket the tables came in
        nb = tables.shape[1]
        widths = list(range(_TABLE_STEP, nb, _TABLE_STEP)) + [nb]
        return lax.switch(jnp.max(pos) // (bs * _TABLE_STEP),
                          [over(w) for w in widths],
                          cache["k"], cache["v"]).reshape(r, -1)

    def paged_forward(self, p, cache, toks, pos, tables, slots):
        """R rows, each a token at position ``pos[i]`` of the sequence
        whose block table is ``tables[i]`` and whose state slot is
        ``slots[i]``: every Mamba layer advances the slot's window and
        state by the token, the attention layers write its K/V at
        ``(table[pos // bs], pos % bs)`` and attend over the ``pos + 1``
        positions the table holds.  Returns hidden [R, d], the cache and
        the step's counts."""
        import jax.numpy as jnp

        cache = dict(cache)
        blk = tables[jnp.arange(toks.shape[0]), pos // self.block_size]
        off = pos % self.block_size

        def attend(l, q, k, v, cache):
            self._write_kv(cache, l, blk, off, k, v)
            return self._attend_pages(l, q, cache, tables, pos)

        return self._blocks(
            p, p["embed"][toks], cache,
            lambda l, xbc, dt, cache: self._mamba_step(p, l, xbc, dt, cache,
                                                       slots),
            attend)

    def riding_forward(self, p, cache, toks, pos, tables, prompt, length,
                       block_ids, slots, slot):
        """A decode step that carries a fresh prompt: ``paged_forward``'s
        R rows (a dead row sits at position 0 of a table of scratch
        blocks, in the scratch slot) and the S rows of one (padded)
        prompt, the first ``length`` real.  Every weight product (the
        mixers' projections, the router, the latent projections, the
        grouped and the shared experts) runs once over the ``R + S``
        rows; they part only inside the mixers: the R rows advance
        their slots by a token and attend through the pages, the S rows
        run the chunked scan into ``slot`` (which gets the window and
        the state after ``length`` tokens) and causal flash attention
        over the in-flight K/V, written with the rows' into
        ``block_ids`` [S // bs] (padding to the scratch block).  Returns
        hidden [R + S, d], the rows' then the prompt's, the cache and
        the step's counts, the prompt's pairs and experts with the
        rows'."""
        import jax.numpy as jnp

        cache = dict(cache)
        r = toks.shape[0]
        blk, off = self._ride_pages(tables, pos, length, block_ids,
                                    prompt.shape[0])

        def mix(l, xbc, dt, cache):
            stepped = self._mamba_step(p, l, xbc[:r], dt[:r], cache, slots)
            scanned = self._mamba_scan(p, l, xbc[r:], dt[r:], cache, length,
                                       slot)
            return tuple(jnp.concatenate(both)
                         for both in zip(stepped, scanned))

        def attend(l, q, k, v, cache):
            k, v = self._write_kv(cache, l, blk, off, k, v)
            fresh = self._attend_fresh(q, k, v, r)
            return jnp.concatenate([
                self._attend_pages(l, q[:r], cache, tables, pos),
                fresh.transpose(1, 0, 2).reshape(fresh.shape[1], -1)])

        return self._blocks(
            p, p["embed"][jnp.concatenate([toks, prompt])], cache,
            mix, attend)

    def _ride_pages(self, tables, pos, length, block_ids, s_len):
        """Where a carrying step writes K/V: ``(block, offset)`` of the
        R rows (each at ``pos`` through its table) then of the S prompt
        rows (position ``i`` in ``block_ids[i // bs]``, padding in the
        scratch block)."""
        import jax.numpy as jnp

        r = tables.shape[0]
        at = jnp.arange(s_len, dtype=jnp.int32)
        blk = jnp.concatenate([
            tables[jnp.arange(r), pos // self.block_size],
            jnp.where(at < length, block_ids[at // self.block_size], 0)])
        off = jnp.concatenate([pos, at]) % self.block_size
        return blk, off

    def _attend_fresh(self, q, k, v, r):
        """A fresh prompt's causal attention over its own K/V (in the
        pages' dtype), flash, each K/V head serving its query heads: the
        prompt is rows ``r:`` of q [R + S, nh, hd] and k / v [R + S, kv,
        hd]; returns [nh, S, hd]."""
        import jax.numpy as jnp

        from paddle_tpu.kernels.flash_attention import flash_attention

        rep = self.n_heads // self.n_kv_heads
        q4 = q[r:].astype(k.dtype).transpose(1, 0, 2)[None]
        k4 = jnp.repeat(k[r:], rep, axis=1).transpose(1, 0, 2)[None]
        v4 = jnp.repeat(v[r:], rep, axis=1).transpose(1, 0, 2)[None]
        return flash_attention(q4, k4, v4, causal=True)[0]

    def head(self, p, h, n_live=None):
        """Final norm and the logit layer: hidden [R, d] to float32
        logits [R, V]; with ``n_live`` to the last live row's [V]."""
        import jax
        import jax.numpy as jnp

        with jax.named_scope("head"):
            if n_live is not None:
                h = jnp.take(h, n_live - 1, axis=0)
            return _mm(_rms(h, p["norm_f"]), p["head"])


def tiny_nemotron_h(seed, pattern="MEM*E", vocab=64, hidden=32,
                    experts_held=(0, 3), **kw):
    """(config, params) of a seeded little hybrid: the test family.
    ``experts_held`` is (first, last) of the ``n_experts`` (default 8)
    routed experts."""
    kw = dict(dict(n_heads=4, n_kv_heads=2, head_dim=8, mamba_heads=4,
                   mamba_head_dim=8, n_groups=2, state=16, conv_kernel=4,
                   chunk=8, n_experts=8, top_k=3, latent=16, expert_ff=24,
                   shared_ff=40, scaling=2.5, block_size=8, max_blocks=8,
                   max_batch=4), **kw)
    cfg = NemotronHConfig(vocab=vocab, hidden=hidden, pattern=pattern,
                          experts_held=experts_held, **kw)
    rng = np.random.RandomState(seed)

    def w(*shape, scale=0.2):
        import jax.numpy as jnp

        return jnp.asarray(rng.randn(*shape) * scale, jnp.bfloat16)

    def f(*shape, scale=0.2, mean=0.0):
        return (mean + rng.randn(*shape) * scale).astype(np.float32)

    n_m, n_a, n_e = (pattern.count(k) for k in "M*E")
    d, held = hidden, len(cfg.experts_held)
    in_proj = 2 * cfg.d_inner + 2 * cfg.n_groups * cfg.state + cfg.mamba_heads
    qkv = (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim
    params = {
        "embed": w(vocab, d, scale=1.0), "norm_f": f(d, mean=1.0),
        "head": w(d, vocab),
        "M.norm": f(n_m, d, mean=1.0), "M.in_proj": w(n_m, d, in_proj),
        "M.conv_w": f(n_m, cfg.conv_kernel, cfg.conv_dim, scale=0.4),
        "M.conv_b": f(n_m, cfg.conv_dim),
        "M.dt_bias": f(n_m, cfg.mamba_heads, mean=-2.0, scale=0.5),
        "M.A_log": np.log(rng.uniform(1, 16, (n_m, cfg.mamba_heads))).astype(
            np.float32),
        "M.D": f(n_m, cfg.mamba_heads, mean=1.0),
        "M.gnorm": f(n_m, cfg.d_inner, mean=1.0),
        "M.out_proj": w(n_m, cfg.d_inner, d),
        "*.norm": f(n_a, d, mean=1.0), "*.wqkv": w(n_a, d, qkv),
        "*.wo": w(n_a, cfg.n_heads * cfg.head_dim, d),
        "E.norm": f(n_e, d, mean=1.0), "E.router": w(n_e, d, cfg.n_experts),
        "E.bias": f(n_e, cfg.n_experts, scale=0.05),
        "E.down": w(n_e, d, cfg.latent), "E.up": w(n_e, cfg.latent, d),
        "E.w1": w(n_e, held, cfg.latent, cfg.expert_ff),
        "E.w2": w(n_e, held, cfg.expert_ff, cfg.latent),
        "E.s1": w(n_e, d, cfg.shared_ff), "E.s2": w(n_e, cfg.shared_ff, d),
    }
    return cfg, params
