"""A served Jamba model (``model_type`` ``jamba`` with one expert, as
AI21-Jamba2-3B): Mamba-1 and multi-query attention mixers, each layer
followed by a gated SiLU feed-forward, the head tied to the embedding;
pure jnp around two kernels.

serving/generative.py is the engine; serving/lm.py says what it asks of
a served model, and serving/nemotron_h.py is the other model with state
that it serves.  This one's cache tree (``cache_spec``):

- ``conv`` [Lm, slots, 8 (K-1), D / 8] float32: a sequence's last K-1
  rows of ``x`` (the causal conv's window) end to end, per Mamba layer,
  folded so that a slot's window is whole (8, 128) tiles: the decode
  kernel writes it by slot;
- ``ssm`` [Lm, slots, N, D] float32: its state, per Mamba layer, the D
  channels (d_inner) on the lanes and the N state indices on the
  sublanes;
- ``k`` / ``v`` [La, blocks, block_size, kv_heads * head_dim] bfloat16:
  paged K/V of the attention layers.

A sequence holds ONE state slot from admission to finish or preemption;
slot 0 is scratch, as block 0 is.

Layer ``i`` is attention where ``i % attn_period == attn_offset`` and
Mamba-1 otherwise, and is pre-norm twice: ``h += mixer(RMSNorm(h))``,
then ``h += down(silu(gate(u)) * up(u))`` with ``u = RMSNorm(h)``
(benchmark/lib/reference_jamba.py has the equations in plain form).
The Mamba-1 mixer: ``[x, z] = in_proj(u)``; ``x = silu(conv(x) + b)``;
``[delta, B, C] = x_proj(x)``, each through an RMSNorm of its own; ``dt
= softplus(dt_proj(delta) + dt_bias)`` a channel; ``S[n, d] <- exp(dt_d
A[n, d]) S[n, d] + dt_d x_d B_n``, ``y_d = sum_n S[n, d] C_n + D_d x_d``;
``out_proj(y * silu(z))``.  Its decay differs by channel and by state
index, so it runs in two kernels: one token a row in decode
(kernels/ssm_update.mamba1_state_update), and a fresh prompt's tokens in
order (kernels/selective_scan), each updating the slot's state in
place.  The attention layers have no positional encoding: their
paged decode attention and flash prompt attention are nemotron_h's.

A decode step that carries a fresh prompt (``riding_forward``) runs
every weight product (``in_proj``, ``x_proj``, ``dt_proj``,
``out_proj``, ``wqkv``, ``wo``, the feed-forward) once over the rows of
both kinds; they part at the conv, the state space and the attention.

Precision: bfloat16 weights, residual stream and K/V pages; float32
accumulation in every product; float32 norm statistics, softplus / exp
/ decay, conv window, state, softmax and logits.
"""
from __future__ import annotations

import itertools

from .nemotron_h import NemotronHConfig, _mm, _rms

__all__ = ["JambaConfig"]


def _mlp(p, i, u):
    """The gated SiLU feed-forward of layer ``i`` on rows u [T, d]."""
    import jax

    gu = _mm(u, p["F.gate_up"][i])
    ff = gu.shape[1] // 2
    return _mm(jax.nn.silu(gu[:, :ff]) * gu[:, ff:], p["F.down"][i])


class JambaConfig:
    """Static shape of one ``jamba`` tenant with dense feed-forwards and
    the model the engine serves under it."""

    # the attention layers' pages, written and read as nemotron_h's are
    _write_kv = NemotronHConfig._write_kv
    _attend_pages = NemotronHConfig._attend_pages
    _ride_pages = NemotronHConfig._ride_pages
    _attend_fresh = NemotronHConfig._attend_fresh

    def __init__(self, vocab, hidden, n_layers, attn_offset, attn_period,
                 n_heads, n_kv_heads, d_inner, state, dt_rank, conv_kernel,
                 ff, eps, block_size, max_blocks, max_batch):
        self.vocab, self.hidden = int(vocab), int(hidden)
        self.n_heads, self.n_kv_heads = int(n_heads), int(n_kv_heads)
        self.head_dim = self.hidden // self.n_heads
        self.d_inner, self.state = int(d_inner), int(state)
        self.dt_rank, self.conv_kernel = int(dt_rank), int(conv_kernel)
        self.ff, self.eps = int(ff), float(eps)
        self.block_size, self.max_blocks = int(block_size), int(max_blocks)
        self.max_batch = int(max_batch)
        self.pattern = "".join(
            "*" if i % attn_period == attn_offset else "M"
            for i in range(int(n_layers)))
        if self.n_heads % self.n_kv_heads or self.hidden % self.n_heads:
            raise ValueError("heads do not divide the width or their groups")
        if self.block_size & (self.block_size - 1):
            raise ValueError("block_size must be a power of two")
        if self.d_inner % 8:
            raise ValueError("d_inner must be a multiple of 8")
        # a slot's conv window, (K-1) rows of d_inner folded by 8
        self.window = (8 * (self.conv_kernel - 1), self.d_inner // 8)
        self.max_seq = self.max_blocks * self.block_size
        # the index of each layer among the layers of its kind
        self.nth = [self.pattern[:i].count(k)
                    for i, k in enumerate(self.pattern)]
        # (kind, first layer, count) of each run of layers of one kind
        self.runs = []
        for kind, run in itertools.groupby(self.pattern):
            first = sum(n for _, _, n in self.runs)
            self.runs.append((kind, first, len(list(run))))
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
        return {"conv": sds((n_m, self.state_slots) + self.window,
                            jnp.float32),
                "ssm": sds((n_m, self.state_slots, self.state, self.d_inner),
                           jnp.float32),
                "k": page, "v": page}

    def stage(self, params, quant, device):
        """``params`` ({kind.leaf: the kind's layers stacked}) on
        ``device`` as they are: arrays already there are not copied."""
        import jax

        if quant:
            raise ValueError("jamba has no %r staging" % (quant,))
        return {k: jax.device_put(v, device) for k, v in params.items()}

    # -- layers ---------------------------------------------------------

    def _mamba(self, p, l, u, cache, conv, scan):
        """Mamba layer ``l`` (its index among the Mamba layers, static or
        traced) on normed rows u [T, d]: the mixer's output [T, d]."""
        import jax
        import jax.numpy as jnp

        eps, di, r, n = self.eps, self.d_inner, self.dt_rank, self.state
        xz = _mm(u, p["M.in_proj"][l])
        x = jax.nn.silu(conv(l, xz[:, :di], cache) + p["M.conv_b"][l])
        dbc = _mm(x, p["M.x_proj"][l])
        delta = _rms(dbc[:, :r], p["M.dt_norm"][l], eps=eps)
        b = _rms(dbc[:, r:r + n], p["M.b_norm"][l], eps=eps)
        c = _rms(dbc[:, r + n:], p["M.c_norm"][l], eps=eps)
        dt = jax.nn.softplus(_mm(delta, p["M.dt_proj"][l]) + p["M.dt_bias"][l])
        y = scan(l, x, dt, -jnp.exp(p["M.A_log"][l]), b, c, cache,
                 xz[:, :di])
        y = (y + p["M.D"][l] * x) * jax.nn.silu(xz[:, di:])
        return _mm(y, p["M.out_proj"][l])

    def _attention(self, p, l, u, cache, attend):
        t = u.shape[0]
        nh, nkv, hd = self.n_heads, self.n_kv_heads, self.head_dim
        qkv = _mm(u, p["*.wqkv"][l])
        att = attend(l, qkv[:, :nh * hd].reshape(t, nh, hd),
                     qkv[:, nh * hd:(nh + nkv) * hd].reshape(t, nkv, hd),
                     qkv[:, (nh + nkv) * hd:].reshape(t, nkv, hd), cache)
        return _mm(att, p["*.wo"][l])

    def _blocks(self, p, h, cache, conv, scan, attend):
        """Every layer in order on the residual stream ``h`` [T, d]
        (bfloat16).  The weight products run over all T rows; what lies
        between them is the mode's own: ``conv(l, x, cache)`` -> the
        causal conv of ``x`` before its bias; ``scan(l, x, dt, a, b, c,
        cache, x_in)`` -> ``S C`` (``x_in`` is ``x`` before the conv);
        ``attend(l, q, k, v, cache)`` between ``wqkv`` and ``wo``.  A run
        of Mamba layers is one ``lax.scan`` over their stacked weights
        (the program holds one such layer a run, not one a layer: a
        28-layer step compiles 3-4 times faster); an attention layer is
        unrolled.  Returns (h, cache)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        def layer(kind, i, l, h, cache):
            u = _rms(h, p[kind + ".norm"][l], eps=self.eps).astype(h.dtype)
            with jax.named_scope("layer"):
                if kind == "M":
                    with jax.named_scope("mamba"):
                        out = self._mamba(p, l, u, cache, conv, scan)
                else:
                    with jax.named_scope("attention"):
                        out = self._attention(p, l, u, cache, attend)
                h = (h.astype(jnp.float32) + out).astype(h.dtype)
                with jax.named_scope("ffn"):
                    u = _rms(h, p["F.norm"][i], eps=self.eps).astype(h.dtype)
                    return (h.astype(jnp.float32)
                            + _mlp(p, i, u)).astype(h.dtype)

        def run_of_mamba(carry, il):
            h, cache = carry
            cache = dict(cache)
            h = layer("M", il[0], il[1], h, cache)
            return (h, cache), None

        for kind, first, count in self.runs:
            l = self.nth[first]
            if kind == "*" or count == 1:
                for j in range(count):
                    h = layer(kind, first + j, l + j, h, cache)
            else:
                at = jnp.arange(count, dtype=jnp.int32)
                (h, cache), _ = lax.scan(run_of_mamba, (h, cache),
                                         jnp.stack([first + at, l + at], 1))
        return h, cache

    def _conv_step(self, p, l, x, cache, slots):
        """One token a row: the causal conv over the window of each
        row's slot and ``x``, before its bias (the window moves on in
        ``_scan_step``)."""
        import jax.numpy as jnp

        win = cache["conv"][l, slots].reshape(x.shape[0], -1, self.d_inner)
        w = p["M.conv_w"][l]
        return jnp.einsum("rkc,kc->rc", win, w[:-1]) + x * w[-1]

    def _conv_prompt(self, p, l, x, cache, length, slot):
        """A fresh prompt [S, D] (the first ``length`` real): the causal
        conv from an empty window; the slot gets the window at
        ``length``.  Returns the conv before its bias."""
        from jax import lax
        import jax.numpy as jnp

        k, s_len = self.conv_kernel, x.shape[0]
        padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
        # rows length-(K-1) .. length-1 of x: what decode's conv reads
        window = lax.dynamic_slice_in_dim(padded, length, k - 1, axis=0)
        cache["conv"] = cache["conv"].at[l, slot].set(
            window.reshape(self.window))
        w = p["M.conv_w"][l]
        return sum(padded[j:j + s_len] * w[j] for j in range(k))

    def _scan_prompt(self, l, x, dt, a, b, c, cache, length, slot):
        """A fresh prompt's state space into ``slot``: the slot (which a
        finished sequence may have left) is zeroed, and the scan walks
        the prompt from there; no step on padding."""
        import jax.numpy as jnp

        from paddle_tpu.kernels.selective_scan import selective_scan

        cache["ssm"] = cache["ssm"].at[l, slot].set(0.0)
        dt = jnp.where((jnp.arange(x.shape[0]) < length)[:, None], dt, 0.0)
        y, cache["ssm"] = selective_scan(cache["ssm"], slot, length, x, dt,
                                         a, b, c, layer=l)
        return y

    def _scan_step(self, l, x, dt, a, b, c, cache, slots, x_in):
        """One token a row: the state of each row's slot moves on, and
        its window by ``x_in``, in one kernel."""
        import jax.numpy as jnp

        from paddle_tpu.kernels.ssm_update import mamba1_state_update

        r = x.shape[0]
        win = cache["conv"][l, slots].reshape(r, -1, self.d_inner)
        window = jnp.concatenate([win[:, 1:], x_in[:, None]], axis=1)
        y, cache["ssm"], cache["conv"] = mamba1_state_update(
            cache["ssm"], cache["conv"], slots, x, dt, a, b, c,
            window.reshape((r,) + self.window), layer=l)
        return y

    def paged_forward(self, p, cache, toks, pos, tables, slots):
        """R rows, each a token at position ``pos[i]`` of the sequence
        whose block table is ``tables[i]`` and whose state slot is
        ``slots[i]``: every Mamba layer advances the slot's window and
        state by the token, the attention layers write its K/V at
        ``(table[pos // bs], pos % bs)`` and attend over the ``pos + 1``
        positions the table holds.  Returns hidden [R, d] and the
        cache."""
        import jax.numpy as jnp

        cache = dict(cache)
        blk = tables[jnp.arange(toks.shape[0]), pos // self.block_size]
        off = pos % self.block_size

        def attend(l, q, k, v, cache):
            self._write_kv(cache, l, blk, off, k, v)
            return self._attend_pages(l, q, cache, tables, pos)

        return self._blocks(
            p, p["embed"][toks], cache,
            lambda l, x, cache: self._conv_step(p, l, x, cache, slots),
            lambda l, x, dt, a, b, c, cache, x_in: self._scan_step(
                l, x, dt, a, b, c, cache, slots, x_in),
            attend)

    def riding_forward(self, p, cache, toks, pos, tables, prompt, length,
                       block_ids, slots, slot):
        """A decode step that carries a fresh prompt: ``paged_forward``'s
        R rows (a dead row sits at position 0 of a table of scratch
        blocks, in the scratch slot) and the S rows of one (padded)
        prompt, the first ``length`` real.  Every weight product runs
        once over the ``R + S`` rows; they part at the conv, the state
        space (the rows' decode update, the prompt's scan into ``slot``,
        which gets the window and the state after ``length`` tokens) and
        the attention (the rows through the pages, the prompt causal
        flash over its in-flight K/V, written with the rows' into
        ``block_ids`` [S // bs], padding to the scratch block).  Returns
        hidden [R + S, d], the rows' then the prompt's, and the
        cache."""
        import jax.numpy as jnp

        cache = dict(cache)
        r = toks.shape[0]
        blk, off = self._ride_pages(tables, pos, length, block_ids,
                                    prompt.shape[0])

        def conv(l, x, cache):
            return jnp.concatenate([
                self._conv_step(p, l, x[:r], cache, slots),
                self._conv_prompt(p, l, x[r:], cache, length, slot)])

        def scan(l, x, dt, a, b, c, cache, x_in):
            return jnp.concatenate([
                self._scan_step(l, x[:r], dt[:r], a, b[:r], c[:r], cache,
                                slots, x_in[:r]),
                self._scan_prompt(l, x[r:], dt[r:], a, b[r:], c[r:], cache,
                                  length, slot)])

        def attend(l, q, k, v, cache):
            k, v = self._write_kv(cache, l, blk, off, k, v)
            fresh = self._attend_fresh(q, k, v, r)
            return jnp.concatenate([
                self._attend_pages(l, q[:r], cache, tables, pos),
                fresh.transpose(1, 0, 2).reshape(fresh.shape[1], -1)])

        return self._blocks(
            p, p["embed"][jnp.concatenate([toks, prompt])], cache,
            conv, scan, attend)

    def head(self, p, h, n_live=None):
        """Final norm and the logit layer, tied to the embedding: hidden
        [R, d] to float32 logits [R, V]; with ``n_live`` to the last
        live row's [V]."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        with jax.named_scope("head"):
            if n_live is not None:
                h = jnp.take(h, n_live - 1, axis=0)
            u = _rms(h, p["norm_f"], eps=self.eps)
            e = p["embed"]
            return lax.dot_general(u.astype(e.dtype), e,
                                   (((u.ndim - 1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
