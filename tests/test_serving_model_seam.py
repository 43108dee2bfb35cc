"""The seam between the generate plane's engine and the model it serves
(serving/generative.py | serving/lm.py): the engine asks the config
object it was given for the page spec, the staging rule, the forward
over paged rows, the forward over a fresh prompt and the logit layer,
and reads its paging geometry — nothing else.

(a) a second model, defined HERE, is loaded and served by the same
    engine, scheduler and cache; (b) decode, verify and propose are
    callers of one forward; (c) the pool, ``import_blocks`` and the
    fleet's MigrateKV handshake read one page spec."""
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.sanitizer import BufferLifetimeError
from paddle_tpu.kernels.flash_attention import paged_attention
from paddle_tpu.serving import (FleetWorker, GenerativeEngine,
                                InferenceServer, LMConfig, LocalTransport,
                                tiny_lm)
from paddle_tpu.serving.fleet import (M_MIGRATE, _kv_head, decode_call,
                                      encode_migrate)
from paddle_tpu.serving.generative import GenRequest


# ---------------------------------------------------------------------------
# (a) the second model: ONE attention head as wide as the model (so a
# page is [L, N, bs, 1, D]), norms without parameters but a final
# scale, a ReLU FFN, dense causal attention over a fresh prompt
# ---------------------------------------------------------------------------

def _rms(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)


def _dot(x, w):
    # rows padded to 8: a row's result must not depend on its batch
    pad = -x.shape[0] % 8
    return jnp.dot(jnp.pad(x, ((0, pad), (0, 0))), w)[:x.shape[0]]


class ReluLM:
    def __init__(self, vocab, width, depth, ffn, block_size, max_blocks,
                 max_batch):
        self.vocab, self.width, self.depth, self.ffn = vocab, width, depth, ffn
        self.block_size, self.max_blocks = block_size, max_blocks
        self.max_batch, self.max_seq = max_batch, block_size * max_blocks

    def page_spec(self, n_blocks):
        return ((self.depth, n_blocks, self.block_size, 1, self.width),
                np.float32)

    def cache_spec(self, n_blocks):
        return (jax.ShapeDtypeStruct(*self.page_spec(n_blocks)),) * 2

    def stage(self, params, quant, device):
        assert not quant
        return {k: jax.device_put(v, device) for k, v in params.items()}

    def _forward(self, p, kp, vp, toks, pos, blk, attend):
        h = p["emb"][toks] + p["pe"][pos]
        for l in range(self.depth):
            q, k, v = jnp.split(_dot(_rms(h), p["qkv%d" % l])[:, None], 3, -1)
            kp = kp.at[l, blk, pos % self.block_size].set(k)
            vp = vp.at[l, blk, pos % self.block_size].set(v)
            h = h + _dot(attend(l, q, k, v, kp, vp)[:, 0], p["o%d" % l])
            h = h + _dot(jax.nn.relu(_dot(_rms(h), p["up%d" % l])),
                         p["down%d" % l])
        return h, (kp, vp)

    def paged_forward(self, p, cache, toks, pos, tables, live=None):
        kp, vp = cache
        if live is not None:
            pos = jnp.where(live, pos, 0)
        blk = tables[jnp.arange(toks.shape[0]), pos // self.block_size]
        if live is not None:
            blk = jnp.where(live, blk, 0)
        return self._forward(
            p, kp, vp, toks, pos, blk,
            lambda l, q, k, v, kp, vp: paged_attention(
                q, kp, vp, tables, pos + 1, layer=l))

    def prompt_forward(self, p, cache, toks, length, block_ids):
        kp, vp = cache
        pos = jnp.arange(toks.shape[0], dtype=jnp.int32)
        blk = jnp.where(pos < length, block_ids[pos // self.block_size], 0)
        return self._forward(p, kp, vp, toks, pos, blk,
                             lambda l, q, k, v, kp, vp: _dense(q, k, v))

    def head(self, p, h, n_live=None):
        h = _rms(h) * p["g"]
        if n_live is None:
            return _dot(h, p["out"])
        return jnp.dot(jnp.take(h, n_live - 1, axis=0), p["out"])


def _dense(q, k, v):
    """Causal attention of [S, 1, D] rows, every column in flight."""
    s = jnp.einsum("shd,thd->hst", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones(s.shape[1:], bool)), s, -jnp.inf)
    return jnp.einsum("hst,thd->shd", jax.nn.softmax(s, axis=-1), v)


def relu_lm(seed, depth=2, width=32, ffn=48):
    cfg = ReluLM(vocab=64, width=width, depth=depth, ffn=ffn, block_size=8,
                 max_blocks=8, max_batch=4)
    rng = np.random.RandomState(seed)

    def w(*shape):
        return (rng.randn(*shape) * 0.2).astype(np.float32)

    params = {"emb": w(64, width), "pe": w(cfg.max_seq, width),
              "g": 1 + w(width), "out": w(width, 64)}
    for l in range(depth):
        params.update({"qkv%d" % l: w(width, 3 * width),
                       "o%d" % l: w(width, width),
                       "up%d" % l: w(width, ffn),
                       "down%d" % l: w(ffn, width)})
    return cfg, params


def relu_lm_reference(cfg, params, prompt, n_new):
    """Greedy tokens by the plain loop: the whole sequence through dense
    causal attention again for every token, no cache, no engine."""
    seq = list(prompt)
    for _ in range(n_new):
        h = params["emb"][np.asarray(seq)] + params["pe"][:len(seq)]
        for l in range(cfg.depth):
            q, k, v = jnp.split((_rms(h) @ params["qkv%d" % l])[:, None],
                                3, -1)
            h = h + _dense(q, k, v)[:, 0] @ params["o%d" % l]
            h = h + jax.nn.relu(_rms(h) @ params["up%d" % l]) \
                @ params["down%d" % l]
        logits = (_rms(h[-1]) * params["g"]) @ params["out"]
        seq.append(int(jnp.argmax(logits)))
    return seq[len(prompt):]


PROMPTS = [[5, 9, 2], list(range(1, 12)), [7] * 17 + [3, 4],
           list(range(20, 29))]


@pytest.mark.parametrize("load_kw", [
    {},
    {"prefix_cache": True},
    {"spec_k": 2},
], ids=["plain", "prefix_cache", "speculative"])
def test_a_second_model_is_served_by_the_same_engine(load_kw):
    """No edit to the engine, the scheduler or the cache: the model
    above goes through ``load_generative`` / ``generate`` and answers
    with the plain loop's tokens, solo and batched — on the decode path,
    through prefix-cache hits (the suffix prefill's ``live`` rows) and
    with a one-layer draft of its own family proposing (propose +
    verify)."""
    cfg, params = relu_lm(11)
    if "spec_k" in load_kw:
        load_kw = dict(load_kw, draft=relu_lm(12, depth=1))
    want = [relu_lm_reference(cfg, params, p, 10) for p in PROMPTS]
    srv = InferenceServer()
    try:
        eng = srv.load_generative("relu", cfg, params, kv_blocks=32,
                                  warm=False, **load_kw)
        assert eng.config is cfg
        kp, vp, _ = eng.kv_pages()
        assert kp.shape == vp.shape == (2, 32, 8, 1, 32)
        solo = [srv.generate("relu", p, max_new_tokens=10)
                .result(300)["tokens"] for p in PROMPTS]
        futs = [srv.generate("relu", p, max_new_tokens=10)
                for p in PROMPTS]
        batched = [f.result(300)["tokens"] for f in futs]
        assert solo == want
        assert batched == want
        if eng.prefix_cache is not None:
            # the batched pass met the three longer prompts' full
            # chunks again, parked since the solo pass
            assert eng.pool._prefix_hits == 3
            assert eng.pool.cached_blocks == 1 + 2 + 1
        assert eng.pool.used_blocks == 0
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# (b) one forward, three callers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", ["", "int8"])
def test_decode_verify_and_propose_are_one_forward(quant):
    """The same rows through ``decode_step``, ``verify_step`` with no
    proposals (k + 1 = 1) and ``propose_step`` at k = 1: the same next
    token, and the same K/V written into the same pages."""
    cfg, params = tiny_lm(5, vocab=64, d_model=32, n_heads=2, n_layers=2,
                          d_ff=64, block_size=8, max_blocks=4, max_batch=4)
    prompts = [[3, 1, 4, 1, 5], list(range(9, 20)), [2] * 8]
    results = []
    for caller in ("decode", "verify", "propose"):
        eng = GenerativeEngine(cfg, params, quant=quant, kv_blocks=16,
                               warm=False, prefix_cache=False, spec_k=0)
        try:
            seqs = []
            for prompt in prompts:
                seq = GenRequest(prompt, 4, None, Future())
                seq.blocks = eng.pool.alloc(
                    eng.pool.blocks_for(len(prompt) + 1))
                seq.out = [eng.prefill(seq)]
                seqs.append(seq)
            rows = ([s.blocks for s in seqs],
                    [s.context_len for s in seqs],
                    [s.out[-1] for s in seqs])
            if caller == "decode":
                toks = eng.decode_step(*rows)
            elif caller == "propose":
                toks = eng.propose_step(*rows, 1)[:, 0]
            else:
                toks = eng.verify_step(
                    seqs, np.zeros((len(seqs), 0), np.int32))[:, 0]
            kp, vp, _ = eng.kv_pages()
            # block 0 is the scratch block padding rows write to
            results.append((toks.tolist(), np.asarray(kp)[:, 1:],
                            np.asarray(vp)[:, 1:]))
        finally:
            eng.close()
    (toks, kp, vp), others = results[0], results[1:]
    assert np.abs(kp).sum() > 0
    for toks_o, kp_o, vp_o in others:
        assert toks_o == toks
        np.testing.assert_array_equal(kp_o, kp)
        np.testing.assert_array_equal(vp_o, vp)


# ---------------------------------------------------------------------------
# (c) one page spec
# ---------------------------------------------------------------------------

class WidePages(LMConfig):
    """LMConfig's model with pages twice as wide as its heads: what
    allocates, installs and ships pages has nowhere else to learn it."""

    def page_spec(self, n_blocks):
        shape, dtype = super().page_spec(n_blocks)
        return shape[:-1] + (2 * shape[-1],), dtype


CFG_KW = dict(vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
              block_size=8, max_blocks=4, max_batch=4)


def test_pool_and_import_blocks_read_the_models_page_spec():
    cfg, params = tiny_lm(3, **CFG_KW)
    wide = WidePages(**cfg.todict())
    eng = GenerativeEngine(wide, params, kv_blocks=12, warm=False,
                           prefix_cache=False, spec_k=0, name="wide")
    try:
        assert eng.page_spec(12) == ((2, 12, 8, 2, 32), np.float32)
        kp, vp, _ = eng.kv_pages()
        assert kp.shape == vp.shape == (2, 12, 8, 2, 32)
        blocks = eng.pool.alloc(3)
        pages = np.random.RandomState(0).randn(2, 3, 8, 2, 32) \
            .astype(np.float32)
        eng.import_blocks(blocks, pages, -pages)
        k_out, v_out, _ = eng.export_blocks(blocks)
        np.testing.assert_array_equal(k_out, pages)
        np.testing.assert_array_equal(v_out, -pages)
        # LMConfig's own page is refused, by the pool's name
        narrow = np.zeros(cfg.page_spec(3)[0], np.float32)
        with pytest.raises(BufferLifetimeError, match="kv_pool:wide"):
            eng.import_blocks(blocks, narrow, narrow)
    finally:
        eng.close()


def test_fleet_handshake_reads_the_models_page_spec():
    """The MigrateKV header is filled from the sender's page spec and
    checked against the receiver's: a worker of the wide-page model
    takes a wide page and refuses LMConfig's, by name, before a block
    is allocated."""
    cfg, params = tiny_lm(3, **CFG_KW)
    wide = WidePages(**cfg.todict())
    tr = LocalTransport()
    dest = FleetWorker("d0", "decode", wide, params, kv_blocks=12,
                       warm=False, transport=tr)
    try:
        free = dest.engine.pool.free_blocks

        def migrate(rid, spec):
            shape, dtype = spec
            head = {"v": 1, "src": "test", "epoch": 0,
                    "req": {"id": rid, "prompt": list(range(1, 10)),
                            "first": 5, "max_new": 1, "eos": None},
                    "kv": _kv_head(spec)}
            body = np.zeros(shape, dtype).tobytes()
            return decode_call(dest.handle(M_MIGRATE, b"".join(
                encode_migrate(head, body, body))))

        assert _kv_head(dest.engine.page_spec(2)) == {
            "n_blocks": 2, "block_size": 8, "n_layers": 2, "n_heads": 2,
            "head_dim": 32, "dtype": "float32"}
        ok = migrate("wide", dest.engine.page_spec(2))
        assert ok["ok"] and len(ok["blocks"]) == 2, ok
        refused = migrate("narrow", cfg.page_spec(2))
        assert not refused["ok"]
        assert refused["kind"] == "ValueError"
        assert "migration geometry" in refused["error"]
        assert "'head_dim': 16" in refused["error"]
        # max_new = 1: the migrated-in request finishes at admission
        assert dest._futures["wide"].result(60)["tokens"] == [5]
        assert dest.engine.pool.free_blocks == free
    finally:
        dest.shutdown()
