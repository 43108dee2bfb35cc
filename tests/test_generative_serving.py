"""Token-level generative serving (ISSUE 11): paged KV cache
accounting, decode-mode paged attention (XLA + interpret-mode Pallas
kernel parity), int8 weight-quantized matmul parity, batcher
token-granularity — a prefill admitted mid-decode produces
bit-identical tokens to the same request run solo — eviction/requeue
under block-pool exhaustion, and the serve_bench generate smoke."""
import functools
import importlib
import json
import os
import subprocess
import sys
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from paddle_tpu.observability import metrics
from paddle_tpu.serving import (BlockPool, GenerativeEngine,
                                InferenceServer, tiny_lm)
from paddle_tpu.serving.batcher import TokenScheduler
from paddle_tpu.serving.engine import StepCache, pow2_bucket
from paddle_tpu.serving.generative import GenRequest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one small config shared across the e2e tests (module-scoped engines
# would share KV pools across tests — fresh engines per test instead,
# sized so each compiles only the buckets it touches)
CFG_KW = dict(vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
              block_size=8, max_blocks=8, max_batch=4)


def _prompts(seed, n, lo=3, hi=15):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 64, size=rng.randint(lo, hi)).tolist()
            for _ in range(n)]


# ---------------------------------------------------------------- unit

def test_block_pool_accounting():
    used0 = metrics.gauge("serve_kv_blocks_used").value
    total0 = metrics.gauge("serve_kv_blocks_total").value
    fails0 = metrics.counter("serve_kv_alloc_failures_total").value
    pool = BlockPool(8, 16)
    assert pool.capacity == 7          # block 0 reserved
    assert metrics.gauge("serve_kv_blocks_total").value == total0 + 7
    a = pool.alloc(3)
    assert len(a) == 3 and 0 not in a
    assert pool.used_blocks == 3
    assert metrics.gauge("serve_kv_blocks_used").value == used0 + 3
    assert pool.alloc(5) is None       # only 4 left
    assert metrics.counter(
        "serve_kv_alloc_failures_total").value == fails0 + 1
    b = pool.alloc(4)
    assert pool.free_blocks == 0
    pool.free(a)
    pool.free(b)
    assert pool.used_blocks == 0
    assert metrics.gauge("serve_kv_blocks_used").value == used0
    with pytest.raises(ValueError):
        pool.free([0])                 # the reserved scratch block
    assert pool.blocks_for(1) == 1
    assert pool.blocks_for(16) == 1
    assert pool.blocks_for(17) == 2
    pool.close()
    assert metrics.gauge("serve_kv_blocks_total").value == total0


def test_lm_config_rejects_degenerate_block_size():
    from paddle_tpu.core.flags import FLAGS
    from paddle_tpu.serving import LMConfig

    for bad in (-8, 12):
        with pytest.raises(ValueError, match="power of"):
            LMConfig(64, 32, 2, 2, 64, block_size=bad)
    # block_size=0/None falls back to the flag; a degenerate FLAG
    # value must fail HERE with the named error, not as a
    # ZeroDivisionError deep inside the first generate
    prev = FLAGS.serve_kv_block_size
    FLAGS.serve_kv_block_size = 0
    try:
        with pytest.raises(ValueError, match="power of"):
            LMConfig(64, 32, 2, 2, 64)
    finally:
        FLAGS.serve_kv_block_size = prev


def test_pow2_bucket():
    assert pow2_bucket(1, 16) == 1
    assert pow2_bucket(3, 16) == 4
    assert pow2_bucket(16, 16) == 16
    assert pow2_bucket(17, 12) == 12   # cap joins the ladder


def test_step_cache_covering_and_sync_compile():
    compiled = []

    def build(key):
        compiled.append(key)
        return ("exe",) + key

    cache = StepCache(build, name="t")
    cache.warm([(2, 8), (4, 8)])
    assert cache.warm_keys == [(2, 8), (4, 8)]
    # exact hit
    key, exe = cache.pick((2, 8))
    assert key == (2, 8) and exe == ("exe", 2, 8)
    # covered miss: smallest covering answers, ideal compiles in bg
    key, exe = cache.pick((2, 4))
    assert key == (2, 8)
    deadline = time.time() + 30
    while (2, 4) not in cache.warm_keys and time.time() < deadline:
        time.sleep(0.01)
    assert (2, 4) in cache.warm_keys
    # nothing covers: synchronous compile
    key, exe = cache.pick((8, 8))
    assert key == (8, 8) and (8, 8) in cache.warm_keys
    cache.drain()


# ------------------------------------------------ paged attention

def _paged_ref(q, kp, vp, tables, lens):
    """Dense per-sequence reference: gather contiguous K/V, plain
    softmax attention over the first ``lens[b]`` positions."""
    B, H, D = q.shape
    outs = []
    for b in range(B):
        L = int(lens[b])
        kc = kp[tables[b]].reshape(-1, H, D)[:L]
        vc = vp[tables[b]].reshape(-1, H, D)[:L]
        s = np.einsum("hd,shd->hs", q[b], kc) / np.sqrt(D)
        p = np.exp(s - s.max(axis=1, keepdims=True))
        p /= p.sum(axis=1, keepdims=True)
        outs.append(np.einsum("hs,shd->hd", p, vc))
    return np.stack(outs)


def _paged_case(seed=0):
    rng = np.random.RandomState(seed)
    B, H, D, bs, NB, N = 3, 2, 16, 8, 4, 32
    q = rng.randn(B, H, D).astype(np.float32)
    kp = rng.randn(N, bs, H, D).astype(np.float32)
    vp = rng.randn(N, bs, H, D).astype(np.float32)
    tables = np.array([[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]],
                      np.int32)
    lens = np.array([5, 17, 32], np.int32)
    return q, kp, vp, tables, lens


def test_paged_attention_xla_parity():
    from paddle_tpu.kernels.flash_attention import paged_attention

    q, kp, vp, tables, lens = _paged_case()
    out = np.asarray(paged_attention(q, kp, vp, tables, lens,
                                     force_xla=True))
    ref = _paged_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_paged_attention_kernel_interpret_parity():
    """The Pallas scalar-prefetch kernel (the TPU path) must answer the
    XLA gather path's floats — interpret mode runs the same kernel
    body the TPU compiles."""
    from paddle_tpu.kernels.flash_attention import paged_attention

    q, kp, vp, tables, lens = _paged_case(seed=4)
    ref = np.asarray(paged_attention(q, kp, vp, tables, lens,
                                     force_xla=True))
    out = np.asarray(paged_attention(q, kp, vp, tables, lens,
                                     interpret=True))
    np.testing.assert_allclose(out, ref, atol=1e-5)


@pytest.mark.parametrize("layer", [0, 2, 4])
@pytest.mark.parametrize("path", ["xla", "interpret"])
@pytest.mark.parametrize("seed", [0, 4])
def test_paged_attention_whole_pool_equals_layer_slice(seed, path, layer):
    """The engine hands the kernel every layer's pool and a static
    ``layer`` (a ``pool[l]`` in front of a Mosaic call is a copy of the
    layer's whole pool): same floats, bit for bit, as the one-layer
    call on ``pool[l]``."""
    from paddle_tpu.kernels.flash_attention import paged_attention

    q, one_layer, _, tables, lens = _paged_case(seed)
    rng = np.random.RandomState(100 + seed)
    kp = rng.randn(5, *one_layer.shape).astype(np.float32)
    vp = rng.randn(5, *one_layer.shape).astype(np.float32)
    kw = {"force_xla": True} if path == "xla" else {"interpret": True}
    whole = np.asarray(paged_attention(q, kp, vp, tables, lens,
                                       layer=layer, **kw))
    sliced = np.asarray(paged_attention(q, kp[layer], vp[layer], tables,
                                        lens, **kw))
    np.testing.assert_array_equal(whole, sliced)
    np.testing.assert_allclose(
        whole, _paged_ref(q, kp[layer], vp[layer], tables, lens),
        atol=1e-5)


def test_paged_attention_layer_needs_the_whole_pool():
    from paddle_tpu.kernels.flash_attention import paged_attention

    q, kp, vp, tables, lens = _paged_case()
    with pytest.raises(AssertionError):
        paged_attention(q, kp, vp, tables, lens, layer=1, force_xla=True)
    with pytest.raises(AssertionError):
        paged_attention(q, kp[None], vp[None], tables, lens, layer=1,
                        force_xla=True)


# the kernel walks a row's live pages a chunk at a time; a chunk of the
# tiny pages below is _PAGED_MAX_PAGES pages
PG_BS, PG_H, PG_D = 8, 2, 16


def _chunk_tokens():
    # (paddle_tpu.kernels.flash_attention, the attribute, is a function)
    mod = importlib.import_module("paddle_tpu.kernels.flash_attention")
    return mod._PAGED_MAX_PAGES * PG_BS


def _pool_case(lens, nb, seed=0, n_l=None, n=None):
    """Rows of ``lens`` tokens over a pool of [bs 8, H 2, D 16] pages:
    each row's live pages are its own, drawn anywhere in the pool; every
    other slot of its table names a page no row holds.  Returns the pool
    as made, and the same pool with NaN wherever the tables name no live
    position: block 0, the pages nobody holds, the pool's tail, and the
    last live page's positions past the row's length."""
    rng = np.random.RandomState(seed)
    B = len(lens)
    held = [-(-int(x) // PG_BS) for x in lens]
    n = n or sum(held) + 9
    lead = () if n_l is None else (n_l,)
    q = rng.randn(B, PG_H, PG_D).astype(np.float32)
    kp = rng.randn(*lead, n, PG_BS, PG_H, PG_D).astype(np.float32)
    vp = rng.randn(*lead, n, PG_BS, PG_H, PG_D).astype(np.float32)
    ids = rng.permutation(np.arange(1, n - 4))
    dead = np.array([0, n - 1, n - 2, n - 3, n - 4], np.int32)
    tables = dead[rng.randint(0, len(dead), size=(B, nb))].astype(np.int32)
    live = np.zeros((n, PG_BS), bool)
    at = 0
    for i in range(B):
        tables[i, :held[i]] = ids[at:at + held[i]]
        at += held[i]
        flat = np.arange(held[i] * PG_BS) < int(lens[i])
        live[tables[i, :held[i]]] = flat.reshape(held[i], PG_BS)
    mask = live[..., None, None]
    return (q, kp, vp, tables, np.asarray(lens, np.int32),
            np.where(mask, kp, np.nan).astype(np.float32),
            np.where(mask, vp, np.nan).astype(np.float32))


def _paged_both(q, kp, vp, tables, lens, kp_nan, vp_nan, layer=None):
    """(XLA path on the pool as made, kernel on the poisoned pool)."""
    from paddle_tpu.kernels.flash_attention import paged_attention

    ref = np.asarray(paged_attention(q, kp, vp, tables, lens, layer=layer,
                                     force_xla=True))
    out = np.asarray(paged_attention(q, kp_nan, vp_nan, tables, lens,
                                     layer=layer, interpret=True))
    return ref, out


@pytest.mark.parametrize("length", [
    "1", "bs-1", "bs", "bs+1", "chunk-1", "chunk", "chunk+1", "nb*bs"])
def test_paged_kernel_reads_only_live_positions(length):
    """At every edge of a page and of a chunk the kernel, on a pool
    that is NaN wherever no live position lies, answers the XLA path's
    floats on the pool as made: a dead page never reaches the result."""
    nb, chunk = 24, _chunk_tokens()
    n = {"1": 1, "bs-1": PG_BS - 1, "bs": PG_BS, "bs+1": PG_BS + 1,
         "chunk-1": chunk - 1, "chunk": chunk, "chunk+1": chunk + 1,
         "nb*bs": nb * PG_BS}[length]
    ref, out = _paged_both(*_pool_case([n, 37, n], nb, seed=n))
    np.testing.assert_allclose(out, ref, atol=1e-5)


@pytest.mark.parametrize("n_l,layer", [(None, None), (5, 0), (5, 2),
                                       (5, 4)])
def test_paged_kernel_poisoned_pool_every_pool_form(n_l, layer):
    chunk = _chunk_tokens()
    lens = [1, 2 * chunk + 3, chunk, 5, chunk + 1, 3 * chunk]
    ref, out = _paged_both(*_pool_case(lens, 24, seed=7, n_l=n_l),
                           layer=layer)
    np.testing.assert_allclose(out, ref, atol=1e-5)


@pytest.mark.parametrize("pad_len", [0, 1])
def test_paged_kernel_padding_row_is_finite(pad_len):
    """decode_step pads a bucket with rows of length 0 (1 once the
    step's own token counts), every table slot block 0: no page, or one,
    must give a finite row and leave its neighbours' floats alone."""
    from paddle_tpu.kernels.flash_attention import paged_attention

    chunk = _chunk_tokens()
    lens = [pad_len, chunk + 5, pad_len, pad_len, 11, pad_len]
    q, kp, vp, tables, lens, kp_nan, vp_nan = _pool_case(lens, 16, seed=3)
    pads = lens == pad_len
    tables[pads] = 0
    if pad_len:     # block 0 is the scratch block: written, never NaN
        kp_nan[0], vp_nan[0] = kp[0], vp[0]
    out = np.asarray(paged_attention(q, kp_nan, vp_nan, tables, lens,
                                     interpret=True))
    assert np.isfinite(out).all()
    ref = np.asarray(paged_attention(q, kp, vp, tables, lens,
                                     force_xla=True))
    rows = ~pads if pad_len == 0 else np.ones_like(pads)
    np.testing.assert_allclose(out[rows], ref[rows], atol=1e-5)


@functools.lru_cache(maxsize=None)
def _invariance_row(b, nb, seed):
    """One fixed row (3 chunks less a few tokens) at a seeded place
    among ``b`` rows of seeded lengths, its table ``nb`` wide."""
    from paddle_tpu.kernels.flash_attention import paged_attention

    rng = np.random.RandomState(seed)
    length = 3 * _chunk_tokens() - 19
    lens = rng.randint(1, 32 * PG_BS, size=b)
    at = rng.randint(b)
    lens[at] = length
    q, kp, vp, tables, lens, _, _ = _pool_case(lens, nb, seed=seed, n=600)
    # the same row everywhere: the query, and the pages it holds
    fixed = np.random.RandomState(99)
    q[at] = fixed.randn(PG_H, PG_D)
    held = -(-length // PG_BS)
    pages = fixed.randn(2, held, PG_BS, PG_H, PG_D).astype(np.float32)
    kp[tables[at, :held]], vp[tables[at, :held]] = pages
    return np.asarray(paged_attention(q, kp, vp, tables, lens,
                                      interpret=True))[at]


@pytest.mark.parametrize("nb", [32, 64, 128])
@pytest.mark.parametrize("b", [1, 8, 16])
def test_paged_kernel_row_does_not_depend_on_its_bucket(b, nb):
    """Solo == batched == repeat rests on this: a row's floats are the
    same bit for bit alone or among 8 or 16 rows, under a table 32, 64
    or 128 wide, whatever its neighbours hold and wherever it sits."""
    np.testing.assert_array_equal(_invariance_row(b, nb, seed=b + nb),
                                  _invariance_row(1, 32, seed=0))


# ------------------------------------------------ int8 matmul

def test_quantize_weight_roundtrip_bound():
    from paddle_tpu.kernels.matmul_fused import (dequantize_weight,
                                                 quantize_weight)

    rng = np.random.RandomState(2)
    w = (rng.randn(128, 64) * 0.1).astype(np.float32)
    q, s, chunk = quantize_weight(w, chunk=32)
    assert q.dtype == np.int8 and s.shape == (128 // 32, 64)
    wd = np.asarray(dequantize_weight(q, s, chunk))
    # per-chunk symmetric: error bounded by half a quantization step
    for c in range(128 // 32):
        seg = slice(c * 32, (c + 1) * 32)
        bound = s[c] * 0.5 + 1e-7
        assert (np.abs(wd[seg] - w[seg]) <= bound[None, :]).all()


def test_matmul_int8_kernel_matches_xla():
    from paddle_tpu.kernels.matmul_fused import (dequantize_weight,
                                                 matmul_epilogue_reference,
                                                 matmul_int8_dequant,
                                                 quantize_weight)

    rng = np.random.RandomState(3)
    x = rng.randn(8, 256).astype(np.float32)
    w = (rng.randn(256, 128) * 0.1).astype(np.float32)
    bias = rng.randn(128).astype(np.float32)
    q, s, chunk = quantize_weight(w, chunk=128)
    xla = np.asarray(matmul_int8_dequant(x, q, s, chunk, bias=bias,
                                         act="gelu", force_xla=True))
    kern = np.asarray(matmul_int8_dequant(x, q, s, chunk, bias=bias,
                                          act="gelu", interpret=True))
    np.testing.assert_allclose(kern, xla, atol=1e-5)
    # and both equal the reference over the dequantized weights
    ref, _ = matmul_epilogue_reference(
        x, np.asarray(dequantize_weight(q, s, chunk)), bias, None,
        "gelu")
    np.testing.assert_allclose(xla, np.asarray(ref), atol=1e-5)


# ------------------------------------------------ generate e2e

def test_generate_e2e_and_kv_drain():
    cfg, params = tiny_lm(7, **CFG_KW)
    with InferenceServer() as srv:
        eng = srv.load_generative("g", cfg, params, kv_blocks=32,
                                  warm=False)
        futs = [srv.generate("g", p, max_new_tokens=6)
                for p in _prompts(1, 5)]
        for f in futs:
            res = f.result(180)
            assert len(res["tokens"]) == 6
            assert res["ttft_ms"] is not None
            assert len(res["itl_ms"]) == 5
            assert all(0 <= t < cfg.vocab for t in res["tokens"])
        # every finished sequence returned its blocks
        assert eng.pool.used_blocks == 0


def test_generate_eos_stops_early():
    cfg, params = tiny_lm(7, **CFG_KW)
    with InferenceServer() as srv:
        srv.load_generative("g", cfg, params, kv_blocks=32, warm=False)
        ref = srv.generate("g", [1, 2, 3],
                           max_new_tokens=12).result(180)["tokens"]
        assert len(ref) == 12
        eos = ref[4]
        res = srv.generate("g", [1, 2, 3], max_new_tokens=12,
                           eos_id=eos).result(180)["tokens"]
        assert res == ref[:ref.index(eos) + 1], (res, ref)


def test_generate_validation():
    cfg, params = tiny_lm(7, **CFG_KW)
    with InferenceServer() as srv:
        srv.load_generative("g", cfg, params, kv_blocks=32, warm=False)
        with pytest.raises(ValueError):
            srv.generate("g", [], max_new_tokens=4)
        with pytest.raises(ValueError):
            srv.generate("g", [999], max_new_tokens=4)   # out of vocab
        with pytest.raises(ValueError):
            srv.generate("g", [1], max_new_tokens=0)
        # in-vocab tokens, so the LENGTH check itself must fire (an
        # out-of-vocab token here would mask a missing length guard)
        with pytest.raises(ValueError, match="max_seq"):
            srv.generate("g", [1] * 130, max_new_tokens=4)
        with pytest.raises(TypeError):
            srv.predict("g", {"x": np.zeros((1, 4), np.float32)})
        with pytest.raises(TypeError):
            srv.swap("g", "/nonexistent")   # predict-tier op
        with pytest.raises(KeyError):
            srv.generate("ghost", [1], max_new_tokens=1)


def test_predict_tenant_rejects_generate(tmp_path):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.scope import Scope

    d = str(tmp_path / "m")
    main, startup = fluid.Program(), fluid.Program()
    scope = Scope()
    with fluid.scope_guard(scope):
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data(name="x", shape=[4],
                                      dtype="float32")
                out = fluid.layers.fc(x, size=3)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(d, ["x"], [out], exe,
                                      main_program=main)
    with InferenceServer(max_batch=2) as srv:
        srv.load("m", d)
        with pytest.raises(TypeError, match="generate"):
            srv.generate("m", [1, 2], max_new_tokens=2)


# ------------------------------------- token-granularity determinism

def test_prefill_admitted_mid_decode_bit_identical():
    """THE batcher token-granularity contract (ISSUE 11 satellite): a
    request admitted into a RUNNING decode batch must produce tokens
    bit-identical to the same request run solo — greedy decode is
    deterministic regardless of which (batch, block-count) buckets its
    iterations landed on or which neighbours shared them."""
    cfg, params = tiny_lm(11, **CFG_KW)
    prompts = _prompts(3, 4)
    with InferenceServer() as srv:
        srv.load_generative("g", cfg, params, kv_blocks=64, warm=False)
        solo = [srv.generate("g", p, max_new_tokens=16).result(180)
                ["tokens"] for p in prompts]
    metrics.zero_all()
    with InferenceServer() as srv:
        srv.load_generative("g", cfg, params, kv_blocks=64, warm=False)
        futs = []
        for p in prompts:
            futs.append(srv.generate("g", p, max_new_tokens=16))
            time.sleep(0.02)       # stagger: admission lands mid-decode
        batched = [f.result(180)["tokens"] for f in futs]
    # the runs genuinely overlapped: some decode iterations carried
    # more than one sequence
    rows = metrics.counter("serve_decode_rows_total").value
    steps = metrics.counter("serve_decode_steps_total").value
    assert rows > steps, "sequences never overlapped — test is vacuous"
    for i, (s, b) in enumerate(zip(solo, batched)):
        assert s == b, "request %d diverged: solo %r vs batched %r" % (
            i, s, b)


def test_pool_exhaustion_preempts_and_requeues():
    """Eviction/requeue (ISSUE 11 satellite): with a pool too small for
    all sequences, the scheduler preempts the youngest (counted),
    requeues it at the front, and the evicted request still completes
    with its solo tokens (greedy recompute determinism)."""
    cfg, params = tiny_lm(11, **CFG_KW)
    prompts = _prompts(9, 3, lo=6, hi=12)
    with InferenceServer() as srv:
        srv.load_generative("g", cfg, params, kv_blocks=64, warm=False)
        solo = [srv.generate("g", p, max_new_tokens=20).result(180)
                ["tokens"] for p in prompts]
    metrics.zero_all()
    with InferenceServer() as srv:
        # 7 usable blocks: 3 growing sequences (prompt 6-12 + 20 new
        # tokens -> up to 4 blocks each) cannot all fit
        srv.load_generative("g", cfg, params, kv_blocks=8, warm=False)
        futs = [srv.generate("g", p, max_new_tokens=20)
                for p in prompts]
        res = [f.result(300) for f in futs]
    preempts = metrics.counter("serve_kv_preemptions_total").value
    fails = metrics.counter("serve_kv_alloc_failures_total").value
    assert preempts > 0, "pool was never exhausted — test is vacuous"
    assert fails > 0
    assert any(r["preempted"] for r in res)
    for i, (s, r) in enumerate(zip(solo, res)):
        assert s == r["tokens"], "request %d diverged after preemption" % i


def test_lone_sequence_too_big_for_pool_fails_cleanly():
    cfg, params = tiny_lm(11, **CFG_KW)
    with InferenceServer() as srv:
        # 2 usable blocks = 16 positions; prompt 10 + 16 new > 16
        srv.load_generative("g", cfg, params, kv_blocks=3, warm=False)
        fut = srv.generate("g", list(range(10)), max_new_tokens=16)
        with pytest.raises(RuntimeError, match="pool too small"):
            fut.result(180)


def test_engine_ctor_failure_retires_pool_gauges():
    """A GenerativeEngine that fails mid-construction (bad params, a
    warm-compile error) must retire its just-registered BlockPool from
    the process gauges — review finding: every failed load left
    phantom serve_kv_blocks capacity behind."""
    total0 = metrics.gauge("serve_kv_blocks_total").value
    cfg, params = tiny_lm(7, **CFG_KW)
    bad = dict(params)
    del bad["lm_head"]
    with pytest.raises(KeyError):
        GenerativeEngine(cfg, bad, kv_blocks=16, warm=True)
    assert metrics.gauge("serve_kv_blocks_total").value == total0


def test_prompt_wider_than_whole_pool_rejected_at_generate():
    """A prompt that can NEVER be admitted (needs more blocks than the
    pool holds) must be rejected synchronously at generate() — left in
    the queue it would spin the decode loop forever AND, since
    admission is FIFO, block every request behind it."""
    cfg, params = tiny_lm(11, **CFG_KW)
    with InferenceServer() as srv:
        srv.load_generative("g", cfg, params, kv_blocks=3, warm=False)
        with pytest.raises(ValueError, match="KV blocks"):
            srv.generate("g", [1] * 20, max_new_tokens=2)  # needs 3 > 2


def test_prefill_failure_fails_only_that_request():
    """A prefill that raises during admission must fail THAT request's
    future, return its just-allocated blocks to the pool, and leave
    the loop serving later traffic (review finding: the blocks leaked
    and the future hung)."""
    cfg, params = tiny_lm(11, **CFG_KW)
    with InferenceServer() as srv:
        eng = srv.load_generative("g", cfg, params, kv_blocks=32,
                                  warm=False)
        orig = eng.prefill

        def bomb(seq, _tr=None):
            raise RuntimeError("synthetic prefill fault")

        eng.prefill = bomb
        fut = srv.generate("g", [1, 2, 3], max_new_tokens=4)
        with pytest.raises(RuntimeError, match="synthetic"):
            fut.result(60)
        eng.prefill = orig
        assert eng.pool.used_blocks == 0, "admission blocks leaked"
        res = srv.generate("g", [1, 2, 3], max_new_tokens=4).result(180)
        assert len(res["tokens"]) == 4


# ------------------------------------------------ speculative decoding

def _spec_pair(seed=13):
    """Target + 1-layer draft sharing vocab/paging geometry (the
    draft-contract _init_draft enforces)."""
    cfg, params = tiny_lm(seed, **CFG_KW)
    dcfg, dparams = tiny_lm(seed + 1, **dict(CFG_KW, n_layers=1))
    return cfg, params, dcfg, dparams


def test_spec_accept_rate_accounting():
    """The serve_spec_* counters must add up against the emission
    contract: per (round, sequence) the engine proposes k, accepts
    m <= k, emits m+1 — so proposed == k * verify-rows, accepted stays
    within proposed, and delivered tokens land between the exact
    emission sum and that sum minus the worst-case final-round
    overshoot trim (k per request)."""
    k = 3
    cfg, params, dcfg, dparams = _spec_pair()
    prompts = _prompts(21, 3, lo=4, hi=10)
    metrics.zero_all()
    with InferenceServer() as srv:
        srv.load_generative("g", cfg, params, kv_blocks=64, warm=False,
                            spec_k=k, draft=(dcfg, dparams))
        res = [srv.generate("g", p, max_new_tokens=12).result(300)
               for p in prompts]
    rounds = metrics.counter("serve_spec_rounds_total").value
    proposed = metrics.counter("serve_spec_proposed_total").value
    accepted = metrics.counter("serve_spec_accepted_total").value
    rows = metrics.counter("serve_decode_rows_total").value
    prefills = metrics.counter("serve_prefills_total").value
    assert rounds > 0, "spec engine never ran a speculative round"
    assert proposed == k * rows
    assert 0 <= accepted <= proposed
    delivered = sum(len(r["tokens"]) for r in res)
    emitted = prefills + accepted + rows      # 1 + sum(m_i + 1)
    assert delivered <= emitted <= delivered + k * len(prompts)
    # draft/verify wall-time observability rides the same gate
    assert metrics.counter("serve_spec_verify_us_total").value > 0


def test_spec_k0_degenerate_equals_plain():
    """spec_k=0 IS plain decode: identical tokens, no draft engine,
    and the serve_spec_* counters never move."""
    cfg, params, _, _ = _spec_pair()
    prompts = _prompts(23, 2, lo=4, hi=9)
    with InferenceServer() as srv:
        srv.load_generative("g", cfg, params, kv_blocks=64, warm=False)
        base = [srv.generate("g", p, max_new_tokens=10).result(300)
                ["tokens"] for p in prompts]
    metrics.zero_all()
    with InferenceServer() as srv:
        eng = srv.load_generative("g", cfg, params, kv_blocks=64,
                                  warm=False, spec_k=0)
        assert eng.draft is None
        k0 = [srv.generate("g", p, max_new_tokens=10).result(300)
              ["tokens"] for p in prompts]
    assert k0 == base
    assert metrics.counter("serve_spec_rounds_total").value == 0
    assert metrics.counter("serve_spec_proposed_total").value == 0


def test_spec_certified_greedy_parity():
    """THE spec-decode correctness contract on the bench LM: the
    speculative token stream is bit-identical to plain greedy decode
    and the per-round acceptance accounting closes exactly —
    serve_bench documents the same certificate in SERVE_BENCH.json."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import serve_bench
    finally:
        sys.path.pop(0)
    rec = serve_bench._gen_spec_parity(steps=24, k=3, fat=512)
    assert rec["identical"], rec
    assert rec["accounting_ok"], rec
    assert rec["certified"], rec
    assert rec["rounds"] > 0
    assert 0.0 <= rec["accept_rate"] <= 1.0


def test_spec_draft_target_bucket_ladder_coexistence():
    """Draft and target run separate StepCache ladders (propose/verify
    vs decode) inside one engine: staggered admissions through the
    spec engine must stay bit-identical to plain solo decode, with
    both ladders demonstrably compiled-through."""
    cfg, params, dcfg, dparams = _spec_pair()
    prompts = _prompts(29, 3, lo=4, hi=10)
    with InferenceServer() as srv:
        srv.load_generative("g", cfg, params, kv_blocks=64, warm=False)
        solo = [srv.generate("g", p, max_new_tokens=14).result(300)
                ["tokens"] for p in prompts]
    metrics.zero_all()
    with InferenceServer() as srv:
        eng = srv.load_generative("g", cfg, params, kv_blocks=64,
                                  warm=False, spec_k=3,
                                  draft=(dcfg, dparams))
        futs = []
        for p in prompts:
            futs.append(srv.generate("g", p, max_new_tokens=14))
            time.sleep(0.02)   # stagger: admissions land mid-round
        batched = [f.result(300)["tokens"] for f in futs]
        assert eng._verify.warm_keys, "target verify ladder never used"
        assert eng.draft._propose.warm_keys, \
            "draft propose ladder never used"
    for i, (s, b) in enumerate(zip(solo, batched)):
        assert s == b, "request %d diverged under spec decode: " \
            "solo %r vs spec %r" % (i, s, b)


# ------------------------------------------------ int8 serving parity

def test_int8_decode_greedy_parity():
    """int8 weight-quantized decode must be token-exact with fp32 on
    the bench model over 64 greedy steps, with the margin certificate:
    every step's fp32 top-2 logit margin exceeds the worst observed
    logit delta (serve_bench documents the same numbers in
    SERVE_BENCH.json)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import serve_bench
    finally:
        sys.path.pop(0)
    rec = serve_bench._gen_int8_parity(max_batch=4, kv_blocks=32,
                                       steps=64)
    assert rec["parity_ok"], rec
    assert rec["certified"], rec
    assert rec["min_top2_margin"] > rec["max_logit_delta"]


# ------------------------------------------------------------ bench

def test_serve_bench_quick_generate_smoke():
    """tools/serve_bench.py --quick --mode generate completes on the
    CPU backend and reports the generate artifact schema — tier-1
    catches a wedged decode loop, not just schema drift (ISSUE 11
    satellite; the predict smoke lives in test_serving.py)."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", SVB_MAX_BATCH="4",
               SVB_GEN_KV_BLOCKS="64", SVB_GEN_MAX_NEW="8",
               SVB_GEN_PARITY_STEPS="16")   # the full 64-step parity
    # guarantee lives in test_int8_decode_greedy_parity (in-process)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_bench.py"),
         "--quick", "--mode", "generate", "--seconds", "0.8"],
        capture_output=True, text=True, timeout=560, env=env, cwd=REPO)
    assert proc.returncode in (0, 1), proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "serve_bench"
    assert rec["mode"] == "generate"
    gen = rec["generate"]
    for key in ("floor", "poisson", "occupancy", "kv", "int8",
                "load_warm_s", "speedup_tokens_vs_floor"):
        assert key in gen, key
    assert gen["poisson"]["completed"] == gen["poisson"]["n_requests"]
    assert gen["poisson"]["tokens"] > 0
    assert gen["drop"]["zero_dropped"] is True
    # the hard guarantee holds even in the smoke: int8 decode is
    # token-exact with fp32 over the smoke's parity horizon
    assert gen["int8"]["parity_ok"] is True
    assert gen["kv"]["blocks_used_after_drain"] == 0


@pytest.mark.parametrize("feature_env,check", [
    ({"SVB_GEN_PREFIX_CACHE": "1"}, "prefix"),
    ({"SVB_GEN_SPEC_K": "2"}, "spec"),
])
def test_serve_bench_quick_generate_feature_smoke(feature_env, check):
    """The generate smoke parametrized over the ISSUE 19 features: the
    SAME Poisson trace with the prefix cache on / a draft speculating
    must complete with zero drops, a drained pool, AND the feature
    demonstrably engaged (hits > 0 / rounds > 0 in the artifact's
    features block) — not just schema presence."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", SVB_MAX_BATCH="4",
               SVB_GEN_KV_BLOCKS="64", SVB_GEN_MAX_NEW="8",
               SVB_GEN_PARITY_STEPS="16")
    env.update(feature_env)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_bench.py"),
         "--quick", "--mode", "generate", "--seconds", "0.8"],
        capture_output=True, text=True, timeout=560, env=env, cwd=REPO)
    assert proc.returncode in (0, 1), proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    gen = rec["generate"]
    feats = gen["features"]
    if check == "prefix":
        assert feats["prefix_cache"] is True
        assert feats["prefix_hits"] > 0, feats
        assert feats["prefix_tokens_cached"] > 0, feats
    else:
        assert feats["spec_k"] == 2
        assert feats["spec_rounds"] > 0, feats
        assert 0.0 <= feats["spec_accept_rate"] <= 1.0
    assert gen["poisson"]["completed"] == gen["poisson"]["n_requests"]
    assert gen["drop"]["zero_dropped"] is True
    assert gen["kv"]["blocks_used_after_drain"] == 0
