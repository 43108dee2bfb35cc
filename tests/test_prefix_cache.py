"""Copy-on-write prefix KV reuse (ISSUE 19 tentpole a): the radix
prefix index over the refcounted BlockPool — hit/miss/partial-block
boundary lookups, COW write isolation, refcount-ordered LRU eviction,
bit-identical tokens with the cache on vs off, and the interplay with
pool-exhaustion preemption.  The refcount/double-free sanitizer cases
and the lifetime checker's shared-block rule ride along."""
import numpy as np
import pytest

from paddle_tpu.core import sanitizer as san
from paddle_tpu.core.flags import FLAGS
from paddle_tpu.observability import metrics
from paddle_tpu.serving import (BlockPool, GenerativeEngine,
                                InferenceServer, tiny_lm)

CFG_KW = dict(vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
              block_size=8, max_blocks=8, max_batch=4)


class _Req:
    """The two attributes PrefixCache.acquire contracts on."""

    def __init__(self, prompt):
        self.prompt = list(prompt)
        self.blocks = None
        self.cached_len = 0


def _engine(**kw):
    cfg, params = tiny_lm(7, **CFG_KW)
    kw.setdefault("kv_blocks", 32)
    kw.setdefault("warm", False)
    return GenerativeEngine(cfg, params, prefix_cache=True, **kw)


def _prompts(seed, n, lo=3, hi=15):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 64, size=rng.randint(lo, hi)).tolist()
            for _ in range(n)]


# ------------------------------------------------------- radix index

def test_radix_hit_miss_partial_boundary():
    """Lookup semantics at block granularity: a cold prompt misses; a
    re-walked prompt hits its full chunks; the final prompt token is
    NEVER served from cache (the suffix prefill must compute
    something); a divergent-suffix prompt gets the shared full chunks
    plus a COW tail capped at the divergence point."""
    eng = _engine()
    try:
        idx = eng.prefix_cache
        bs = eng.config.block_size
        prompt = list(np.random.RandomState(0).randint(0, 64, 20))

        # cold: miss
        assert idx.probe(prompt) == (0, 0)
        a = _Req(prompt)
        assert idx.acquire(a) and a.cached_len == 0
        assert len(a.blocks) == eng.pool.blocks_for(20)
        idx.insert(a)
        assert idx.nodes == 2          # 20 // 8 full chunks

        # exact re-walk: both full chunks hit, positions 16..19 do not
        assert idx.probe(prompt) == (2, 16)
        # a prompt that IS exactly the indexed chunks: the full-chunk
        # walk stops a chunk early (position n-1 stays un-cached by
        # contract) and the final chunk downgrades to a COW tail of
        # bs-1 tokens
        assert idx.probe(prompt[:2 * bs]) == (2, 2 * bs - 1)
        # unrelated prompt: miss
        assert idx.probe([63] * 20) == (0, 0)

        # partial tail: shares chunk 0 whole, diverges inside chunk 1
        b_prompt = prompt[:12] + [(prompt[12] + 1) % 64]
        shared_n, cached = idx.probe(b_prompt)
        assert shared_n == 2 and cached == 12   # 8 full + 4 COW tail

        b = _Req(b_prompt)
        cow0 = metrics.counter("serve_kv_cow_copies_total").value
        assert idx.acquire(b) and b.cached_len == 12
        assert metrics.counter(
            "serve_kv_cow_copies_total").value == cow0 + 1
        # the shared full chunk is the SAME block; the COW tail is a
        # private copy, not A's chunk-1 block
        assert b.blocks[0] == a.blocks[0]
        assert b.blocks[1] != a.blocks[1]
        assert eng.pool.ref(a.blocks[0]) == 2
        assert eng.pool.ref(b.blocks[1]) == 1
        eng.pool.free(a.blocks)
        eng.pool.free(b.blocks)
    finally:
        eng.close()


def test_cow_write_isolation():
    """The COW copy carries the shared prefix's device pages: after
    the copy the two sequences' K/V diverge without either seeing the
    other's writes — checked at page level via export_blocks."""
    eng = _engine()
    try:
        idx = eng.prefix_cache
        prompt = list(range(16))
        a = _Req(prompt)
        assert idx.acquire(a)
        # write recognizable K/V into A's pages via a real prefill
        eng.prefill_tokens(a.prompt, a.blocks)
        idx.insert(a)

        b = _Req(prompt[:12] + [63])
        assert idx.acquire(b)
        assert b.blocks[1] != a.blocks[1]
        # COW copied A's chunk-1 pages into B's private block...
        k_a, v_a, _ = eng.export_blocks([a.blocks[1]])
        k_b, v_b, _ = eng.export_blocks([b.blocks[1]])
        np.testing.assert_array_equal(k_a, k_b)
        np.testing.assert_array_equal(v_a, v_b)
        # ...and a write into B's block leaves A's pages untouched
        before = eng.export_blocks([a.blocks[1]])[0]
        eng.prefill_tokens(b.prompt, b.blocks, start=12)
        after = eng.export_blocks([a.blocks[1]])[0]
        np.testing.assert_array_equal(before, after)
        eng.pool.free(a.blocks)
        eng.pool.free(b.blocks)
    finally:
        eng.close()


# ------------------------------------------------- refcount eviction

def test_refcount_eviction_order():
    """Released cacheable blocks PARK in the LRU (used -> cached, not
    freed); allocation pressure reclaims oldest-parked first, and a
    revived (shared) block re-parks at the recent end."""
    evicted = []
    pool = BlockPool(6, 8)             # 5 usable
    pool.set_evict_callback(lambda b: evicted.append(b) or ())
    try:
        a = pool.alloc(3)
        pool.set_cacheable(a)
        pool.free(a)                   # park a0, a1, a2 (oldest first)
        assert pool.used_blocks == 0 and pool.cached_blocks == 3
        assert metrics.gauge("serve_kv_blocks_cached").value >= 3

        # revive the oldest, re-park it: now a1 is LRU-oldest
        assert pool.share([a[0]])
        assert pool.ref(a[0]) == 1
        pool.free([a[0]])
        assert pool.cached_blocks == 3

        # 2 free blocks remain; asking for 4 reclaims 2 parked, LRU
        # order: a1 then a2, never the recently-parked a0
        got = pool.alloc(4)
        assert got is not None
        assert evicted == [a[1], a[2]]
        assert pool.cached_blocks == 1
        pool.free(got)
    finally:
        pool.close()


def test_shared_block_counts_once_and_decref_is_not_free():
    pool = BlockPool(6, 8)
    try:
        used0 = pool.used_blocks
        blk = pool.alloc(1)
        assert pool.share(blk) and pool.ref(blk[0]) == 2
        # refcount semantics: shared counts once in used
        assert pool.used_blocks == used0 + 1
        assert metrics.gauge("serve_kv_blocks_shared").value >= 1
        pool.free(blk)                 # decref to 1: NOT a free
        assert pool.used_blocks == used0 + 1
        assert pool.ref(blk[0]) == 1
        pool.free(blk)                 # terminal decref
        assert pool.used_blocks == used0
    finally:
        pool.close()


def test_double_free_trips_buffers_sanitizer():
    prev = FLAGS.sanitizer
    FLAGS.sanitizer = "buffers"
    try:
        pool = BlockPool(6, 8)
        blk = pool.alloc(1)
        pool.share(blk)
        pool.free(blk)
        pool.free(blk)                 # terminal decref: fine
        with pytest.raises(san.BufferLifetimeError, match="decref"):
            pool.free(blk)             # one decref too many
        pool.close()
    finally:
        FLAGS.sanitizer = prev


def test_lifetime_checker_covers_shared_blocks():
    from paddle_tpu.analysis import lifetime as lt
    from paddle_tpu.analysis.diagnostics import Severity

    diags = lt.check_serving_fetches(
        ["tokens", "shared_prefix"], [], site="tenant g",
        shared_state=["shared_prefix"])
    assert len(diags) == 1 and diags[0].var == "shared_prefix"
    assert diags[0].severity == Severity.ERROR
    assert "copy-on-write" in diags[0].message
    # donated classification wins over shared (one report per var)
    diags = lt.check_serving_fetches(
        ["kv_pages"], ["kv_pages"], shared_state=["kv_pages"])
    assert len(diags) == 1 and "donated" in diags[0].message


# --------------------------------------------------------------- e2e

def test_bit_identical_tokens_cache_on_vs_off():
    """THE correctness contract: greedy tokens must be bit-identical
    with the prefix cache on vs off, and the cached run must actually
    share (hits > 0, cached tokens > 0)."""
    cfg, params = tiny_lm(7, **CFG_KW)
    shared = list(np.random.RandomState(3).randint(0, 64, 17))
    prompts = [shared + [t] for t in (1, 2, 3)] + [shared[:10] + [5]]

    hits = []

    def run(on):
        metrics.zero_all()
        with InferenceServer() as srv:
            srv.load_generative("g", cfg, params, kv_blocks=64,
                                warm=False, prefix_cache=on)
            toks = [srv.generate("g", p, max_new_tokens=12).result(300)
                    ["tokens"] for p in prompts]
            # the hits gauge is recomputed from LIVE pools — read it
            # before unload retires this tenant's pool
            hits.append(metrics.gauge("serve_kv_prefix_hits").value)
        return toks

    off = run(False)
    on = run(True)
    assert on == off, "prefix cache changed greedy tokens"
    assert hits == [0, 3], hits     # 3 warm lookups shared blocks
    assert metrics.counter(
        "serve_prefix_tokens_cached_total").value > 0


def test_pool_exhaustion_preemption_with_prefix_cache():
    """Pool exhaustion with the cache ON: parked prefix blocks are
    reclaimed under pressure, sequences preempt/requeue, and every
    request still produces its solo tokens."""
    cfg, params = tiny_lm(11, **CFG_KW)
    shared = list(np.random.RandomState(5).randint(0, 64, 9))
    prompts = [shared + [t] for t in (1, 2, 3)]
    with InferenceServer() as srv:
        srv.load_generative("g", cfg, params, kv_blocks=64, warm=False)
        solo = [srv.generate("g", p, max_new_tokens=20).result(300)
                ["tokens"] for p in prompts]
    metrics.zero_all()
    with InferenceServer() as srv:
        # 7 usable blocks for 3 growing sequences + parked prefix
        srv.load_generative("g", cfg, params, kv_blocks=8, warm=False,
                            prefix_cache=True)
        futs = [srv.generate("g", p, max_new_tokens=20)
                for p in prompts]
        res = [f.result(300) for f in futs]
    preempts = metrics.counter("serve_kv_preemptions_total").value
    assert preempts > 0, "pool was never exhausted — test is vacuous"
    for i, (s, r) in enumerate(zip(solo, res)):
        assert s == r["tokens"], \
            "request %d diverged under preemption+cache" % i


def test_eviction_drops_unreachable_subtree():
    """Reclaiming a parked parent chunk drops its trie node AND every
    parked descendant (they are unreachable: a lookup can never walk
    through a missing parent)."""
    eng = _engine(kv_blocks=8)        # 7 usable
    try:
        idx = eng.prefix_cache
        prompt = list(range(24))      # 3 full chunks: parent chain
        a = _Req(prompt)
        assert idx.acquire(a)
        idx.insert(a)
        eng.pool.free(a.blocks)       # all parked (cacheable)
        assert idx.nodes == 3
        parked = eng.pool.cached_blocks
        assert parked >= 3
        # pressure: demand everything allocatable — the parent chunk
        # is reclaimed and the chain under it goes with it
        got = eng.pool.alloc(eng.pool.free_blocks)
        assert got is not None
        assert idx.nodes == 0
        assert eng.pool.cached_blocks == 0
        eng.pool.free(got)
    finally:
        eng.close()
