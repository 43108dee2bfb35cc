"""One decode step in flight (ISSUE 31): DecodeLoop dispatches step n+1
on step n's device tokens before it reads them, wherever the next step
runs over the same rows.  Whatever the order, the delivered tokens are
those of the engine's synchronous ``decode_step`` chain over the same
request alone — for finishes by count at mixed lengths, an EOS under a
step already dispatched, an arrival, a preemption, the prefix cache and
speculative rounds — and a failed dispatch or a stop with a step in
flight leaves no future, block or thread behind."""
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from paddle_tpu.core import sanitizer as san
from paddle_tpu.core.flags import FLAGS
from paddle_tpu.observability import metrics
from paddle_tpu.serving import GenerativeEngine, InferenceServer, tiny_lm
from paddle_tpu.serving.batcher import RequestQueue
from paddle_tpu.serving.generative import (DecodeLoop, GenRequest,
                                           _DecodeFlight)

CFG_KW = dict(vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
              block_size=8, max_blocks=8, max_batch=4)
SEED = 11


@pytest.fixture(scope="module")
def model():
    return tiny_lm(SEED, **CFG_KW)


@pytest.fixture(params=["off", "all"])
def sanitizer(request):
    """FLAGS_sanitizer for the engines the test builds (locks bind at
    creation), restored after; the test must not trip it."""
    prev = FLAGS.sanitizer
    FLAGS.sanitizer = request.param
    trips = metrics.counter("sanitizer_trips_total").value
    yield request.param
    FLAGS.sanitizer = prev
    san.reset_lock_graph()
    assert metrics.counter("sanitizer_trips_total").value == trips


def _prompts(seed, n, lo=3, hi=15):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 64, size=rng.randint(lo, hi)).tolist()
            for _ in range(n)]


_CHAINS = {}


def _chain(model, prompt, max_new, eos_id=None):
    """The request alone through ``prefill`` and then ``decode_step``,
    each step read before the next is staged, its input the host's."""
    key = (tuple(prompt), max_new)
    if key not in _CHAINS:
        cfg, params = model
        eng = GenerativeEngine(cfg, params, kv_blocks=16, warm=False)
        req = GenRequest(prompt, max_new, None, Future())
        try:
            req.blocks = eng.pool.alloc(
                eng.pool.blocks_for(len(prompt) + max_new))
            out, n = [eng.prefill(req)], len(prompt)
            while len(out) < max_new and n < cfg.max_seq:
                out.append(int(eng.decode_step([req.blocks], [n],
                                               [out[-1]])[0]))
                n += 1
        finally:
            eng.free_sequence(req)
            eng.close()
        _CHAINS[key] = out
    out = _CHAINS[key]
    return out[:out.index(eos_id) + 1] if eos_id in out else out


def _counts():
    return {name: metrics.counter("serve_decode_%s_total" % name).value
            for name in ("ahead", "steps", "rows")}


def _since(c0):
    c1 = _counts()
    return {k: c1[k] - c0[k] for k in c1}


# ------------------------------------------------------- the token paths

def test_mixed_lengths_finish_by_count(model, sanitizer):
    """(a) rows that end at different steps: each ending is known before
    its step is read and drains; between them the loop runs ahead."""
    prompts = _prompts(3, 4)
    lengths = [5, 9, 16, 12]
    c0 = _counts()
    with InferenceServer() as srv:
        eng = srv.load_generative("g", *model, kv_blocks=64, warm=False)
        futs = [srv.generate("g", p, max_new_tokens=n)
                for p, n in zip(prompts, lengths)]
        res = [f.result(180) for f in futs]
        assert eng.pool.used_blocks == 0
    for p, n, r in zip(prompts, lengths, res):
        assert r["tokens"] == _chain(model, p, n)
        assert len(r["itl_ms"]) == n - 1        # one delivery a token
    d = _since(c0)
    assert 0 < d["ahead"] <= d["steps"]
    # no EOS: no step ran a dead row, so every row of every step
    # delivered a token
    assert d["rows"] == sum(lengths) - len(lengths)


@pytest.mark.parametrize("neighbour", [False, True])
def test_eos_under_a_step_already_dispatched(model, sanitizer, neighbour):
    """(b) an EOS the host could not foresee: the step after it is out
    already and runs the finished row dead.  That token is never
    delivered, the row's blocks are freed once, and a neighbour goes on
    to its own tokens."""
    prompt, other = _prompts(8, 2)      # a chain that does not repeat early
    ref = _chain(model, prompt, 14)
    eos = next(t for i, t in enumerate(ref)
               if 3 <= i <= 10 and t not in ref[:i])
    want = ref[:ref.index(eos) + 1]
    c0 = _counts()
    with InferenceServer() as srv:
        eng = srv.load_generative("g", *model, kv_blocks=64, warm=False)
        fut = srv.generate("g", prompt, max_new_tokens=14, eos_id=eos)
        rest = (srv.generate("g", other, max_new_tokens=24)
                if neighbour else None)
        assert fut.result(180)["tokens"] == want
        if neighbour:
            assert rest.result(180)["tokens"] == _chain(model, other, 24)
        deadline = time.time() + 10
        while eng.pool.used_blocks and time.time() < deadline:
            time.sleep(0.01)
        assert eng.pool.used_blocks == 0
        # the loop still serves: the dropped step left nothing behind
        assert srv.generate("g", prompt, max_new_tokens=3).result(
            180)["tokens"] == ref[:3]
    d = _since(c0)
    assert d["ahead"] > 0
    if not neighbour:
        # alone, every step but the first after the prefill went out
        # ahead, the dead one too: one more row than tokens delivered
        delivered = (len(want) - 1) + 2
        assert d["rows"] == delivered + 1


def test_a_request_arrives_under_a_step_in_flight(model, sanitizer):
    """(c) the arrival drains: it is admitted at the next iteration, and
    both requests' tokens are their own."""
    first, second = _prompts(7, 2)
    sent = []
    c0 = _counts()
    with InferenceServer() as srv:
        eng = srv.load_generative("g", *model, kv_blocks=64, warm=False)
        start = eng.decode_start

        def hooked(seqs, after=None, **kw):
            flight = start(seqs, after=after, **kw)
            if after is not None and not sent:      # two steps are out
                sent.append(srv.generate("g", second, max_new_tokens=10))
            return flight

        eng.decode_start = hooked
        a = srv.generate("g", first, max_new_tokens=40).result(180)
        b = sent[0].result(180)
    assert a["tokens"] == _chain(model, first, 40)
    assert b["tokens"] == _chain(model, second, 10)
    d = _since(c0)
    assert d["rows"] > d["steps"], "the two never shared a step"
    assert 0 < d["ahead"] < d["steps"]


def test_a_pool_small_enough_to_preempt(model, sanitizer):
    """(d) growth that needs a preemption is never made ahead: the step
    in flight is read first, and the evicted request regenerates its
    tokens."""
    prompts = _prompts(9, 3, lo=6, hi=12)
    preempts = metrics.counter("serve_kv_preemptions_total").value
    c0 = _counts()
    with InferenceServer() as srv:
        # 7 usable blocks: three sequences growing to 4 blocks each
        # cannot all fit
        eng = srv.load_generative("g", *model, kv_blocks=8, warm=False)
        futs = [srv.generate("g", p, max_new_tokens=20) for p in prompts]
        res = [f.result(300) for f in futs]
        assert eng.pool.used_blocks == 0
    assert metrics.counter("serve_kv_preemptions_total").value > preempts
    for p, r in zip(prompts, res):
        assert r["tokens"] == _chain(model, p, 20)
    assert _since(c0)["ahead"] > 0


def test_the_prefix_cache_on(model):
    """(e) shared prefix blocks, a suffix prefill and parked blocks
    beside steps dispatched ahead."""
    stem = _prompts(13, 1, lo=17, hi=18)[0]
    prompts = [stem + tail for tail in _prompts(14, 3, lo=2, hi=6)]
    c0 = _counts()
    cached = metrics.counter("serve_prefix_tokens_cached_total").value
    with InferenceServer() as srv:
        eng = srv.load_generative("g", *model, kv_blocks=64, warm=False,
                                  prefix_cache=True)
        res = [srv.generate("g", p, max_new_tokens=12).result(180)
               for p in prompts[:1]]
        futs = [srv.generate("g", p, max_new_tokens=12)
                for p in prompts[1:]]
        res += [f.result(180) for f in futs]
        assert metrics.counter(
            "serve_prefix_tokens_cached_total").value > cached
        assert eng.pool.used_blocks == 0
    for p, r in zip(prompts, res):
        assert r["tokens"] == _chain(model, p, 12)
    assert _since(c0)["ahead"] > 0


def test_speculative_rounds_are_never_ahead(model):
    """(f) acceptance reads the round's tokens: nothing stays in
    flight, and the tokens are plain greedy decode's."""
    dcfg, dparams = tiny_lm(SEED + 1, **dict(CFG_KW, n_layers=1))
    prompts = _prompts(21, 3, lo=4, hi=10)
    c0 = _counts()
    with InferenceServer() as srv:
        srv.load_generative("g", *model, kv_blocks=64, warm=False,
                            spec_k=3, draft=(dcfg, dparams))
        futs = [srv.generate("g", p, max_new_tokens=12) for p in prompts]
        res = [f.result(300) for f in futs]
        loop = srv._tenants["g"].dispatcher
        assert loop._flight is None
    for p, r in zip(prompts, res):
        assert r["tokens"] == _chain(model, p, 12)
    d = _since(c0)
    assert d["steps"] > 0 and d["ahead"] == 0


# ------------------------------------------------- failure and shutdown

def test_a_dispatch_that_raises_under_a_step_in_flight(model):
    """(g) the step in flight goes with the running rows: every future
    resolves, every block comes back, and the loop serves the next
    request."""
    prompts = _prompts(31, 3)
    with InferenceServer() as srv:
        eng = srv.load_generative("g", *model, kv_blocks=64, warm=False)
        free0 = eng.pool.free_blocks
        dispatch = eng.decode_dispatch
        raised = []

        def failing(blocks_list, lens_list, toks, **kw):
            if (isinstance(toks, _DecodeFlight) and len(blocks_list) == 3
                    and not raised):
                raised.append(True)
                raise RuntimeError("planted dispatch failure")
            return dispatch(blocks_list, lens_list, toks, **kw)

        eng.decode_dispatch = failing
        futs = [srv.generate("g", p, max_new_tokens=30) for p in prompts]
        for f in futs:
            with pytest.raises(RuntimeError, match="planted"):
                f.result(180)
        assert raised
        loop = srv._tenants["g"].dispatcher
        assert loop._thread.is_alive() and loop._flight is None
        assert eng.pool.free_blocks == free0
        again = srv.generate("g", prompts[0], max_new_tokens=8).result(180)
        assert again["tokens"] == _chain(model, prompts[0], 8)
        assert eng.pool.free_blocks == free0


def test_stop_with_a_step_in_flight(model):
    """(h) a stopping loop drains: the running request gets all its
    tokens, nothing stays in flight and the thread ends."""
    cfg, params = model
    prompt = _prompts(41, 1)[0]
    eng = GenerativeEngine(cfg, params, kv_blocks=64, warm=False)
    try:
        ahead = threading.Event()
        start = eng.decode_start

        def hooked(seqs, after=None, **kw):
            flight = start(seqs, after=after, **kw)
            if after is not None:
                ahead.set()
            return flight

        eng.decode_start = hooked
        queue = RequestQueue()
        loop = DecodeLoop(eng, queue, label="stop")
        req = GenRequest(prompt, 50, None, Future())
        queue.put(req)
        assert ahead.wait(120)
        loop.stop()
        assert not loop._thread.is_alive()
        assert req.future.result(0)["tokens"] == _chain(model, prompt, 50)
        assert loop._flight is None and eng.pool.used_blocks == 0
    finally:
        eng.close()


# ------------------------------------------------------ the engine's split

def test_a_flight_feeds_the_next_dispatch_or_is_read_first(model):
    """``decode_dispatch`` on an unread flight of its own bucket hands
    the device tokens on; on a read one, or one of another batch
    bucket, it takes the host's: the same tokens every way."""
    cfg, params = model
    prompts = _prompts(51, 2)
    eng = GenerativeEngine(cfg, params, kv_blocks=64, warm=False)
    reqs = [GenRequest(p, 8, None, Future()) for p in prompts]
    try:
        for r in reqs:
            r.blocks = eng.pool.alloc(eng.pool.blocks_for(len(r.prompt) + 8))
            r.out.append(eng.prefill(r))
        c0 = _counts()
        first = eng.decode_start(reqs)
        assert isinstance(first, _DecodeFlight) and first.host is None
        second = eng.decode_start(reqs, after=first)     # ahead
        t1 = eng.decode_read(first)
        third = eng.decode_start(reqs, after=second)     # ahead
        t2 = eng.decode_read(second)
        fourth = eng.decode_start(reqs, after=third)     # third is unread
        t3, t4 = eng.decode_read(third), eng.decode_read(fourth)
        # a read flight, and one of another batch bucket (a covering
        # bucket answered for it): read first, the host's tokens go in
        fifth = eng.decode_start(reqs, after=fourth)
        fifth.bb *= 2
        sixth = eng.decode_start(reqs, after=fifth)
        assert fifth.host is not None
        t5, t6 = eng.decode_read(fifth), eng.decode_read(sixth)
        d = _since(c0)
        assert d["steps"] == 6 and d["ahead"] == 3
        for i, r in enumerate(reqs):
            ref = _chain(model, r.prompt, 8)
            got = [r.out[0]] + [int(t[i])
                                for t in (t1, t2, t3, t4, t5, t6)]
            assert got == ref[:7]
        assert eng.decode_read(first) is t1          # read once
    finally:
        for r in reqs:
            eng.free_sequence(r)
        eng.close()
