"""An admitted prompt rides the decode step (ISSUE 33): where rows are
running, a model with the riding forward has its fresh prompt run in the
program that decodes them, one prompt a step, and nobody waits a prefill
out.  Whatever rides, the tokens and the cache are those of a prefill
followed by a plain decode step; what cannot ride (no running row, a
prefix-cache hit's suffix, a speculative tenant) is prefilled as before
and is not counted as a ride."""
from concurrent.futures import Future

import jax
import numpy as np
import pytest

from paddle_tpu.observability import metrics
from paddle_tpu.observability.trace import TRACER
from paddle_tpu.serving import GenerativeEngine, InferenceServer, tiny_lm
from paddle_tpu.serving.batcher import RequestQueue
from paddle_tpu.serving.generative import DecodeLoop, GenRequest
from paddle_tpu.serving.nemotron_h import tiny_nemotron_h

# blocks of 8, 4 rows, 256 positions: prompt buckets of 64, 128 and 256
LM_KW = dict(vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
             block_size=8, max_blocks=32, max_batch=4)
MODELS = {"lm": lambda: tiny_lm(11, **LM_KW),
          "hybrid": lambda: tiny_nemotron_h(5, max_blocks=32)}


@pytest.fixture(scope="module", params=sorted(MODELS))
def model(request):
    return (request.param, *MODELS[request.param]())


def _prompts(seed, n, lo=3, hi=15):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 64, size=rng.randint(lo, hi)).tolist()
            for _ in range(n)]


def _counts():
    return {name: metrics.counter("serve_%s_total" % name).value
            for name in ("prefill_rides", "prefills", "admissions",
                         "decode_steps", "decode_ahead")}


def _since(c0):
    c1 = _counts()
    return {k: c1[k] - c0[k] for k in c1}


_ALONE = {}


def _alone(model, prompt, max_new, eos_id=None):
    """The request's tokens when it is served one at a time: its prompt
    meets no running row, so it is prefilled and then decoded alone."""
    name, cfg, params = model
    key = (name, tuple(prompt), max_new)
    if key not in _ALONE:
        with InferenceServer() as srv:
            srv.load_generative("g", cfg, params, kv_blocks=64, warm=False)
            _ALONE[key] = srv.generate(
                "g", prompt, max_new_tokens=max_new).result(300)["tokens"]
    out = _ALONE[key]
    return out[:out.index(eos_id) + 1] if eos_id in out else out


def _admitted(eng, prompt, max_new=8):
    """A request as the scheduler admits it: blocks, and a state slot
    where the model keeps state."""
    req = GenRequest(prompt, max_new, None, Future())
    req.blocks = eng.pool.alloc(eng.pool.blocks_for(len(prompt) + max_new))
    if eng.stateful:
        req.slot = eng.pool.take_slot()
    return req


def _cache_leaves(eng):
    """The engine's cache arrays without the scratch block / slot (index
    0 of axis 1 in every one of them), which padding writes to."""
    tree, _ = eng.cache_state()
    return [np.asarray(a, np.float32)[:, 1:]
            for a in jax.tree_util.tree_leaves(tree)]


# ------------------------------------------------- (a) the step itself

@pytest.mark.parametrize("n_prompt", [5, 70], ids=["bucket64", "bucket128"])
@pytest.mark.parametrize("live", [0, 1, 3])
def test_riding_step_equals_prefill_then_decode(model, live, n_prompt):
    """The riding step's R next tokens, the newcomer's first token and
    afterwards the whole cache (K/V pages; the hybrid's windows and
    states) are those of ``prefill`` followed by a plain decode step,
    with no row, one row and all rows but the newcomer's running."""
    _, cfg, params = model
    running = _prompts(3, live, lo=4, hi=20)
    newcomer = _prompts(4 + n_prompt, 1, lo=n_prompt, hi=n_prompt + 1)[0]
    got = []
    for ride in (True, False):
        eng = GenerativeEngine(cfg, params, kv_blocks=48, warm=False)
        try:
            assert eng.prompt_ladder == [64, 128, 256]
            rows = [_admitted(eng, p) for p in running]
            for r in rows:
                r.out.append(eng.prefill(r))
            for _ in range(2):      # the rows are mid-generation
                for r, t in zip(rows, eng.decode(rows) if rows else ()):
                    r.out.append(int(t))
            new = _admitted(eng, newcomer)
            if ride:
                flight = eng.decode_start(rows, rider=new)
                assert flight.riding == (64 if n_prompt == 5 else 128)
                *nxt, first = (int(t) for t in eng.decode_read(flight))
            else:
                first = eng.prefill(new)
                nxt = [int(t) for t in eng.decode(rows)] if rows else []
            assert new.context_len == len(newcomer)
            assert [r.context_len for r in rows] == [
                len(p) + 3 for p in running]
            got.append((nxt, first, _cache_leaves(eng)))
        finally:
            eng.close()
    (nxt, first, cache), (nxt_w, first_w, cache_w) = got
    assert nxt == nxt_w and first == first_w
    assert len(nxt) == live
    for a, b in zip(cache, cache_w):
        assert np.abs(b).sum() > 0
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


# ------------------------------------- (b) whole generations, mixed arrivals

def test_mixed_arrivals_generate_as_one_at_a_time(model):
    """More requests than rows, unequal lengths, one EOS mid-way: every
    request's tokens are those it gets when served alone, and all but
    the prompt that met an empty batch rode."""
    prompts = _prompts(23, 7)
    lengths = [14, 5, 9, 20, 3, 12, 7]
    ref = _alone(model, prompts[0], lengths[0])
    eos = next(t for i, t in enumerate(ref)
               if 3 <= i <= 10 and t not in ref[:i])
    want = [ref[:ref.index(eos) + 1]] + [
        _alone(model, p, n) for p, n in zip(prompts[1:], lengths[1:])]
    c0 = _counts()
    with InferenceServer() as srv:
        eng = srv.load_generative("g", *model[1:], kv_blocks=64, warm=False)
        futs = [srv.generate("g", p, max_new_tokens=n,
                             eos_id=eos if i == 0 else None)
                for i, (p, n) in enumerate(zip(prompts, lengths))]
        res = [f.result(300)["tokens"] for f in futs]
        assert eng.pool.used_blocks == 0 and eng.pool.slots_held == 0
    d = _since(c0)
    assert res == want
    assert d["admissions"] == d["prefills"] == 7
    assert 5 <= d["prefill_rides"] <= 6
    assert d["decode_ahead"] > 0


# ---------------------------------------- (c) one prompt a step, in order

def test_prompts_admitted_together_ride_consecutive_steps(model):
    """Two requests admitted in one iteration: the first rides the step
    dispatched next, the second the one after it, each in the row after
    the running ones, and both steps go out behind the one before (a
    waiting prompt rides a step dispatched ahead)."""
    _, cfg, params = model
    first, second, third = _prompts(31, 3)
    eng = GenerativeEngine(cfg, params, kv_blocks=64, warm=False)
    queue = RequestQueue()
    reqs = [GenRequest(p, 12, None, Future()) for p in (second, third)]
    steps = []
    try:
        start = eng.decode_start

        def hooked(seqs, after=None, rider=None, **kw):
            flight = start(seqs, after=after, rider=rider, **kw)
            steps.append((len(seqs), rider and rider.rid, after is not None,
                          len(loop._waiting)))
            if after is not None and len(steps) == 2:
                for r in reqs:      # both arrive under one step
                    queue.put(r)
            return flight

        eng.decode_start = hooked
        loop = DecodeLoop(eng, queue, label="rides")
        lone = GenRequest(first, 12, None, Future())
        queue.put(lone)
        out = [r.future.result(300)["tokens"] for r in [lone] + reqs]
        loop.stop()
        assert eng.pool.used_blocks == 0 and not loop._waiting
    finally:
        eng.close()
    for p, toks in zip((first, second, third), out):
        assert toks == _alone(model, p, 12)
    # a step from the host, one ahead (the arrivals), then the two rides,
    # each rider among its step's rows
    assert steps[:2] == [(1, None, False, 0), (1, None, True, 0)]
    assert steps[2:4] == [(2, reqs[0].rid, True, 1),
                          (3, reqs[1].rid, True, 0)]
    assert steps[4] == (3, None, True, 0)       # and on ahead, all three


def test_a_carrying_step_goes_out_ahead_where_rows_run(model):
    """A prompt admitted while rows run rides a step dispatched on the
    device tokens of the one before: its ``serve.decode`` span says
    ``ahead=1``, its ``rows`` are the live decode rows and ``dead`` 0.
    The step after a prefill (no row ran) goes out from the host."""
    _, cfg, params = model
    prompts = _prompts(35, 4)
    eng = GenerativeEngine(cfg, params, kv_blocks=64, warm=False)
    queue = RequestQueue()
    reqs = [GenRequest(p, 6, None, Future()) for p in prompts]
    for r in reqs:      # all four are there at the first admission
        queue.put(r)
    assert not TRACER.on
    TRACER.clear()
    TRACER.enable()
    try:
        loop = DecodeLoop(eng, queue, label="ahead")
        out = [r.future.result(300)["tokens"] for r in reqs]
        loop.stop()
    finally:
        TRACER.disable()
        eng.close()
    spans = TRACER.completed()
    TRACER.clear()
    assert out == [_alone(model, p, 6) for p in prompts]
    carried = [s["args"] for s in spans if s["name"] == "serve.decode"
               and "riding" in s["args"]]
    assert [(a["ahead"], a["rows"], a["dead"]) for a in carried] == [
        (0, 1, 0), (1, 2, 0), (1, 3, 0)]


# --------------------------------------------- (d) a riding prompt fails

def test_a_riding_prompt_that_raises_fails_alone(model):
    """The request whose prompt was to ride fails alone: its blocks and
    slot come back, the step goes out without it and the running rows'
    tokens are their own."""
    good, bad, later = _prompts(41, 3)
    with InferenceServer() as srv:
        eng = srv.load_generative("g", *model[1:], kv_blocks=64, warm=False)
        free0 = eng.pool.free_blocks
        dispatch = eng.decode_dispatch
        sent = []

        def failing(blocks_list, lens_list, toks, rider=None, **kw):
            if rider is not None and list(rider[0]) == bad:
                raise RuntimeError("planted riding failure")
            flight = dispatch(blocks_list, lens_list, toks, rider=rider, **kw)
            if blocks_list and not sent:    # a row is running: now arrive
                sent.append(srv.generate("g", bad, max_new_tokens=6))
                sent.append(srv.generate("g", later, max_new_tokens=6))
            return flight

        eng.decode_dispatch = failing
        a = srv.generate("g", good, max_new_tokens=16).result(300)
        with pytest.raises(RuntimeError, match="planted riding"):
            sent[0].result(300)
        c = sent[1].result(300)
        assert eng.pool.free_blocks == free0 and eng.pool.slots_held == 0
    assert a["tokens"] == _alone(model, good, 16)
    assert c["tokens"] == _alone(model, later, 6)


def test_a_waiting_prompt_is_the_first_to_be_preempted(model):
    """A prompt that waits for its step holds its blocks; when a row
    cannot grow, the youngest request goes first, and that is the one
    still waiting: it is requeued, comes back and generates its own
    tokens."""
    prompts = _prompts(45, 3, lo=7, hi=8)       # one block of 8 each
    want = [_alone(model, p, 6) for p in prompts]
    with InferenceServer() as srv:
        # three usable blocks: all three are admitted, none can grow
        eng = srv.load_generative("g", *model[1:], kv_blocks=4, warm=False)
        futs = [srv.generate("g", p, max_new_tokens=6) for p in prompts]
        res = [f.result(300) for f in futs]
        assert eng.pool.used_blocks == 0 and eng.pool.slots_held == 0
    assert [r["tokens"] for r in res] == want
    assert res[0]["preempted"] == 0 and res[2]["preempted"] >= 1


# ------------------------------------------------- (e) what does not ride

def test_a_prompt_into_an_empty_batch_is_a_waited_prefill(model):
    """No row is running: the loop prefills and waits, as before, under
    ``serve_loop_prefill_us_total``; nothing rode."""
    prompt = _prompts(51, 1)[0]
    want = _alone(model, prompt, 5)
    waited = metrics.counter("serve_loop_prefill_us_total")
    c0, w0 = _counts(), waited.value
    with InferenceServer() as srv:
        srv.load_generative("g", *model[1:], kv_blocks=64, warm=False)
        for _ in range(2):
            assert srv.generate("g", prompt, max_new_tokens=5).result(
                300)["tokens"] == want
    d = _since(c0)
    assert d["prefills"] == d["admissions"] == 2
    assert d["prefill_rides"] == 0
    assert waited.value > w0


@pytest.mark.parametrize("how", ["prefix_hit", "speculative"])
def test_suffix_prefills_and_speculative_tenants_do_not_ride(how):
    """A prefix-cache hit computes its suffix through the pages and a
    speculative tenant's steps are rounds: neither rides, beside running
    rows or not; a cold prompt of the prefix-cache tenant does."""
    model = ("lm", *MODELS["lm"]())
    stem = _prompts(61, 1, lo=17, hi=18)[0]
    prompts = [stem + tail for tail in _prompts(62, 3, lo=2, hi=6)]
    cold = _prompts(63, 1)[0]
    kw = ({"prefix_cache": True} if how == "prefix_hit" else
          {"spec_k": 2, "draft": tiny_lm(12, **dict(LM_KW, n_layers=1))})
    order = [prompts[0], cold] + prompts[1:]
    want = [_alone(model, p, 10) for p in order]
    c0 = _counts()
    with InferenceServer() as srv:
        eng = srv.load_generative("g", *model[1:], kv_blocks=64, warm=False,
                                  **kw)
        # alone first: its full chunks are indexed for the others
        res = [srv.generate("g", order[0], max_new_tokens=10).result(300)]
        futs = [srv.generate("g", p, max_new_tokens=10) for p in order[1:]]
        res += [f.result(300) for f in futs]
        assert eng.pool.used_blocks == 0
        hits = eng.pool._prefix_hits
    d = _since(c0)
    assert [r["tokens"] for r in res] == want
    assert d["admissions"] == 4
    # the cold prompt met an empty batch; the hits came beside it
    assert d["prefill_rides"] == 0
    if how == "prefix_hit":
        assert hits == 2
        # a cold prompt beside a running row rides in this tenant too
        c1 = _counts()
        with InferenceServer() as srv:
            srv.load_generative("g", *model[1:], kv_blocks=64, warm=False,
                                **kw)
            futs = [srv.generate("g", p, max_new_tokens=10)
                    for p in order[:2]]
            assert [f.result(300)["tokens"] for f in futs] == want[:2]
        assert _since(c1)["prefill_rides"] == 1
