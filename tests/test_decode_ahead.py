"""One decode step in flight (ISSUE 31): DecodeLoop dispatches step n+1
on step n's device tokens before it reads them.  The batch is a stable
row layout (ISSUE 36): a finished row stays as a dead row until a
newcomer takes its index, so the loop runs ahead through finishes by
count and through admissions.  Whatever the order, the delivered tokens
are those of the engine's synchronous ``decode_step`` chain over the
same request alone — for finishes by count at mixed lengths, an EOS
under a step already dispatched, an arrival, a preemption, the prefix
cache and speculative rounds — what a live row holds of the cache is
what a synchronous run wrote, and a failed dispatch or a stop with a
step in flight leaves no future, block or thread behind."""
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
from paddle_tpu.serving.nemotron_h import tiny_nemotron_h

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


def _synchronous(eng, prompt, max_new, upto=None):
    """``prompt`` alone through ``eng``'s ``prefill`` and then
    ``decode_step``, each step read before the next is staged, its input
    the host's: its tokens, and where ``upto`` is given, what it holds
    of the cache (``_held``) once ``upto`` positions are written."""
    req = GenRequest(prompt, max_new, None, Future())
    try:
        req.blocks = eng.pool.alloc(eng.pool.blocks_for(len(prompt)
                                                        + max_new))
        if eng.stateful:
            req.slot = eng.pool.take_slot()
        slots = {"slots": [req.slot]} if eng.stateful else {}
        out, n = [eng.prefill(req)], len(prompt)
        while (len(out) < max_new and n < eng.config.max_seq
               and (upto is None or n < upto)):
            out.append(int(eng.decode_step([req.blocks], [n], [out[-1]],
                                           **slots)[0]))
            n += 1
        held = None
        if upto is not None:
            assert n == upto
            held = _held(_leaves(eng), eng.pool.num_blocks, req.blocks,
                         req.slot, n, eng.config.block_size)
        return out, held
    finally:
        eng.free_sequence(req)


def _chain(model, prompt, max_new, eos_id=None):
    """The request alone, through ``_synchronous`` on an engine of its
    own."""
    key = (id(model[0]), tuple(prompt), max_new)
    if key not in _CHAINS:
        cfg, params = model
        eng = GenerativeEngine(cfg, params, kv_blocks=16, warm=False)
        try:
            _CHAINS[key] = _synchronous(eng, prompt, max_new)[0]
        finally:
            eng.close()
    out = _CHAINS[key]
    return out[:out.index(eos_id) + 1] if eos_id in out else out


def _leaves(eng):
    """The engine's cache arrays on the host, in the tree's order."""
    import jax

    tree, _ = eng.cache_state()
    return [np.asarray(a, np.float32) for a in jax.tree_util.tree_leaves(tree)]


def _held(leaves, n_blocks, blocks, slot, n, block_size):
    """What a sequence holds of the cache: its first ``n`` positions of
    every page array (axis 1 the block, axis 2 the position in it) and
    its slot of every state array (axis 1 the slot)."""
    out = []
    for a in leaves:
        if a.shape[1] == n_blocks:
            out.append(np.stack([a[:, blocks[p // block_size],
                                   p % block_size] for p in range(n)], 1))
        else:
            out.append(a[:, slot])
    return out


def _counts():
    return {name: metrics.counter("serve_decode_%s_total" % name).value
            for name in ("ahead", "steps", "rows")}


def _since(c0):
    c1 = _counts()
    return {k: c1[k] - c0[k] for k in c1}


# ------------------------------------------------------- the token paths

def test_mixed_lengths_finish_by_count(model, sanitizer):
    """(a) rows that end at different steps: each ending is known before
    its step is read, and the row is dead in the step after it."""
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
    """(c) the arrival is admitted at the next iteration, in front of
    the read, and rides the step dispatched there; both requests' tokens
    are their own."""
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
            # a plain step: a carrying one that raises fails its rider
            # alone (tests/test_prefill_rides.py)
            if (isinstance(toks, _DecodeFlight) and len(blocks_list) == 3
                    and kw.get("rider") is None and not raised):
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


# ---------------------------------------------- the stable row layout

LAYOUT_MODELS = {"lm": lambda: tiny_lm(SEED, **CFG_KW),
                 "hybrid": lambda: tiny_nemotron_h(5)}


@pytest.fixture(scope="module", params=sorted(LAYOUT_MODELS))
def layout_model(request):
    return LAYOUT_MODELS[request.param]()


def _run_queued(model, prompts, lengths, snapshot_at=()):
    """Every request in the queue before the loop starts, so that what
    it admits when does not depend on the clock: a full batch that a
    newcomer joins as a row finishes.  Returns the tokens, each step's
    ``(layout as request ids, rider's id, dispatched behind a flight)``,
    the counters' change and, at the steps ``snapshot_at``, what each
    row of the layout holds of the cache with what it was fed."""
    cfg, params = model
    eng = GenerativeEngine(cfg, params, kv_blocks=64, warm=False)
    queue = RequestQueue()
    reqs = [GenRequest(p, n, None, Future())
            for p, n in zip(prompts, lengths)]
    for r in reqs:
        queue.put(r)
    steps, snaps = [], []
    try:
        start = eng.decode_start

        def hooked(seqs, after=None, rider=None, **kw):
            flight = start(seqs, after=after, rider=rider, **kw)
            steps.append(([s and s.rid for s in seqs], rider and rider.rid,
                          after is not None))
            if len(steps) in snapshot_at:
                leaves = _leaves(eng)
                snaps.append([
                    (s.prompt, s.max_new, s.context_len,
                     _held(leaves, eng.pool.num_blocks, s.blocks, s.slot,
                           s.context_len, cfg.block_size))
                    for s in seqs if s is not None])
            return flight

        eng.decode_start = hooked
        c0 = _counts()
        loop = DecodeLoop(eng, queue, label="layout")
        res = [r.future.result(300)["tokens"] for r in reqs]
        loop.stop()
        d = _since(c0)
        # (e) everything a row held came back
        assert eng.pool.used_blocks == 0 and eng.pool.slots_held == 0
        assert not loop._waiting and loop._flight is None
    finally:
        eng.close()
    return res, [r.rid for r in reqs], steps, d, snaps


def test_the_layout_runs_ahead_through_finishes_and_admissions(
        layout_model):
    """(a) across finishes by count every step after the first goes out
    on the device tokens of the one before; (b) a newcomer rides a step
    dispatched so, at the index a finished row left; (c) every request's
    tokens are its synchronous chain's.  A row keeps its index from the
    step that carries it to its last, and the dead rows are counted."""
    prompts = _prompts(71, 8)
    lengths = [5, 9, 3, 12, 7, 4, 10, 6]
    res, rids, steps, d, _ = _run_queued(layout_model, prompts, lengths)
    ref = GenerativeEngine(*layout_model, kv_blocks=64, warm=False)
    try:
        for p, n, r in zip(prompts, lengths, res):
            assert r == _synchronous(ref, p, n)[0]
    finally:
        ref.close()
    # the first step follows the prefill of a prompt that met no row;
    # the batch never empties after it, and no step drained
    assert d["steps"] == len(steps) and d["ahead"] == d["steps"] - 1
    assert [after for _, _, after in steps] == [False] + [True] * (
        len(steps) - 1)
    assert d["rows"] == sum(lengths) - len(lengths)
    assert any(None in rows for rows, _, _ in steps)
    index = {}
    for rows, rider, _ in steps:
        for i, rid in enumerate(rows):
            if rid is not None:
                assert index.setdefault(rid, i) == i    # kept its row
    riders = [(k, rows.index(rider)) for k, (rows, rider, _) in
              enumerate(steps) if rider is not None]
    assert [steps[k][1] for k, _ in riders] == rids[1:]
    # after the first three ride into the empty rows, each newcomer takes
    # the row of one that ended: in the step right after its last, or
    # after it ran dead
    took = [steps[k - 1][0][i] for k, i in riders[3:]]
    assert all(i < len(steps[k - 1][0]) for k, i in riders[3:])
    assert all(rid is None or rid not in steps[k][0]
               for rid, (k, _) in zip(took, riders[3:]))
    assert any(rid is not None for rid in took)


def test_live_rows_hold_what_a_synchronous_run_wrote(layout_model):
    """(d) after mixed finishes and admissions, every page position and
    state slot a live row holds (a rider's prompt included) equals what
    the request alone wrote through ``prefill`` and ``decode_step``: no
    dead row wrote into a block or slot a newcomer was given."""
    prompts = _prompts(73, 8)
    lengths = [4, 11, 3, 9, 6, 3, 8, 5]
    cfg, params = layout_model
    res, _, steps, _, snaps = _run_queued(
        layout_model, prompts, lengths, snapshot_at=range(4, 40, 3))
    assert len(snaps) >= 4
    ref = GenerativeEngine(cfg, params, kv_blocks=64, warm=False)
    try:
        for rows in snaps:
            for prompt, max_new, n, held in rows:
                want = _synchronous(ref, prompt, max_new, upto=n)[1]
                for a, b in zip(held, want):
                    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
        for p, n, r in zip(prompts, lengths, res):
            assert r == _synchronous(ref, p, n)[0]
    finally:
        ref.close()


@pytest.mark.parametrize("name", sorted(LAYOUT_MODELS))
def test_the_warm_keys_a_tenant_compiles_at_load(name):
    """The stable layout adds no program: a tenant warms the decode
    ladder at the top block count and one riding program a prompt
    bucket, and nothing else."""
    cfg, params = LAYOUT_MODELS[name]()
    eng = GenerativeEngine(cfg, params, kv_blocks=16)
    try:
        assert eng._decode.warm_keys == [(1, 8), (2, 8), (4, 8)]
        assert eng._prefill.warm_keys == [(64,)]
        for cache in (eng._decode_logits, eng._prefill_cached, eng._verify,
                      eng._propose):
            assert cache.warm_keys == []
    finally:
        eng.close()
