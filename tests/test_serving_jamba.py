"""The served Jamba model (serving/jamba.py: Mamba-1 and multi-query
attention layers, a gated SiLU feed-forward in each, the head tied to
the embedding) through the engine, at a small size on the CPU, against
the plain reference (benchmark/lib/reference_jamba.py) with the same
seeded weights; and its two kernels, interpreted, against their XLA
paths and the recurrence by hand."""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import reference_jamba as ref           # noqa: E402
from paddle_tpu.kernels.selective_scan import selective_scan  # noqa: E402
from paddle_tpu.kernels.ssm_update import mamba1_state_update  # noqa: E402
from paddle_tpu.observability import metrics               # noqa: E402
from paddle_tpu.serving import GenerativeEngine, InferenceServer  # noqa: E402
from paddle_tpu.serving.jamba import JambaConfig           # noqa: E402

SEED = 7
# five layers, attention at 2 (2 % 4): two runs of two Mamba layers
SIZES = dict(vocab=96, hidden=64, pattern="MM*MM", n_heads=4, n_kv_heads=1,
             d_inner=128, state=16, dt_rank=8, conv_kernel=4, ff=96,
             eps=1e-6, time_step_min=0.001, time_step_max=0.1,
             time_step_floor=1e-4)


def config(**kw):
    s = SIZES
    kw = dict(dict(block_size=8, max_blocks=8, max_batch=4), **kw)
    return JambaConfig(
        vocab=s["vocab"], hidden=s["hidden"], n_layers=5, attn_offset=2,
        attn_period=4, n_heads=s["n_heads"], n_kv_heads=s["n_kv_heads"],
        d_inner=s["d_inner"], state=s["state"], dt_rank=s["dt_rank"],
        conv_kernel=s["conv_kernel"], ff=s["ff"], eps=s["eps"], **kw)


@pytest.fixture(scope="module")
def params():
    return ref.stacked_params(SEED, SIZES)


def engine(params, **kw):
    kw = dict(dict(kv_blocks=40, warm=False, prefix_cache=False, spec_k=0),
              **kw)
    return GenerativeEngine(config(), params, **kw)


def prompt_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, SIZES["vocab"],
                                                n).tolist()


def test_the_layer_pattern_and_its_runs():
    cfg = config()
    assert cfg.pattern == SIZES["pattern"] and cfg.head_dim == 16
    assert cfg.runs == [("M", 0, 2), ("*", 2, 1), ("M", 3, 2)]
    assert cfg.state_slots == cfg.max_batch + 1
    with pytest.raises(ValueError, match="no 'int8' staging"):
        cfg.stage({}, "int8", None)


# ---------------------------------------------------------------------------
# against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_prompt", [5, 11, 19])
def test_prefill_then_decode_through_slots_and_pages_is_the_reference(
        params, n_prompt):
    """The prompt whole (a padded bucket where it is no power of two:
    the scan walks ``length`` tokens and leaves their state), then one
    token a step through the slot's window and state and the table's
    pages: every step's logits are the reference's full forward at that
    position.  The tolerance is bfloat16's: the residual stream, the
    weights and the products' operands are bfloat16 in the program and
    float32 in the reference (logits of magnitude ~1.5, rounding of a
    few 1e-3); a served token is the reference's first choice or within
    that rounding of it."""
    eng = engine(params)
    try:
        prompt, n_new = prompt_of(n_prompt), 12
        slot, blocks = eng.pool.take_slot(), eng.pool.alloc(4)
        toks, got = [eng.prefill_tokens(prompt, blocks, slot=slot)], []
        for j in range(n_new):
            nxt, lg = eng.decode_step([blocks], [n_prompt + j], [toks[-1]],
                                      with_logits=True, slots=[slot])
            toks.append(int(nxt[0]))
            got.append(lg[0])
    finally:
        eng.close()
    full = np.zeros((1, 32), np.int32)
    full[0, :n_prompt + n_new + 1] = prompt + toks
    want = np.asarray(ref.logits_at(
        SEED, SIZES, full, np.arange(n_prompt - 1, n_prompt + n_new)[None]))[0]
    gap = want.max(axis=-1) - want[np.arange(len(toks)), toks]
    assert gap.max() < 0.01 and (gap == 0).mean() > 0.8, gap
    np.testing.assert_allclose(np.stack(got), want[1:], atol=0.02)


def test_a_decode_step_moves_every_mamba_layers_state_of_the_slot(params):
    """The runs of Mamba layers are loops over their layers: a decode
    step reads and writes the window and the state of its slot in every
    Mamba layer, and no other slot's."""
    eng = engine(params)
    try:
        slot, blocks = eng.pool.take_slot(), eng.pool.alloc(2)
        eng.prefill_tokens(prompt_of(6), blocks, slot=slot)
        before, _ = eng.cache_state()
        before = {k: np.asarray(before[k]) for k in ("conv", "ssm")}
        eng.decode_step([blocks], [6], [3], slots=[slot])
        after, _ = eng.cache_state()
        for k in ("conv", "ssm"):
            moved = np.abs(np.asarray(after[k]) - before[k]).reshape(
                before[k].shape[:2] + (-1,)).max(axis=-1)     # [Lm, slots]
            assert (moved[:, slot] > 0).all(), k
            others = [s for s in range(moved.shape[1]) if s not in (0, slot)]
            assert (moved[:, others] == 0).all(), k
    finally:
        eng.close()


def test_a_riding_step_is_the_prompt_prefilled_alone_and_a_plain_step(
        params):
    """Two rows mid-generation and a newcomer riding their step: the
    rows' tokens, the newcomer's first token and the whole cache (pages,
    windows, states; the scratch block and slot left out) are those of
    the newcomer prefilled alone followed by a plain decode step."""
    from concurrent.futures import Future

    from paddle_tpu.serving.generative import GenRequest

    def admitted(eng, prompt):
        req = GenRequest(prompt, 8, None, Future())
        req.blocks = eng.pool.alloc(eng.pool.blocks_for(len(prompt) + 8))
        req.slot = eng.pool.take_slot()
        return req

    got = []
    for ride in (True, False):
        eng = engine(params, kv_blocks=48)
        try:
            rows = [admitted(eng, prompt_of(n, n)) for n in (6, 13)]
            for r in rows:
                r.out.append(eng.prefill(r))
            for r, t in zip(rows, eng.decode(rows)):
                r.out.append(int(t))
            new = admitted(eng, prompt_of(9, 2))
            if ride:
                flight = eng.decode_start(rows, rider=new)
                assert flight.riding == 64
                *nxt, first = (int(t) for t in eng.decode_read(flight))
            else:
                first = eng.prefill(new)
                nxt = [int(t) for t in eng.decode(rows)]
            tree, _ = eng.cache_state()
            got.append((nxt, first, {k: np.asarray(a, np.float32)[:, 1:]
                                     for k, a in tree.items()}))
        finally:
            eng.close()
    (nxt, first, cache), (nxt_w, first_w, cache_w) = got
    assert nxt == nxt_w and first == first_w
    for k, b in cache_w.items():
        assert np.abs(b).sum() > 0
        # the rows' products run over another number of rows in the two
        # programs: float32 state to its rounding, and a bfloat16 page
        # to one unit of its last place (2**-7 relative)
        rtol = 2 ** -7 if k in ("k", "v") else 2e-5
        np.testing.assert_allclose(cache[k], b, rtol=rtol, atol=2e-6,
                                   err_msg=k)


def test_generations_through_a_server_are_batch_invariant_and_counted(
        params):
    """Two requests through ``InferenceServer.generate`` in one batch
    give their solo tokens; every slot comes back; the prompts' tokens
    are counted as scanned."""
    jobs = [(prompt_of(9, 1), 10), (prompt_of(17, 3), 5)]

    def serve(batch):
        srv = InferenceServer()
        try:
            eng = srv.load_generative("j", config(), params, warm=False,
                                      kv_blocks=40)
            futs = [srv.generate("j", p, max_new_tokens=n) for p, n in batch]
            out = [f.result(300)["tokens"] for f in futs]
            return out, eng.pool.slots_held
        finally:
            srv.close()

    solo = [serve([job])[0][0] for job in jobs]
    before = metrics.counter("serve_scan_tokens_total").value
    out, held = serve(jobs)
    assert out == solo and held == 0
    assert [len(o) for o in out] == [n for _, n in jobs]
    assert metrics.counter("serve_scan_tokens_total").value - before == sum(
        len(p) for p, _ in jobs)


# ---------------------------------------------------------------------------
# the two kernels, interpreted, against their XLA paths and by hand
# ---------------------------------------------------------------------------

def _recurrence(s0, x, dt, a, b, c):
    """The state space by hand in float64, token after token: (y, S)."""
    s = np.asarray(s0, np.float64)
    ys = []
    for t in range(len(x)):
        s = (np.exp(np.asarray(dt[t], np.float64) * np.asarray(a)) * s
             + np.asarray(b[t], np.float64)[:, None]
             * (np.asarray(dt[t], np.float64) * np.asarray(x[t]))[None])
        ys.append((s * np.asarray(c[t], np.float64)[:, None]).sum(0))
    return np.stack(ys), s


def _operands(rng, t, n=16, d=256):
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, size=(t, d)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, size=(n, d)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(t, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(t, n)), jnp.float32)
    return x, dt, a, b, c


def test_decode_state_update_is_its_xla_path_and_the_recurrence():
    """Four rows (one in the scratch slot 0): each row's slot gets its
    state moved on by one token and its new conv window; every other
    slot and layer keeps its own."""
    rng = np.random.default_rng(5)
    state = jnp.asarray(rng.normal(size=(2, 6, 16, 256)), jnp.float32)
    conv = jnp.asarray(rng.normal(size=(2, 6, 24, 32)), jnp.float32)
    window = jnp.asarray(rng.normal(size=(4, 24, 32)), jnp.float32)
    slots = jnp.asarray([3, 1, 0, 4], jnp.int32)
    x, dt, a, b, c = _operands(rng, 4)
    y, new, win = mamba1_state_update(state, conv, slots, x, dt, a, b, c,
                                      window, layer=1)
    y_k, new_k, win_k = mamba1_state_update(state, conv, slots, x, dt, a,
                                            b, c, window, layer=1,
                                            interpret=True)
    np.testing.assert_allclose(y_k, y, rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(new_k, new, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(win_k, win)
    np.testing.assert_array_equal(np.asarray(win_k)[1, [3, 1, 0, 4]],
                                  window)
    untouched = np.ones((2, 6), bool)
    untouched[1, [3, 1, 0, 4]] = False
    np.testing.assert_array_equal(np.asarray(new_k)[untouched],
                                  np.asarray(state)[untouched])
    np.testing.assert_array_equal(np.asarray(win_k)[untouched],
                                  np.asarray(conv)[untouched])
    for r, s in enumerate([3, 1, 0, 4]):
        y_h, s_h = _recurrence(state[1, s], x[r:r + 1], dt[r:r + 1], a,
                               b[r:r + 1], c[r:r + 1])
        np.testing.assert_allclose(new_k[1, s], s_h, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(y_k[r], y_h[0], rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("s_len,length", [(16, 16), (48, 37), (512, 300)])
def test_prompt_scan_is_its_xla_path_and_the_recurrence(s_len, length):
    """From a slot's state through ``length`` tokens of a bucket of
    ``s_len`` (``dt`` 0 on the padding): the state left and every
    token's ``y`` (0 on the padding) are the XLA path's and the
    recurrence's; only that slot of that layer moves.  At 512 tokens the
    kernel walks two token blocks, the second one partly."""
    rng = np.random.default_rng(s_len)
    state = jnp.asarray(rng.normal(size=(2, 5, 16, 256)), jnp.float32)
    x, dt, a, b, c = _operands(rng, s_len)
    dt = jnp.where((jnp.arange(s_len) < length)[:, None], dt, 0.0)
    y, new = selective_scan(state, 2, length, x, dt, a, b, c, layer=1)
    y_k, new_k = selective_scan(state, 2, length, x, dt, a, b, c, layer=1,
                                interpret=True)
    np.testing.assert_allclose(y_k, y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(new_k, new, rtol=1e-5, atol=1e-6)
    assert not np.asarray(y_k[length:]).any()
    y_h, s_h = _recurrence(state[1, 2], x[:length], dt[:length], a,
                           b[:length], c[:length])
    np.testing.assert_allclose(new_k[1, 2], s_h, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(y_k[:length], y_h, rtol=1e-4, atol=1e-4)
    untouched = np.ones((2, 5), bool)
    untouched[1, 2] = False
    np.testing.assert_array_equal(np.asarray(new_k)[untouched],
                                  np.asarray(state)[untouched])


def test_a_state_carried_across_two_scans_is_one_scan():
    """The first 24 tokens, then the next 24 from the state they left,
    are the 48 at once: the scan starts from the slot's state."""
    rng = np.random.default_rng(9)
    state = jnp.asarray(rng.normal(size=(1, 3, 16, 256)), jnp.float32)
    x, dt, a, b, c = _operands(rng, 48)
    y_all, s_all = selective_scan(state, 1, 48, x, dt, a, b, c,
                                  interpret=True)
    y1, mid = selective_scan(state, 1, 24, x[:24], dt[:24], a, b[:24],
                             c[:24], interpret=True)
    y2, end = selective_scan(mid, 1, 24, x[24:], dt[24:], a, b[24:],
                             c[24:], interpret=True)
    np.testing.assert_allclose(np.concatenate([y1, y2]), y_all, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(end, s_all, rtol=1e-5, atol=1e-6)
    # and a decode step from there is the scan's next token
    conv = jnp.zeros((1, 3, 24, 32), jnp.float32)
    y3, after, _ = mamba1_state_update(
        end, conv, jnp.asarray([1], jnp.int32), x[:1], dt[:1], a, b[:1],
        c[:1], jnp.zeros((1, 24, 32), jnp.float32), interpret=True)
    y4, again = selective_scan(end, 1, 1, jnp.tile(x[:1], (8, 1)),
                               jnp.pad(dt[:1], ((0, 7), (0, 0))), a,
                               jnp.tile(b[:1], (8, 1)),
                               jnp.tile(c[:1], (8, 1)), interpret=True)
    np.testing.assert_allclose(y4[0], y3[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(again, after, rtol=1e-5, atol=1e-6)
