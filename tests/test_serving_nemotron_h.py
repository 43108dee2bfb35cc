"""The served hybrid model (serving/nemotron_h.py: Mamba-2, latent
experts, grouped-query attention) through the engine, at a small size on
the CPU, against the plain reference (benchmark/lib/
reference_nemotron_h.py) with the same seeded weights.  Two kinds of
state live side by side here: pages by block table, recurrent state by
slot."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import reference_nemotron_h as ref      # noqa: E402
from paddle_tpu.kernels import moe_grouped  # noqa: E402
from paddle_tpu.kernels.moe_grouped import grouped_expert_ffn  # noqa: E402
from paddle_tpu.kernels.moe_grouped import row_tile  # noqa: E402
from paddle_tpu.kernels.ssm_update import ssm_state_update  # noqa: E402
from paddle_tpu.observability import metrics               # noqa: E402
from paddle_tpu.serving import (GenerativeEngine, InferenceServer,  # noqa: E402
                                tiny_lm)
from paddle_tpu.serving.nemotron_h import (NemotronHConfig,  # noqa: E402
                                           tiny_nemotron_h)

SEED = 7
SIZES = dict(vocab=96, hidden=64, pattern="MEM*E", n_heads=4, n_kv_heads=2,
             head_dim=16, mamba_heads=8, mamba_head_dim=8, n_groups=2,
             state=16, conv_kernel=4, chunk=8, n_experts=8, top_k=3,
             experts_held=range(2, 6), latent=32, expert_ff=48, shared_ff=80,
             scaling=2.5, time_step_min=0.001, time_step_max=0.1,
             time_step_floor=1e-4)


def config(sizes=SIZES, **kw):
    kw = dict(dict(block_size=8, max_blocks=8, max_batch=4), **kw)
    return NemotronHConfig(**{k: v for k, v in sizes.items()
                              if not k.startswith("time_step")}, **kw)


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


# ---------------------------------------------------------------------------
# against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_prompt", [5, 8, 11, 19])
def test_prefill_then_decode_through_slots_and_pages_is_the_reference(
        params, n_prompt):
    """The prompt whole (a padded bucket where it is no power of two),
    then one token a step through the slot's state and the table's
    pages: every step's logits are the reference's full forward at that
    position, to bfloat16's rounding."""
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
    # the served token is the reference's first choice or within
    # rounding of it (the cell's own measure), never a token apart
    gap = want.max(axis=-1) - want[np.arange(len(toks)), toks]
    assert gap.max() < 0.01 and (gap == 0).mean() > 0.8, gap
    np.testing.assert_allclose(np.stack(got), want[1:], atol=0.02)


def test_a_padded_prompt_bucket_leaves_the_state_of_length(params):
    """11 tokens in the bucket of 16 leave what 8 tokens in the bucket
    of 8 and three decode steps leave: the window and the state after
    ``length`` tokens, not after the bucket's end; and the chunked scan
    is the one-token update."""
    prompt = prompt_of(11)
    states = []
    for whole in (11, 8):
        eng = engine(params)
        try:
            slot, blocks = eng.pool.take_slot(), eng.pool.alloc(2)
            eng.prefill_tokens(prompt[:whole], blocks, slot=slot)
            for j in range(whole, 11):
                eng.decode_step([blocks], [j], [prompt[j]], slots=[slot])
            cache, _ = eng.cache_state()
            states.append({k: np.asarray(cache[k][:, slot])
                           for k in ("conv", "ssm")})
        finally:
            eng.close()
    assert np.abs(states[0]["ssm"]).max() > 1e-3
    for k in ("conv", "ssm"):
        np.testing.assert_allclose(states[0][k], states[1][k], rtol=2e-2,
                                   atol=2e-3, err_msg=k)


def _generate(params, jobs, **kw):
    """[(prompt, max_new)] through a server, all in flight together."""
    srv = InferenceServer()
    try:
        eng = srv.load_generative("h", config(), params, warm=False, **kw)
        futs = [srv.generate("h", p, max_new_tokens=n) for p, n in jobs]
        out = [f.result(300) for f in futs]
        return out, eng.pool.slots_held, eng.pool.free_blocks
    finally:
        srv.close()


JOBS = [(prompt_of(9, 1), 14), (prompt_of(5, 2), 4), (prompt_of(17, 3), 9),
        (prompt_of(12, 4), 6)]


@pytest.fixture(scope="module")
def solo(params):
    return [_generate(params, [job], kv_blocks=40)[0][0]["tokens"]
            for job in JOBS]


@pytest.mark.parametrize("which", range(len(JOBS)))
def test_solo_equals_batched_and_a_finished_neighbour_disturbs_nothing(
        params, solo, which):
    """Four requests of different lengths in one batch: rows finish at
    different steps, the survivors' row indices move, their slots do
    not; every request's tokens are its solo tokens."""
    out, held, _ = _generate(params, JOBS, kv_blocks=40)
    assert out[which]["tokens"] == solo[which]
    assert held == 0


def test_slots_return_on_finish_and_preemption(params, solo):
    """A pool too small for all four at once preempts; the preempted
    request takes a slot again, regenerates the same tokens, and every
    slot and block is back at the end."""
    out, held, free = _generate(params, JOBS, kv_blocks=8)
    assert [o["tokens"] for o in out] == solo
    assert sum(o["preempted"] for o in out) > 0
    assert held == 0 and free == 7


def test_a_fifth_request_waits_for_a_slot_by_count(params):
    """max_batch rows, max_batch slots: nothing waits for a slot while a
    row is free, and the gauge counts the slots held."""
    cfg = config()
    assert cfg.state_slots == cfg.max_batch + 1
    eng = engine(params)
    try:
        slots = [eng.pool.take_slot() for _ in range(cfg.max_batch)]
        assert sorted(slots) == [1, 2, 3, 4] and eng.pool.slots_held == 4
        before = metrics.snapshot()["serve_state_slot_waits_total"]["value"]
        assert eng.pool.take_slot() is None
        snap = metrics.snapshot()
        assert snap["serve_state_slot_waits_total"]["value"] == before + 1
        assert snap["serve_state_slots_held"]["value"] >= 4
        eng.pool.return_slot(slots[0])
        with pytest.raises(ValueError, match="not held"):
            eng.pool.return_slot(slots[0])
        assert eng.pool.take_slot() == slots[0]
    finally:
        eng.close()


def test_decode_counts_pairs_and_experts_hit(params):
    """The step's own counts ride its tokens back: pairs routed to held
    experts, held experts hit, and the slots they are a share of."""
    names = ("serve_moe_pairs_total", "serve_moe_experts_hit_total",
             "serve_moe_expert_slots_total", "serve_moe_tile_rows_total")
    before = {k: metrics.snapshot()[k]["value"] for k in names}
    out, _, _ = _generate(params, JOBS[:2], kv_blocks=40)
    after = {k: metrics.snapshot()[k]["value"] - before[k] for k in names}
    steps = after["serve_moe_expert_slots_total"] // (4 * 2)
    assert steps >= max(len(o["tokens"]) for o in out) - 1
    assert 0 < after["serve_moe_experts_hit_total"] <= after[
        "serve_moe_expert_slots_total"]
    assert after["serve_moe_experts_hit_total"] <= after[
        "serve_moe_pairs_total"] <= steps * 2 * 4 * SIZES["top_k"]
    # the XLA path here computes every held expert over every row
    assert after["serve_moe_pairs_total"] <= after[
        "serve_moe_tile_rows_total"]
    assert after["serve_moe_tile_rows_total"] % 4 == 0


# ---------------------------------------------------------------------------
# the share of a layer that a chip holds
# ---------------------------------------------------------------------------

def _moe_inputs():
    rng = np.random.default_rng(3)
    return jnp.asarray(rng.normal(size=(1, 24, SIZES["hidden"])), jnp.float32)


@pytest.mark.parametrize("who", ["reference", "program"])
def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_layer(who):
    """Each of four chips holds two of the eight experts, routes over all
    eight and computes its own experts' part; those four parts and the
    shared expert counted once are the uncut layer of the reference."""
    u = _moe_inputs()
    whole = dict(SIZES, experts_held=range(8), pattern="E")
    lp = {k: v.astype(jnp.float32)
          for k, v in ref.init_layer(SEED, whole, 0).items()}
    with jax.default_matmul_precision("highest"):
        want = ref.moe_block(whole, lp, u)
        shared = (ref._relu2(u[0] @ lp["s1"]) @ lp["s2"])[None]
    total = shared
    for c in range(4):
        share = dict(whole, experts_held=range(2 * c, 2 * c + 2))
        if who == "reference":
            mine = ref.init_layer(SEED, share, 0)
            # a share's experts are a slice of the same layer
            np.testing.assert_array_equal(
                np.asarray(mine["w1"], np.float32),
                np.asarray(lp["w1"][2 * c:2 * c + 2]))
            mine = {k: v.astype(jnp.float32) for k, v in mine.items()}
            with jax.default_matmul_precision("highest"):
                total = total + ref.moe_block(share, mine, u, shared=False)
        else:
            cfg = config(share)
            p = ref.stacked_params(SEED, share)
            out, pairs, hit, _ = cfg._moe(p, 0, u[0].astype(jnp.bfloat16))
            assert 0 < int(hit) <= 2 and int(hit) <= int(pairs)
            with jax.default_matmul_precision("highest"):
                routed = out - (ref._relu2(u[0] @ lp["s1"]) @ lp["s2"])
            total = total + routed[None]
    scale = float(jnp.abs(want).max())
    tol = 1e-5 if who == "reference" else 0.02
    assert float(jnp.abs(total - want).max()) < tol * scale


# ---------------------------------------------------------------------------
# what stays K/V-only refuses the model by name
# ---------------------------------------------------------------------------

def test_prefix_cache_refuses_a_model_with_state(params):
    with pytest.raises(ValueError, match="prefix cache.*NemotronHConfig"):
        engine(params, prefix_cache=True)


def test_speculation_refuses_a_model_with_state(params):
    with pytest.raises(ValueError, match="spec_k=2.*NemotronHConfig"):
        engine(params, spec_k=2, draft=tiny_lm(1))
    cfg, p = tiny_lm(3, vocab=96, d_model=32, n_heads=2, n_layers=1,
                     d_ff=64, block_size=8, max_blocks=8, max_batch=4)
    with pytest.raises(ValueError, match="draft model with per-sequence"):
        GenerativeEngine(cfg, p, kv_blocks=40, warm=False,
                         prefix_cache=False, spec_k=2,
                         draft=(config(), params))


@pytest.mark.parametrize("call", [
    lambda e: e.export_blocks([1]),
    lambda e: e.import_blocks([1], None, None),
    lambda e: e.copy_block(1, 2),
    lambda e: e.kv_pages(),
])
def test_page_migration_and_copy_refuse_a_model_with_state(params, call):
    eng = engine(params)
    try:
        with pytest.raises(ValueError, match="K/V-only.*NemotronHConfig"):
            call(eng)
        assert eng.pool.slots_held == 0
    finally:
        eng.close()


def test_a_kv_only_model_takes_no_slot_and_no_slot_operand():
    cfg, p = tiny_lm(3, vocab=64, d_model=32, n_heads=2, n_layers=1,
                     d_ff=64, block_size=8, max_blocks=4, max_batch=2)
    eng = GenerativeEngine(cfg, p, kv_blocks=12, warm=False,
                           prefix_cache=False, spec_k=0)
    try:
        assert not eng.stateful and eng.pool.state_slots == 0
        kp, vp, _ = eng.kv_pages()
        tree, _ = eng.cache_state()
        assert tree[0] is kp and tree[1] is vp
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# the two kernels, interpreted, against their XLA paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_tok,n_routed,d_ff,tm", [
    (3, None, 48, 16),
    (64, None, 48, 64),
    (300, None, 48, 128),
    # the rule's other tiles; routed over more experts than are held
    (16, 12, 48, 16),
    (40, 12, 48, 32),
    # d_ff in two chunks, and one expert's pairs over three tiles: each
    # tile fetches the expert's weights again from chunk 0
    (96, 24, 256, 32),
])
def test_grouped_expert_kernel_is_its_xla_path(n_tok, n_routed, d_ff, tm,
                                               monkeypatch):
    rng = np.random.default_rng(n_tok)
    n_l, n_e, k, d_in = 2, 6, 3, 32
    x = jnp.asarray(rng.normal(size=(n_tok, d_in)), jnp.bfloat16)
    ids = rng.integers(-1, n_e + 2, size=(n_tok, k))
    ids = np.where(ids >= n_e, -1, ids)
    if d_ff > 48:
        # a weight block of one 128-lane chunk
        monkeypatch.setattr(moe_grouped, "_W_BLOCK_BYTES", d_in * 128 * 2)
        ids[:, 0] = 0
    ids = jnp.asarray(ids, jnp.int32)
    gates = jnp.asarray(rng.uniform(size=(n_tok, k)), jnp.float32)
    w1 = jnp.asarray(rng.normal(size=(n_l, n_e, d_in, d_ff)) * 0.2,
                     jnp.bfloat16)
    w2 = jnp.asarray(rng.normal(size=(n_l, n_e, d_ff, d_in)) * 0.2,
                     jnp.bfloat16)
    assert row_tile(n_tok * k, n_e, n_routed) == tm
    counts = np.bincount(np.asarray(ids).ravel() + 1, minlength=n_e + 1)[1:]
    if d_ff > 48:
        assert moe_grouped._ff_chunks(d_in, d_ff, 2) == 2
        assert counts.max() > tm
    want, hit, rows = grouped_expert_ffn(x, ids, gates, w1, w2, layer=1,
                                         n_routed=n_routed)
    got, hit_k, rows_k = grouped_expert_ffn(x, ids, gates, w1, w2, layer=1,
                                            n_routed=n_routed,
                                            interpret=True)
    assert int(hit) == int(hit_k) == len(set(np.asarray(ids).ravel()) - {-1})
    # the XLA path computes every held expert over every row; the kernel
    # its real tiles
    assert int(rows) == n_e * n_tok
    assert int(rows_k) == tm * int(np.sum(-(-counts // tm)))
    np.testing.assert_allclose(got, want, atol=1e-5 * float(
        jnp.abs(want).max()))
    # an expert nobody chose is never read: its weights may be anything
    dead = [e for e in range(n_e) if e not in np.asarray(ids)]
    if dead:
        poisoned = w1.at[1, dead[0]].set(jnp.nan)
        again, _, _ = grouped_expert_ffn(x, ids, gates, poisoned, w2,
                                         layer=1, n_routed=n_routed,
                                         interpret=True)
        np.testing.assert_array_equal(again, got)


@pytest.mark.parametrize("rows,tm", [
    (64, 16),          # a decode step: the program it always ran
    (64 + 64, 32),     # a decode step carrying a prompt of each bucket
    (64 + 128, 32),
    (64 + 256, 32),
    (64 + 512, 64),
    (512, 64),         # a 512-token prompt into an empty batch
    (2048, 128),
])
def test_row_tile_follows_the_pairs_a_held_expert_expects(rows, tm):
    # the hybrid cell: top-22 of 512 routed experts, 128 held here
    assert row_tile(rows * 22, 128, 512) == tm
    # where every routed expert is held, n_routed defaults to them
    assert row_tile(rows * 22, 512) == tm


@pytest.mark.parametrize("h,p,n,g", [
    (8, 8, 16, 2),      # a group's 32 lanes
    (8, 32, 16, 2),     # a group a whole lane tile (per * P = 128)
    (8, 64, 8, 2),      # a group two lane tiles
    (4, 8, 16, 2),      # tiny_nemotron_h's widths
])
def test_state_update_kernel_is_its_xla_path_and_touches_its_slots_only(
        h, p, n, g):
    rng = np.random.default_rng(5)
    n_l, n_s, r = 2, 6, 4
    state = jnp.asarray(rng.normal(size=(n_l, n_s, n, h * p)), jnp.float32)
    slots = jnp.asarray([3, 1, 0, 4], jnp.int32)
    x = jnp.asarray(rng.normal(size=(r, h, p)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, size=(r, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1, 16, size=(h,)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(r, g, n)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(r, g, n)), jnp.float32)
    y, new = ssm_state_update(state, slots, x, dt, a, b, c, layer=1)
    y_k, new_k = ssm_state_update(state, slots, x, dt, a, b, c, layer=1,
                                  interpret=True)
    np.testing.assert_allclose(y_k, y, atol=1e-5)
    np.testing.assert_allclose(new_k, new, atol=1e-6)
    untouched = np.ones((n_l, n_s), bool)
    untouched[1, [3, 1, 0, 4]] = False
    np.testing.assert_array_equal(np.asarray(new_k)[untouched],
                                  np.asarray(state)[untouched])
    # by hand, one row: S <- exp(dt A) S + dt x (x) B; y = S C, with a
    # slot's [N, H P] read as [H, P, N]
    s0 = np.asarray(state[1, 3], np.float64).reshape(n, h, p).transpose(
        1, 2, 0)
    bh = np.repeat(np.asarray(b[0], np.float64), h // g, axis=0)
    ch = np.repeat(np.asarray(c[0], np.float64), h // g, axis=0)
    s1 = (np.exp(np.asarray(dt[0] * a, np.float64))[:, None, None] * s0
          + (np.asarray(dt[0], np.float64)[:, None] * np.asarray(x[0]))[
              ..., None] * bh[:, None, :])
    np.testing.assert_allclose(new[1, 3], s1.transpose(2, 0, 1).reshape(
        n, h * p), atol=1e-5)
    np.testing.assert_allclose(y[0], np.einsum("hpn,hn->hp", s1, ch),
                               atol=1e-4)


@pytest.mark.parametrize("length", [7, 11])
def test_a_scanned_prompt_and_a_decoded_token_leave_the_scan_of_both(length):
    """The two Mamba-2 forms agree through the slot: a prompt of
    ``length`` scanned, then its next token through the decode step,
    leave the slot's window and state that the prompt of ``length + 1``
    scanned whole leaves (a transposition between the scan's state and
    the step's layout would not)."""
    cfg, p = tiny_nemotron_h(3)
    rng = np.random.default_rng(length)
    s_len, l, slot = 16, 1, 2
    xbc = jnp.asarray(rng.normal(size=(s_len, cfg.conv_dim)), jnp.float32)
    dt = jnp.asarray(rng.normal(size=(s_len, cfg.mamba_heads)), jnp.float32)
    zero = {k: jnp.zeros(v.shape, v.dtype)
            for k, v in cfg.cache_spec(4).items() if k in ("conv", "ssm")}
    stepped = dict(zero)
    cfg._mamba_scan(p, l, xbc, dt, stepped, length, slot)
    cfg._mamba_step(p, l, xbc[length:length + 1], dt[length:length + 1],
                    stepped, jnp.asarray([slot], jnp.int32))
    whole = dict(zero)
    cfg._mamba_scan(p, l, xbc, dt, whole, length + 1, slot)
    assert np.abs(np.asarray(whole["ssm"][l, slot])).max() > 1e-2
    for k in ("conv", "ssm"):
        np.testing.assert_allclose(stepped[k], whole[k], atol=1e-5,
                                   err_msg=k)


def test_tiny_family_serves_end_to_end():
    """``tiny_nemotron_h``: the seeded little hybrid of the docs and the
    tools, greedy and deterministic through a server."""
    cfg, p = tiny_nemotron_h(5)
    outs = []
    for _ in range(2):
        srv = InferenceServer()
        try:
            srv.load_generative("t", cfg, p, kv_blocks=24, warm=False)
            outs.append(srv.generate("t", [1, 2, 3, 4, 5],
                                     max_new_tokens=6).result(300)["tokens"])
        finally:
            srv.close()
    assert outs[0] == outs[1] and len(outs[0]) == 6
