#!/usr/bin/env python
"""Serving-tier load harness (ISSUE 9): open-loop Poisson arrivals
against the continuous-batching InferenceServer, with a single-request-
at-a-time floor to quantify the batching win, and a hot model swap
under load asserting zero dropped requests.

Phases (all on the CPU tier unless JAX_PLATFORMS says otherwise):
  floor      closed-loop serial predict() through a max_batch=1,
             max_wait=0 server — what one request at a time sustains.
             This is the Clipper no-batching baseline.
  saturated  bounded-window pipelined submits (the capacity probe):
             the max QPS the batcher reaches when arrivals never gate.
  poisson    open-loop Poisson arrivals at ``--rate-x`` times the floor
             QPS (open-loop = every arrival is an independent simulated
             client; completions are recorded via future callbacks so a
             slow server cannot gate the arrival process).  Halfway
             through, ``swap()`` flips the tenant to a second model
             version built from different parameters — every request
             must complete and classify bit-clean as served by exactly
             one version (zero dropped, zero torn).

Output: ONE JSON line (``--out FILE`` also writes it to a file —
SERVE_BENCH.json in the repo ledger), including the batch-occupancy
histogram and the queue-wait/assemble/dispatch phase breakdown from
the always-on metrics registry, plus the aot_load_fallback_total
counter (a fleet quietly re-jitting is visible here, not only in
stderr).  ``--quick`` shrinks everything to a seconds-long tier-1
smoke (wired like pserver_bench --quick).  Set FLAGS_telemetry=1 and
FLAGS_telemetry_dump_dir to get the serve.batch/assemble/dispatch
spans into tools/trace_report.py.
"""
import argparse
import json
import os
import random
import sys
import threading
import time

# a CPU tool: its rates are host-side scheduling evidence, never chip
# numbers (the chip path is chip_smoke.py / the benchmark cells)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np

# model dims (env-overridable like pserver_bench): heavy enough that
# the single-request floor pays real per-dispatch compute — the
# batching win being measured is amortization of exactly that
D_IN = int(os.environ.get("SVB_D_IN", "128"))
HIDDEN = int(os.environ.get("SVB_HIDDEN", "512"))
D_OUT = int(os.environ.get("SVB_D_OUT", "32"))


def _device_fields():
    """The device the run was on, as jax reports it."""
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind}


def _build_and_save(dirname, seed, max_batch):
    """Save one model version; ``seed`` differentiates the parameter
    draw so the swap phase can classify which engine served each
    request (constant inits would be degenerate: softmax over equal
    logits answers uniform for every version)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.scope import Scope

    main, startup = fluid.Program(), fluid.Program()
    scope = Scope()
    init = fluid.initializer.UniformInitializer
    with fluid.scope_guard(scope):
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data(name="x", shape=[D_IN],
                                      dtype="float32")
                h = fluid.layers.fc(
                    x, size=HIDDEN, act="tanh",
                    param_attr=fluid.ParamAttr(
                        initializer=init(-0.08, 0.08, seed=seed)))
                h = fluid.layers.fc(
                    h, size=HIDDEN, act="tanh",
                    param_attr=fluid.ParamAttr(
                        initializer=init(-0.08, 0.08, seed=seed + 1)))
                out = fluid.layers.fc(
                    h, size=D_OUT, act="softmax",
                    param_attr=fluid.ParamAttr(
                        initializer=init(-0.08, 0.08, seed=seed + 2)))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(
            dirname, ["x"], [out], exe, main_program=main,
            aot_feed_specs={"x": ((1, D_IN), "float32")})


def _pctl(vals, p):
    from paddle_tpu.observability.metrics import nearest_rank

    return nearest_rank(sorted(vals), p)


def _lat_ms(vals):
    return {"p50_ms": round(_pctl(vals, 50) * 1e3, 3),
            "p90_ms": round(_pctl(vals, 90) * 1e3, 3),
            "p99_ms": round(_pctl(vals, 99) * 1e3, 3)}


def _measure_floor(model_dir, x, seconds):
    """Single-request-at-a-time QPS: serial closed loop, no batching
    (max_batch=1), no coalesce wait (max_wait=0)."""
    from paddle_tpu.serving import InferenceServer

    lats = []
    with InferenceServer(max_batch=1, max_wait_us=0) as srv:
        srv.load("m", model_dir)
        for _ in range(10):
            srv.predict("m", {"x": x})
        t_end = time.perf_counter() + seconds
        n = 0
        t0 = time.perf_counter()
        while time.perf_counter() < t_end:
            t = time.perf_counter()
            srv.predict("m", {"x": x})
            lats.append(time.perf_counter() - t)
            n += 1
        wall = time.perf_counter() - t0
    return dict(qps=round(n / wall, 1), n=n, **_lat_ms(lats))


def _measure_saturated(srv, x, seconds, window):
    """Capacity probe: keep ``window`` requests in flight."""
    from collections import deque

    done = []
    lock = threading.Lock()

    def _done_cb(t0):
        def cb(fut):
            fut.result()
            with lock:
                done.append(time.perf_counter() - t0)
        return cb

    for _ in range(5):
        srv.predict("m", {"x": x})
    inflight = deque()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    n = 0
    while time.perf_counter() < t_end:
        while len(inflight) >= window:
            inflight.popleft().result()
        t = time.perf_counter()
        fut = srv.submit("m", {"x": x})
        fut.add_done_callback(_done_cb(t))
        inflight.append(fut)
        n += 1
    for f in inflight:
        f.result(60)
    wall = time.perf_counter() - t0
    with lock:
        lats = list(done)
    return dict(qps=round(n / wall, 1), n=n, window=window,
                **_lat_ms(lats))


def _poisson(srv, x, ref_v1, seconds, rate, seed=7, swap_to=None,
             swap_at=0.5):
    """Open-loop arrivals at ``rate``/s; with ``swap_to`` set, swap the
    tenant to that model dir at ``swap_at`` x seconds.  Returns stats +
    the zero-dropped/zero-torn classification."""
    rng = random.Random(seed)
    results = []     # (latency_s, output ndarray) via callbacks
    lock = threading.Lock()
    errors = []

    def _cb(t0):
        def cb(fut):
            t = time.perf_counter() - t0
            try:
                out = next(iter(fut.result().values()))
            except Exception as e:       # a dropped request
                with lock:
                    errors.append(repr(e))
                return
            with lock:
                results.append((t, np.asarray(out)))
        return cb

    swap_state = {}

    def _swapper():
        time.sleep(seconds * swap_at)
        t0 = time.perf_counter()
        srv.swap("m", swap_to)
        swap_state["swap_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 2)

    swapper = None
    if swap_to is not None:
        swapper = threading.Thread(target=_swapper, daemon=True)
        swapper.start()
    n = 0
    t0 = time.perf_counter()
    next_t = t0
    t_end = t0 + seconds
    while next_t < t_end:
        # sleep, never spin: a spinning arrival thread starves the
        # dispatcher of the GIL and manufactures an overload that is
        # the harness's, not the server's.  Oversleep just lowers the
        # realized rate — reported from the actual submission count.
        gap = next_t - time.perf_counter()
        if gap > 0:
            time.sleep(gap)
        t = time.perf_counter()
        fut = srv.submit("m", {"x": x})
        fut.add_done_callback(_cb(t))
        n += 1
        next_t += rng.expovariate(rate)
    if swapper is not None:
        swapper.join(timeout=120)
    # drain: every submitted request must complete
    deadline = time.perf_counter() + 120
    while time.perf_counter() < deadline:
        with lock:
            if len(results) + len(errors) >= n:
                break
        time.sleep(0.01)
    wall = time.perf_counter() - t0
    ref_v2 = np.asarray(next(iter(
        srv.predict("m", {"x": x}).values())))
    with lock:
        lats = [r[0] for r in results]
        v1 = sum(1 for _, o in results
                 if np.allclose(o, ref_v1, atol=1e-5))
        v2 = 0 if swap_to is None else sum(
            1 for _, o in results
            if np.allclose(o, ref_v2, atol=1e-5))
        completed = len(results)
        n_err = len(errors)
    torn = completed - v1 - v2
    stats = dict(
        offered_qps=round(rate, 1), qps=round(completed / wall, 1),
        n_requests=n, n_simulated_clients=n, completed=completed,
        duration_s=round(wall, 2), **_lat_ms(lats))
    if swap_to is None:
        return stats, dict(zero_dropped=(completed == n and not n_err),
                           dropped=n - completed, errors=errors[:5])
    return stats, dict(
        zero_dropped=(completed == n and n_err == 0),
        dropped=n - completed, errors=errors[:5],
        served_v1=v1, served_v2=v2, torn=torn,
        swap_ms=swap_state.get("swap_ms"))


# ---------------------------------------------------------------------------
# Generate mode (ISSUE 11): token-level decode under Poisson arrivals
# ---------------------------------------------------------------------------

# bench LM (env-overridable): dims sized so int8 weight quantization
# holds greedy-token parity with a measured margin certificate (min
# top-2 logit margin > max |logit delta| at every one of >= 64 steps —
# scanned over seeds; SVB_GEN_SEED=3 is the certified draw)
GEN_VOCAB = int(os.environ.get("SVB_GEN_VOCAB", "64"))
GEN_DMODEL = int(os.environ.get("SVB_GEN_DMODEL", "128"))
GEN_HEADS = int(os.environ.get("SVB_GEN_HEADS", "4"))
GEN_LAYERS = int(os.environ.get("SVB_GEN_LAYERS", "3"))
GEN_DFF = int(os.environ.get("SVB_GEN_DFF", "256"))
GEN_SEED = int(os.environ.get("SVB_GEN_SEED", "3"))
GEN_BLOCK = int(os.environ.get("SVB_GEN_BLOCK", "16"))
GEN_MAX_BLOCKS = int(os.environ.get("SVB_GEN_MAX_BLOCKS", "8"))


def _gen_cfg(max_batch, kv_blocks):
    from paddle_tpu.serving import tiny_lm

    cfg, params = tiny_lm(GEN_SEED, vocab=GEN_VOCAB, d_model=GEN_DMODEL,
                          n_heads=GEN_HEADS, n_layers=GEN_LAYERS,
                          d_ff=GEN_DFF, block_size=GEN_BLOCK,
                          max_blocks=GEN_MAX_BLOCKS,
                          max_batch=max_batch)
    return cfg, params, int(kv_blocks)


def _gen_prompts(rng, n, lo=4, hi=24):
    return [rng.randint(0, GEN_VOCAB, size=rng.randint(lo, hi))
            .tolist() for _ in range(n)]


def _gen_floor(srv, prompt, max_new):
    """Single-sequence closed loop: solo decode rate — the no-batching
    baseline the continuous decode batch amortizes against.  One
    unmeasured warm-up generation first: a cold engine's first solo
    pass kicks the narrow (1, nb) decode-bucket background compiles,
    and those would contend with the measured loop for host CPU."""
    srv.generate("g", prompt, max_new_tokens=max_new).result(300)
    time.sleep(0.3)      # let stragglers of the bucket compiles land
    t0 = time.perf_counter()
    res = srv.generate("g", prompt, max_new_tokens=max_new).result(300)
    wall = time.perf_counter() - t0
    itl = sorted(res["itl_ms"])
    return {"tokens": len(res["tokens"]),
            "tokens_s": round(len(res["tokens"]) / wall, 1),
            "ttft_ms": round(res["ttft_ms"], 3),
            "itl_p50_ms": round(_pctl(itl, 50), 3),
            "itl_p99_ms": round(_pctl(itl, 99), 3)}


def _gen_capacity(srv, prompts, max_new):
    """Full-batch token throughput: submit a closed wave and measure
    tokens/s — calibrates the Poisson offered rate."""
    t0 = time.perf_counter()
    futs = [srv.generate("g", p, max_new_tokens=max_new)
            for p in prompts]
    toks = sum(len(f.result(600)["tokens"]) for f in futs)
    wall = time.perf_counter() - t0
    return toks / wall


def _gen_poisson(srv, prompts, max_new, seconds, rate_rps, seed=17):
    """Open-loop Poisson generate arrivals at ``rate_rps``; returns
    (stats, per-request results).  Same sleep-don't-spin arrival
    process as the predict phases; completions via future callbacks."""
    rng = random.Random(seed)
    results, errors = [], []
    lock = threading.Lock()

    def _cb(fut):
        try:
            r = fut.result()
        except Exception as e:
            with lock:
                errors.append(repr(e))
            return
        with lock:
            results.append(r)

    n = 0
    t0 = time.perf_counter()
    next_t = t0
    t_end = t0 + seconds
    while next_t < t_end:
        gap = next_t - time.perf_counter()
        if gap > 0:
            time.sleep(gap)
        fut = srv.generate("g", prompts[n % len(prompts)],
                           max_new_tokens=max_new)
        fut.add_done_callback(_cb)
        n += 1
        next_t += rng.expovariate(rate_rps)
    deadline = time.perf_counter() + 300
    while time.perf_counter() < deadline:
        with lock:
            if len(results) + len(errors) >= n:
                break
        time.sleep(0.01)
    wall = time.perf_counter() - t0
    with lock:
        done = list(results)
        errs = list(errors)
    toks = sum(len(r["tokens"]) for r in done)
    ttfts = sorted(r["ttft_ms"] for r in done)
    itls = sorted(v for r in done for v in r["itl_ms"])
    stats = {
        "offered_rps": round(rate_rps, 2),
        "n_requests": n, "completed": len(done),
        "duration_s": round(wall, 2),
        "tokens": toks,
        "tokens_s": round(toks / wall, 1),
        "ttft_p50_ms": round(_pctl(ttfts, 50), 3),
        "ttft_p99_ms": round(_pctl(ttfts, 99), 3),
        "itl_p50_ms": round(_pctl(itls, 50), 3),
        "itl_p99_ms": round(_pctl(itls, 99), 3),
        "preempted_requests": sum(1 for r in done if r["preempted"]),
    }
    return stats, {"zero_dropped": len(done) == n and not errs,
                   "dropped": n - len(done), "errors": errs[:5]}


def _gen_int8_parity(max_batch, kv_blocks, steps):
    """Greedy-token parity fp32 vs int8-quantized decode, closed loop
    over ``steps`` tokens, with the logit-margin certificate: at every
    step of the (matched) trajectory the fp32 top-2 margin must exceed
    the worst fp32-vs-int8 logit delta — token parity then holds with
    measured headroom, not by luck."""
    from concurrent.futures import Future

    from paddle_tpu.serving.batcher import TokenScheduler
    from paddle_tpu.serving.generative import (GenRequest,
                                               GenerativeEngine)

    cfg, params, kv = _gen_cfg(max_batch, kv_blocks)
    prompt = np.random.RandomState(1000 + GEN_SEED) \
        .randint(0, GEN_VOCAB, size=12).tolist()

    def run(quant):
        eng = GenerativeEngine(cfg, params, quant=quant, kv_blocks=kv,
                               name="parity-" + (quant or "fp32"),
                               warm=False)
        req = GenRequest(prompt, steps, None, Future())
        try:
            req.blocks = eng.pool.alloc(
                eng.pool.blocks_for(len(prompt)))
            out = [eng.prefill(req)]
            req.out = out
            sched = TokenScheduler(eng.pool, cfg.max_batch)
            logits = []
            while len(out) < steps:
                cap = len(req.blocks) * cfg.block_size
                if req.context_len >= cap:
                    sched.grow(req)
                t, lg = eng.decode([req], with_logits=True)
                logits.append(lg[0])
                out.append(int(t[0]))
            return out, logits
        finally:
            eng.free_sequence(req)
            eng.close()

    tf, lf = run("")
    tq, lq = run("int8")
    n_match = sum(a == b for a, b in zip(tf, tq))
    deltas = [float(np.abs(a - b).max()) for a, b in zip(lf, lq)]
    margins = []
    for a in lf:
        srt = np.sort(a)[::-1]
        margins.append(float(srt[0] - srt[1]))
    parity_ok = n_match == steps
    return {
        "steps": steps,
        "token_parity": "%d/%d" % (n_match, steps),
        "parity_ok": parity_ok,
        # the logit certificate covers the DECODE steps (steps - 1):
        # the first token comes from the prefill dispatch, which is
        # token-compared above but exposes no logits
        "certified_decode_steps": len(deltas),
        "max_logit_delta": round(max(deltas), 5) if deltas else 0.0,
        "min_top2_margin": round(min(margins), 5) if margins else 0.0,
        "certified": bool(parity_ok and deltas
                          and min(margins) > max(deltas)),
        "quantized": "wqkv/wo/w1/w2 int8 per-chunk symmetric "
                     "(compress.quantize_symmetric); embed/pos/"
                     "lm_head/LN fp32",
    }


# ---------------------------------------------------------------------------
# Prefix-cache + speculative phases (ISSUE 19)
# ---------------------------------------------------------------------------

# speculative bench LM (env-overridable): 3 layers whose layer 0 is
# dimension-shared with the 1-layer draft; the two DEEP layers carry a
# fat (SVB_SPEC_FAT-wide) MLP whose outputs are damped by
# SVB_SPEC_DAMP, so the draft predicts the target's greedy argmax at
# ~0.95+ acceptance while the target pays ~6x the draft's FLOPs — the
# regime speculative decoding exists for (cheap proposer, expensive
# verifier), scaled to a CI-sized model.  SVB_SPEC_DAMP=0.002 is the
# certified draw: smaller perturbations leave the argmax unmoved on
# most steps without making the deep layers a no-op
SPEC_VOCAB = int(os.environ.get("SVB_SPEC_VOCAB", "128"))
SPEC_DMODEL = int(os.environ.get("SVB_SPEC_DMODEL", "256"))
SPEC_HEADS = int(os.environ.get("SVB_SPEC_HEADS", "4"))
SPEC_FAT = int(os.environ.get("SVB_SPEC_FAT", "8192"))
SPEC_DAMP = float(os.environ.get("SVB_SPEC_DAMP", "0.002"))
SPEC_K = int(os.environ.get("SVB_SPEC_K", "8"))
SPEC_SEED = int(os.environ.get("SVB_SPEC_SEED", "3"))
SPEC_MAX_NEW = int(os.environ.get("SVB_SPEC_MAX_NEW", "60"))


def _spec_lm(max_batch=4, fat=None):
    """(cfg, params, draft_cfg, draft_params) for the speculative
    bench: target = 3 layers (layer 0 thin, deep layers fat and
    damped); draft = layer 0 plus the shared embedding/head — a strict
    parameter subset, so draft quality comes from the damping, not
    from any training step the bench would have to carry."""
    import re as _re

    from paddle_tpu.serving import tiny_lm
    from paddle_tpu.serving.generative import LMConfig

    kw = dict(vocab=SPEC_VOCAB, d_model=SPEC_DMODEL,
              n_heads=SPEC_HEADS, n_layers=3, d_ff=256,
              block_size=GEN_BLOCK, max_blocks=GEN_MAX_BLOCKS,
              max_batch=max_batch)
    cfg, params = tiny_lm(SPEC_SEED, **kw)
    fat = SPEC_FAT if fat is None else fat
    rng = np.random.RandomState(99)
    for layer in (1, 2):
        params["l%d.w1" % layer] = (
            rng.randn(SPEC_DMODEL, fat) * 0.1).astype(np.float32)
        params["l%d.w2" % layer] = (
            rng.randn(fat, SPEC_DMODEL) * 0.1 * SPEC_DAMP
        ).astype(np.float32)
        params["l%d.wo" % layer] = params["l%d.wo" % layer] * SPEC_DAMP
    dcfg = LMConfig(**dict(kw, n_layers=1))
    dparams = {k: v for k, v in params.items()
               if not _re.match(r"l[0-9]+\.", k)
               or k.startswith("l0.")}
    return cfg, params, dcfg, dparams


def _solo_loop(eng, cfg, prompt, max_new, spec=False):
    """Closed-loop single-sequence generation at the engine level (no
    server thread in the measured path): the solo decode floor both
    spec numbers quote.  Returns (tokens, rounds) where ``rounds``
    carries the per-round accepted-draft counts when ``spec``."""
    from concurrent.futures import Future

    from paddle_tpu.serving.batcher import TokenScheduler
    from paddle_tpu.serving.generative import GenRequest

    k = eng.spec_k
    req = GenRequest(prompt, max_new, None, Future())
    req.blocks = eng.pool.alloc(eng.pool.blocks_for(len(prompt)))
    req.out.append(eng.prefill(req))
    sched = TokenScheduler(eng.pool, cfg.max_batch)
    rounds = []
    need = (k + 1) if spec else 1
    while len(req.out) < max_new \
            and req.context_len + need <= cfg.max_seq:
        cap = len(req.blocks) * cfg.block_size
        while req.context_len + need > cap:
            if not sched.grow(req):
                raise RuntimeError("kv pool exhausted")
            cap += cfg.block_size
        if spec:
            toks = eng.spec_decode([req])[0]
            rounds.append(len(toks) - 1)
            for t in toks:
                if len(req.out) < max_new:
                    req.out.append(int(t))
        else:
            req.out.append(int(eng.decode([req])[0]))
    toks = list(req.out)
    eng.free_sequence(req)
    return toks, rounds


def _gen_spec_parity(steps, k=None, fat=None):
    """Greedy-parity certificate for speculative decoding (the ISSUE
    19 extension of the int8 certificate): the spec engine's token
    stream must be BIT-IDENTICAL to plain greedy decode on the same
    LM, and the per-round acceptance accounting must add up exactly —
    every emitted token is either a verified draft token or the verify
    pass's own correction/bonus token, so the emitted count equals
    1 (prefill) + sum(m_i + 1) over rounds, modulo the final-round
    max_new cap.  The measured accept-rate rides the record as an
    efficiency number; it is never a correctness input."""
    from paddle_tpu.serving.generative import GenerativeEngine

    k = SPEC_K if k is None else k
    cfg, params, dcfg, dparams = _spec_lm(fat=fat)
    prompt = np.random.RandomState(1000 + SPEC_SEED) \
        .randint(0, SPEC_VOCAB, size=8).tolist()
    eng = GenerativeEngine(cfg, params, kv_blocks=64, warm=False,
                           name="specparity-plain", prefix_cache=False,
                           spec_k=0)
    try:
        plain, _ = _solo_loop(eng, cfg, prompt, steps)
    finally:
        eng.close()
    eng = GenerativeEngine(cfg, params, kv_blocks=64, warm=False,
                           name="specparity", prefix_cache=False,
                           spec_k=k, draft=(dcfg, dparams))
    try:
        spec, rounds = _solo_loop(eng, cfg, prompt, steps, spec=True)
    finally:
        eng.close()
    n = min(len(plain), len(spec))
    identical = bool(plain[:n] == spec[:n] and n == steps)
    accepted = sum(rounds)
    proposed = k * len(rounds)
    emitted = 1 + accepted + len(rounds)
    accounting_ok = len(spec) <= emitted <= len(spec) + k
    return {
        "steps": steps, "k": k,
        "token_parity": "%d/%d" % (
            sum(a == b for a, b in zip(plain, spec)), n),
        "identical": identical,
        "rounds": len(rounds), "accepted": accepted,
        "proposed": proposed,
        "accept_rate": round(accepted / proposed, 4) if proposed
        else 0.0,
        "accounting_ok": bool(accounting_ok),
        "certified": bool(identical and accounting_ok),
        "acceptance": "greedy longest-matching-prefix + correction "
                      "token (lossless for greedy decode by "
                      "construction; this record MEASURES it)",
    }


def _run_spec(quick):
    """Solo-floor speculative phase: plain greedy tokens/s vs
    spec-decode tokens/s on the same LM and prompt, best-of-N closed
    loops after an unmeasured warm-up (engine compiles land there).
    Accept-rate and draft-overhead come from the serve_spec_* metric
    counters, so the observable numbers are also smoke-tested."""
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving.generative import GenerativeEngine

    fat = int(os.environ.get("SVB_SPEC_FAT_QUICK", "512")) if quick \
        else SPEC_FAT
    k = min(SPEC_K, 4) if quick else SPEC_K
    max_new = 24 if quick else SPEC_MAX_NEW
    trials = 2 if quick else 3
    cfg, params, dcfg, dparams = _spec_lm(fat=fat)
    prompt = np.random.RandomState(1000 + SPEC_SEED) \
        .randint(0, SPEC_VOCAB, size=8).tolist()

    def best_of(fn):
        fn()
        # the warm pass above absorbed the engine compiles — rebase
        # the spec timing counters so draft-overhead reflects steady
        # state, not jit time
        metrics.zero_all()
        best, out = None, None
        for _ in range(trials):
            t0 = time.perf_counter()
            out = fn()
            dt = time.perf_counter() - t0
            best = dt if best is None or dt < best else best
        return out, best

    eng = GenerativeEngine(cfg, params, kv_blocks=64, warm=False,
                           name="specbench-plain", prefix_cache=False,
                           spec_k=0)
    try:
        plain_toks, dt_p = best_of(
            lambda: _solo_loop(eng, cfg, prompt, max_new)[0])
    finally:
        eng.close()
    plain_tps = len(plain_toks) / dt_p

    eng = GenerativeEngine(cfg, params, kv_blocks=64, warm=False,
                           name="specbench", prefix_cache=False,
                           spec_k=k, draft=(dcfg, dparams))
    try:
        spec_toks, dt_s = best_of(
            lambda: _solo_loop(eng, cfg, prompt, max_new,
                               spec=True)[0])
    finally:
        eng.close()
    spec_tps = len(spec_toks) / dt_s
    snap = metrics.snapshot()

    def _c(name):
        ent = snap.get(name)
        return ent["value"] if ent else 0

    proposed = _c("serve_spec_proposed_total")
    accepted = _c("serve_spec_accepted_total")
    draft_us = _c("serve_spec_draft_us_total")
    verify_us = _c("serve_spec_verify_us_total")
    accept = accepted / proposed if proposed else 0.0
    overhead = draft_us / (draft_us + verify_us) \
        if draft_us + verify_us else 0.0
    cert = _gen_spec_parity(
        int(os.environ.get("SVB_SPEC_PARITY_STEPS",
                           "24" if quick else "48")), k=k, fat=fat)
    speedup = round(spec_tps / max(plain_tps, 1e-9), 2)
    # quick runs keep the parity guarantee but only a collapse floor
    # on speed — a seconds-long smoke is not a perf measurement
    floor_x = float(os.environ.get("SVB_SPEC_FLOOR_X",
                                   "0.5" if quick else "2.0"))
    return {
        "model": {"vocab": SPEC_VOCAB, "d_model": SPEC_DMODEL,
                  "n_heads": SPEC_HEADS, "n_layers": 3,
                  "d_ff_thin": 256, "d_ff_fat": fat,
                  "deep_damp": SPEC_DAMP, "seed": SPEC_SEED,
                  "draft": "layer 0 + embed/head (1 layer)"},
        "k": k, "max_new_tokens": max_new, "trials": trials,
        "plain": {"tokens": len(plain_toks),
                  "tokens_s": round(plain_tps, 1)},
        "spec": {"tokens": len(spec_toks),
                 "tokens_s": round(spec_tps, 1),
                 "rounds": _c("serve_spec_rounds_total"),
                 "accept_rate": round(accept, 4),
                 "draft_overhead_pct": round(100.0 * overhead, 1),
                 "draft_us": draft_us, "verify_us": verify_us},
        "speedup_vs_plain": speedup,
        "floor_x": floor_x,
        "parity": cert,
        "ok": bool(cert["certified"] and speedup >= floor_x),
    }


PFX_USERS = int(os.environ.get("SVB_PFX_USERS", "12"))
PFX_SHARED = int(os.environ.get("SVB_PFX_SHARED", "120"))
# wider MLP than the generate-phase LM: prefill must be COMPUTE-bound
# for the suffix-only dispatch to show its win — on the CPU fallback
# the paged K/V gather costs rows x max_blocks regardless of how many
# tokens were cached, so a skinny model measures the gather, not the
# avoided FLOPs
PFX_DFF = int(os.environ.get("SVB_PFX_DFF", "2048"))


def _run_prefix(quick):
    """Multi-tenant shared-prefix trace: ``users`` tenants whose
    prompts share a long system prefix, swept over 80/90/95% shared
    mixes, prefix cache OFF vs ON.  Reports the prefill FLOPs avoided
    (from the serve_prefix_tokens_* counters — prefill compute is
    linear in tokens actually computed), TTFT p50 both ways, and the
    peak KV bytes per user (shared blocks count ONCE under refcount
    semantics).  Each mode runs one unmeasured warm trace first so
    bucket compiles never land inside a measured TTFT; the shared
    prefix is deliberately block-unaligned so the partial-tail
    copy-on-write path is on the measured path, not just in tests."""
    from concurrent.futures import Future

    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import tiny_lm
    from paddle_tpu.serving.generative import (GenRequest,
                                               GenerativeEngine)

    users = 6 if quick else PFX_USERS
    shared_len = PFX_SHARED
    # prompts run ~150 tokens (shared prefix + per-tenant suffix), so
    # the prefix phase carries its own max_blocks rather than the
    # generate phase's 8-block sequences
    max_blocks = 16
    kv = int(os.environ.get(
        "SVB_PFX_KV_BLOCKS", "72" if quick else "160"))
    rng = np.random.RandomState(21)
    shared = rng.randint(0, GEN_VOCAB, size=shared_len).tolist()
    cfg, params = tiny_lm(GEN_SEED, vocab=GEN_VOCAB,
                          d_model=GEN_DMODEL, n_heads=GEN_HEADS,
                          n_layers=GEN_LAYERS, d_ff=PFX_DFF,
                          block_size=GEN_BLOCK, max_blocks=max_blocks,
                          max_batch=4)
    block_bytes = cfg.n_layers * 2 * cfg.block_size * cfg.d_model * 4

    def run_mode(prompts, on):
        eng = GenerativeEngine(cfg, params, kv_blocks=kv, warm=False,
                               name="pfx-%s" % ("on" if on else "off"),
                               prefix_cache=on, spec_k=0)
        try:
            def trace():
                reqs, ttfts, firsts = [], [], []
                for p in prompts:
                    req = GenRequest(p, 4, None, Future())
                    t0 = time.perf_counter()
                    if eng.prefix_cache is not None:
                        if not eng.prefix_cache.acquire(req):
                            raise RuntimeError("prefix admission "
                                               "failed")
                    else:
                        req.blocks = eng.pool.alloc(
                            eng.pool.blocks_for(len(p)))
                        if req.blocks is None:
                            raise RuntimeError("kv pool exhausted")
                    tok = eng.prefill(req)
                    if eng.prefix_cache is not None:
                        eng.prefix_cache.insert(req)
                    ttfts.append((time.perf_counter() - t0) * 1e3)
                    firsts.append(int(tok))
                    reqs.append(req)
                # snapshot while every tenant is LIVE: the shared
                # gauge reads sharing as it exists under load, not
                # after the drain parks everything at refcount zero
                peak = eng.pool.used_blocks
                snap = metrics.snapshot()
                for req in reqs:
                    eng.free_sequence(req)
                return ttfts, firsts, peak, snap

            # warm TWICE: the first trace fills the trie (and, cache
            # on, runs the cold COW path), the second hits the exact
            # steady-state suffix buckets the measured trace will use
            # — a bucket first compiled inside a measured TTFT, or a
            # background compile still churning on a small box, would
            # be harness noise dressed up as cache overhead
            metrics.zero_all()
            trace()
            cold = metrics.snapshot()
            trace()
            time.sleep(1.5)
            metrics.zero_all()
            ttfts, firsts, peak, snap = trace()
            # COW fires on the COLD trace (divergent suffixes sharing
            # a partial block); the measured steady-state trace is an
            # exact repeat, so its counter would hide it
            snap = dict(snap, _cow_cold=cold[
                "serve_kv_cow_copies_total"]["value"])
        finally:
            eng.close()
        return ttfts, firsts, peak, snap

    out_mixes = []
    for mix in (80, 90, 95):
        suffix_len = max(1, int(round(
            shared_len * (100.0 / mix - 1.0))))
        prompts = [shared + rng.randint(
            0, GEN_VOCAB, size=suffix_len).tolist()
            for _ in range(users)]
        ttf_off, first_off, peak_off, _ = run_mode(prompts, on=False)
        ttf_on, first_on, peak_on, snap = run_mode(prompts, on=True)
        tok_total = snap["serve_prefix_tokens_total"]["value"]
        tok_cached = snap["serve_prefix_tokens_cached_total"]["value"]
        avoided = 100.0 * tok_cached / tok_total if tok_total else 0.0
        p50_off = _pctl(sorted(ttf_off), 50)
        p50_on = _pctl(sorted(ttf_on), 50)
        out_mixes.append({
            "mix_pct": mix, "users": users,
            "prompt_tokens": len(prompts[0]),
            "shared_tokens": shared_len,
            "prefix_hits": snap["serve_kv_prefix_hits"]["value"],
            "prefill_tokens": tok_total,
            "prefill_tokens_cached": tok_cached,
            "prefill_flops_avoided_pct": round(avoided, 1),
            "ttft_p50_ms": {"off": round(p50_off, 3),
                            "on": round(p50_on, 3)},
            "ttft_speedup": round(p50_off / max(p50_on, 1e-9), 2),
            "kv_blocks_peak": {"off": peak_off, "on": peak_on},
            "kv_bytes_per_user": {
                "off": int(peak_off * block_bytes / users),
                "on": int(peak_on * block_bytes / users)},
            "blocks_shared": snap["serve_kv_blocks_shared"]["value"],
            "cow_copies_cold_trace": snap["_cow_cold"],
            "cow_copies": snap["serve_kv_cow_copies_total"]["value"],
            "tokens_identical": bool(first_off == first_on),
        })
    ok = all(m["tokens_identical"]
             and m["kv_blocks_peak"]["on"] < m["kv_blocks_peak"]["off"]
             and m["prefill_flops_avoided_pct"]
             >= 0.75 * m["mix_pct"]
             and m["ttft_p50_ms"]["on"] <= m["ttft_p50_ms"]["off"]
             for m in out_mixes)
    return {"users": users, "shared_tokens": shared_len,
            "kv_block_bytes": block_bytes, "mixes": out_mixes,
            "ok": bool(ok)}


def _run_generate(quick, seconds, max_batch):
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import InferenceServer

    kv_blocks = int(os.environ.get("SVB_GEN_KV_BLOCKS",
                                   "128" if quick else "512"))
    max_new = int(os.environ.get("SVB_GEN_MAX_NEW",
                                 "16" if quick else "32"))
    cfg, params, kv = _gen_cfg(max_batch, kv_blocks)
    rng = np.random.RandomState(5)
    prompts = _gen_prompts(rng, 64)
    # feature knobs (the tier-1 smoke parametrizes over these): run
    # the SAME Poisson trace with the prefix cache on and/or a draft
    # LM speculating — correctness under load, not a perf claim
    prefix_on = os.environ.get("SVB_GEN_PREFIX_CACHE", "") == "1"
    spec_k = int(os.environ.get("SVB_GEN_SPEC_K", "0"))
    draft = None
    if spec_k:
        import re as _re

        from paddle_tpu.serving.generative import LMConfig

        dcfg = LMConfig(vocab=GEN_VOCAB, d_model=GEN_DMODEL,
                        n_heads=GEN_HEADS, n_layers=1, d_ff=GEN_DFF,
                        block_size=GEN_BLOCK,
                        max_blocks=GEN_MAX_BLOCKS,
                        max_batch=max_batch)
        draft = (dcfg, {k: v for k, v in params.items()
                        if not _re.match(r"l[0-9]+\.", k)
                        or k.startswith("l0.")})
    if prefix_on:
        # give the trace something to share: one block-sized system
        # prefix on every prompt, so admission-time lookups hit
        common = rng.randint(0, GEN_VOCAB, size=GEN_BLOCK).tolist()
        prompts = [common + p for p in prompts]
    srv = InferenceServer()
    t_load = time.perf_counter()
    eng = srv.load_generative("g", cfg, params, kv_blocks=kv,
                              prefix_cache=True if prefix_on else None,
                              spec_k=spec_k or None, draft=draft)
    load_s = time.perf_counter() - t_load
    try:
        floor = _gen_floor(srv, prompts[0], max(max_new, 32))
        cap_tokens_s = _gen_capacity(
            srv, prompts[:4 * max_batch], max_new)
        # offered rate: high enough that the decode batch stays full
        # (the occupancy acceptance), low enough for a steady state —
        # 0.85 of the measured full-batch token capacity
        rate_rps = 0.85 * cap_tokens_s / max_new
        metrics.zero_all()
        poisson, drop = _gen_poisson(srv, prompts, max_new,
                                     2 * seconds, rate_rps)
        snap = metrics.snapshot()
        rows = snap["serve_decode_rows_total"]["value"]
        slots = snap["serve_decode_slots_total"]["value"]
        steps_n = snap["serve_decode_steps_total"]["value"]
        occupancy = {
            # live rows / dispatched bucket rows: padding waste (a
            # drained batch re-buckets down, so a sustained high share
            # needs admission keeping rows IN the batch while prompts
            # stream).  A step that carries an admitted prompt runs the
            # tenant's top bucket whatever rows are live (one program a
            # prompt bucket), so at an open loop's few rows the mean
            # falls with the arrivals; the acceptance reads the median
            # step
            "mean_pct": round(100.0 * rows / slots, 1) if slots else 0.0,
            "p50_pct": snap["serve_decode_occupancy_pct"]["p50"],
            "buckets": snap["serve_decode_occupancy_pct"]["buckets"],
            "decode_steps": steps_n,
            # absolute concurrency, for honesty alongside the bucket-
            # relative number: mean live rows per iteration and the
            # same as a fraction of the configured batch ceiling (a
            # function of offered load, not an engine property — the
            # Poisson rate targets 0.85x capacity, not full batches)
            "mean_rows": round(rows / steps_n, 2) if steps_n else 0.0,
            "utilization_vs_max_batch_pct": round(
                100.0 * rows / (steps_n * max_batch), 1)
            if steps_n else 0.0,
            "prefills": snap["serve_prefills_total"]["value"],
        }
        kv_stats = {
            # capacity from the live pool: metrics.zero_all() above
            # rebased the gauges to measure the phase, not the load
            "blocks_total": eng.pool.capacity,
            "blocks_used_after_drain": eng.pool.used_blocks,
            "blocks_cached_after_drain": eng.pool.cached_blocks,
            "alloc_failures":
                snap["serve_kv_alloc_failures_total"]["value"],
            "preemptions": snap["serve_kv_preemptions_total"]["value"],
        }
        features = {"prefix_cache": prefix_on, "spec_k": spec_k}
        if prefix_on:
            features["prefix_hits"] = \
                snap["serve_kv_prefix_hits"]["value"]
            features["prefix_tokens_cached"] = \
                snap["serve_prefix_tokens_cached_total"]["value"]
        if spec_k:
            prop = snap["serve_spec_proposed_total"]["value"]
            acc = snap["serve_spec_accepted_total"]["value"]
            features["spec_rounds"] = \
                snap["serve_spec_rounds_total"]["value"]
            features["spec_accept_rate"] = \
                round(acc / prop, 4) if prop else 0.0
    finally:
        srv.close()
    int8 = _gen_int8_parity(max_batch, kv_blocks,
                            int(os.environ.get("SVB_GEN_PARITY_STEPS",
                                               "64")))
    speedup = round(poisson["tokens_s"] / max(floor["tokens_s"], 1e-9),
                    2)
    return {
        "model": {"vocab": GEN_VOCAB, "d_model": GEN_DMODEL,
                  "n_heads": GEN_HEADS, "n_layers": GEN_LAYERS,
                  "d_ff": GEN_DFF, "seed": GEN_SEED,
                  "block_size": GEN_BLOCK,
                  "max_blocks": GEN_MAX_BLOCKS,
                  "kv_blocks": kv_blocks},
        "max_batch": max_batch,
        "max_new_tokens": max_new,
        "features": features,
        "load_warm_s": round(load_s, 2),
        "floor": floor,
        "capacity_tokens_s": round(cap_tokens_s, 1),
        "poisson": poisson,
        "speedup_tokens_vs_floor": speedup,
        "occupancy": occupancy,
        "kv": kv_stats,
        "drop": drop,
        "int8": int8,
        "ok": bool(drop["zero_dropped"] and int8["parity_ok"]
                   and int8["certified"]
                   and occupancy["p50_pct"] >= 80.0),
    }


def _wire_sanity(srv, x):
    """One request over the socket endpoint — the fastwire-framed
    Predict method answers and matches the in-process result."""
    from paddle_tpu.serving import PredictClient

    port = srv.start_endpoint()
    with PredictClient("127.0.0.1", port) as cli:
        t0 = time.perf_counter()
        outs = cli.predict("m", {"x": x})
        lat = time.perf_counter() - t0
    ref = srv.predict("m", {"x": x})
    ok = all(np.allclose(outs[k], ref[k], atol=1e-5) for k in outs)
    return {"ok": bool(ok), "latency_ms": round(lat * 1e3, 3),
            "port": port}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="seconds-long tier-1 smoke (CPU)")
    ap.add_argument("--out", default="",
                    help="also write the JSON to this file")
    ap.add_argument("--rate-x", type=float, default=4.0,
                    help="poisson offered rate as a multiple of the "
                         "measured floor QPS")
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="override per-phase duration")
    ap.add_argument("--mode",
                    choices=("predict", "generate", "prefix", "spec",
                             "all"),
                    default="all",
                    help="which serving planes to bench: the PR 9 "
                         "predict phases, the ISSUE 11 token-level "
                         "generate phases, the ISSUE 19 shared-prefix "
                         "trace or speculative solo-floor phases, or "
                         "all of them (default)")
    ap.add_argument("--sentinel", action="store_true",
                    help="gate this run against PERF_TRAJECTORY.json "
                         "via tools/perf_sentinel.py (rc 3 on a >15%% "
                         "regression vs the recorded floor; quick "
                         "runs only compare against quick floors).  "
                         "ROADMAP: always pass this")
    args = ap.parse_args(argv)

    import tempfile

    from paddle_tpu.core.flags import FLAGS, apply_xla_flags
    from paddle_tpu.inference import aot as aot_mod
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import InferenceServer

    apply_xla_flags()
    seconds = args.seconds or (1.0 if args.quick else 6.0)
    max_batch = int(os.environ.get("SVB_MAX_BATCH",
                                   "8" if args.quick else "16"))
    max_wait_us = int(os.environ.get("SVB_MAX_WAIT_US", "2000"))

    def _finish(out):
        line = json.dumps(out)
        print(line)
        if args.out:
            with open(args.out, "w") as f:
                f.write(line + "\n")
        rc = 0 if out["ok"] else 1
        return rc or (_sentinel_check(out) if args.sentinel else 0)

    if args.mode in ("generate", "prefix", "spec"):
        rec = {"generate": lambda: _run_generate(args.quick, seconds,
                                                 max_batch),
               "prefix": lambda: _run_prefix(args.quick),
               "spec": lambda: _run_spec(args.quick)}[args.mode]()
        return _finish({
            "metric": "serve_bench", "quick": bool(args.quick),
            "mode": args.mode, **_device_fields(),
            args.mode: rec, "ok": rec["ok"]})

    tmp = tempfile.mkdtemp(prefix="serve_bench_")
    d1, d2 = os.path.join(tmp, "v1"), os.path.join(tmp, "v2")
    t_build = time.perf_counter()
    _build_and_save(d1, 11, max_batch)
    _build_and_save(d2, 911, max_batch)
    build_s = time.perf_counter() - t_build
    x = np.linspace(-1, 1, D_IN).astype(np.float32).reshape(1, D_IN)

    floor = _measure_floor(d1, x, seconds)

    metrics.zero_all()
    srv = InferenceServer(max_batch=max_batch, max_wait_us=max_wait_us)
    t_load = time.perf_counter()
    srv.load("m", d1)
    load_s = time.perf_counter() - t_load
    ref_v1 = np.asarray(next(iter(srv.predict("m", {"x": x}).values())))

    saturated = _measure_saturated(srv, x, seconds,
                                   window=4 * max_batch)
    metrics.zero_all()
    # open-loop offered rate: rate_x x floor, capped under the probed
    # capacity — an open-loop rate above capacity has no steady state
    # (the queue and p99 grow without bound for as long as you let it)
    rate = min(args.rate_x * floor["qps"], 0.65 * saturated["qps"])
    # headline phase: steady open-loop load, no configuration churn
    poisson, steady_drop = _poisson(srv, x, ref_v1, 2 * seconds, rate)
    # swap phase: same load while swap() builds + flips to v2 — the
    # shadow compile competes for the host, so its latency spike is
    # reported HERE, not folded into the steady-state headline
    poisson_swap, swap = _poisson(srv, x, ref_v1, 2 * seconds, rate,
                                  seed=13, swap_to=d2, swap_at=0.33)
    swap["steady_phase_dropped"] = steady_drop["dropped"]
    snap = metrics.snapshot()
    occupancy = snap["serve_batch_occupancy"]
    phases = {k: {"p50_ms": snap[k]["p50"], "p99_ms": snap[k]["p99"],
                  "count": snap[k]["count"]}
              for k in ("serve_queue_wait_ms", "serve_batch_assemble_ms",
                        "serve_dispatch_ms")}
    wire = _wire_sanity(srv, x)
    srv.close()

    speedup = round(poisson["qps"] / max(floor["qps"], 1e-9), 2)
    speedup_saturated = round(
        saturated["qps"] / max(floor["qps"], 1e-9), 2)
    p99_budget_ms = max(2.0 * floor["p99_ms"], 10.0)
    out = {
        "metric": "serve_bench",
        "quick": bool(args.quick), **_device_fields(),
        "model": {"d_in": D_IN, "hidden": HIDDEN, "d_out": D_OUT},
        "max_batch": max_batch,
        "max_wait_us": max_wait_us,
        "build_s": round(build_s, 2),
        "load_warm_s": round(load_s, 2),
        "floor": floor,
        "saturated": saturated,
        "poisson": poisson,
        "poisson_under_swap": poisson_swap,
        "speedup_vs_floor": speedup,
        "speedup_saturated_vs_floor": speedup_saturated,
        "p99_budget_ms": round(p99_budget_ms, 3),
        "within_p99_budget": poisson["p99_ms"] <= p99_budget_ms,
        "batch_occupancy": {"count": occupancy["count"],
                            "p50": occupancy["p50"],
                            "buckets": occupancy["buckets"]},
        "phases": phases,
        "swap": swap,
        "wire": wire,
        "aot_load_fallback_total":
            metrics.counter("aot_load_fallback_total").value,
        "aot_load_fallbacks": list(aot_mod.FALLBACKS),
        "ok": bool(speedup >= 3.0
                   and poisson["p99_ms"] <= p99_budget_ms
                   and steady_drop["zero_dropped"]
                   and swap["zero_dropped"] and swap["torn"] == 0
                   and wire["ok"]),
    }
    if args.mode == "all":
        gen = _run_generate(args.quick, seconds, max_batch)
        out["generate"] = gen
        pfx = _run_prefix(args.quick)
        out["prefix"] = pfx
        spec = _run_spec(args.quick)
        out["spec"] = spec
        out["ok"] = bool(out["ok"] and gen["ok"] and pfx["ok"]
                         and spec["ok"])
    return _finish(out)


def _sentinel_check(out):
    """Perf sentinel (ISSUE 13): gate the fresh run against the
    recorded PERF_TRAJECTORY.json floors; rc 3 (and a one-line JSON
    report) on regression."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from perf_sentinel import sentinel_gate

    return sentinel_gate(out)


if __name__ == "__main__":
    sys.exit(main())
