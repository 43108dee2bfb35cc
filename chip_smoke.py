#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

One process drives the framework's main paths once, through the entry
points a user calls, at the full width of the models the repo has an
on-chip history for (depth uncut, weights random from a seed):

  device            jax found a TPU whose device_kind is in the peaks table
  train_transformer transformer LM d1024 L6 bs16 seq2048 bf16 on
                    fluid.Executor(fluid.TPUPlace()): exe.run, then the
                    prepared hot path (exe.prepare / run_prepared)
  serve_generate    InferenceServer.load_generative at d1024 L6, one
                    fp32 and one int8 tenant, concurrent generate()
  train_resnet50    ResNet-50 bs256 bf16 224x224 (NCHW, default flags)
  multichip         the transformer under ParallelExecutor dp4 (bs64)
                    and dp2 x tp2, when four chips are present

Each phase prints one JSON line, then a ``summary`` line (phases,
compile seconds, cache hits, ``"claim": null``); the last line of stdout
is the result and holds exactly ``{"ok": true, "device": {"platform":
..., "kind": ..., "count": ...}}``, the device as jax reports it.

A phase asserts what it claims — arrays on the TPU, the Pallas kernels
TAKEN (trace-time dispatch counters, and the Mosaic custom calls in the
lowered modules jax dumps), loss finite and falling, no compile in the
steady window, greedy tokens bit-identical solo / batched / repeated —
and the first failed assertion ends the run with a non-zero exit code
and no result line.  There is no CPU mode: under JAX_PLATFORMS=cpu the ``device``
phase fails.  tests/test_tpu_lowering.py rehearses the phase bodies on
the CPU at toy width (interpreted kernels), so chip time is not spent
finding typos.  Rates are printed for orientation; none is a claim.
"""
import glob
import json
import os
import re
import sys
import threading
import time

import numpy as np

# Full width (the configurations with an on-chip history; ISSUE 21 §1).
TRANSFORMER = dict(vocab_size=8192, seq_len=2048, d_model=1024, n_head=8,
                   n_layers=6, d_ff=4096)
TRANSFORMER_BATCH = 16
SERVE_LM = dict(vocab=8192, d_model=1024, n_heads=8, n_layers=6,
                d_ff=4096, block_size=16, max_blocks=128, max_batch=8)
# six prompts of a few hundred tokens whose block counts (with 32 new
# tokens) share ONE power-of-two block-count bucket (17..27 -> 32), so
# the warm-up rounds below can make every bucket the window touches warm
SERVE_PROMPT_LENS = (280, 300, 320, 340, 360, 380)
SERVE_NEW_TOKENS = 32
SERVE_KV_BLOCKS = 256
RESNET_BATCH = 256
# Lower-priority phases are skipped (explicitly) rather than started
# when they could not finish inside the driver's 1200 s.
WALL_BUDGET_S = 1080.0

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def emit(rec):
    print(json.dumps(rec), flush=True)


def result_line(ok):
    """The last line of stdout: exactly these keys (the driver parses
    it), the device as jax reports it; everything else the run has to
    say goes on the summary line before it."""
    import jax

    devices = jax.devices()
    return {"ok": bool(ok),
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind,
                       "count": len(devices)}}


class CompileMeter:
    """Counts jax backend compiles (a persistent-cache retrieval counts:
    it is still a compile request on the path) and sums their seconds,
    from jax's own monitoring events — every thread, background bucket
    compiles included."""

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == _BACKEND_COMPILE:
            with self._lock:
                self.compiles += 1
                self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def mark(self):
        with self._lock:
            return (self.compiles, self.seconds, self.cache_hits)

    def since(self, mark):
        now = self.mark()
        return {"compiles": now[0] - mark[0],
                "compile_s": round(now[1] - mark[1], 2),
                "cache_hits": now[2] - mark[2]}


class Target:
    """What a phase runs on and what it then asserts about it: the
    chip in ``main``; the CPU in the tier-1 rehearsal, where the Mosaic
    custom calls cannot exist (kernels run interpreted) and only the
    dispatch counters witness the Pallas path."""

    def __init__(self, place, platform, ir_dir):
        self.place = place
        self.platform = platform
        self.ir_dir = ir_dir


def kernel_delta(before):
    from paddle_tpu.kernels import dispatch

    now = dispatch.counts()
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)}


def kernel_mark():
    from paddle_tpu.kernels import dispatch

    return dispatch.counts()


def assert_kernels(got, want):
    """Exactly the paths in ``want`` were dispatched — a kernel that
    fell to its XLA reference shows up as an unexpected '<kernel>.xla' —
    each at least ``want`` times (a step is traced more than once: the
    program verifier's shape pass traces the same lowerings)."""
    assert set(got) == set(want), (got, want)
    short = {k: (got[k], n) for k, n in want.items() if got[k] < n}
    assert not short, short


def ir_files(target):
    return set(glob.glob(os.path.join(target.ir_dir, "*.mlir")))


def mosaic_kernels(paths):
    """{kernel_name: count} over the Mosaic custom calls in the lowered
    modules ``paths`` (what jax handed the compiler)."""
    found = {}
    for path in paths:
        with open(path) as f:
            text = f.read()
        for name in re.findall(
                r'@tpu_custom_call\(.*?kernel_name = "(\w+)"', text):
            found[name] = found.get(name, 0) + 1
    return found


def assert_on_device(target, what, arrays):
    import jax

    n = 0
    for a in arrays:
        assert isinstance(a, jax.Array), (what, type(a))
        platforms = {d.platform for d in a.devices()}
        assert platforms == {target.platform}, (what, platforms)
        n += 1
    assert n, "no arrays to check for %s" % what
    return n


def scope_arrays(scope):
    import jax

    vals = (scope.find_var(n) for n in scope.local_var_names())
    return [v for v in vals if isinstance(v, jax.Array)]


def _scalar(x):
    """The host float of a fetched [1] loss (waits for the device)."""
    return float(np.asarray(x).reshape(-1)[0])


def assert_loss_falls(losses):
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], "loss did not fall: %r" % (losses,)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device():
    import importlib.metadata as md

    import jax
    import jaxlib

    from paddle_tpu.core.peaks import device_peaks

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SystemExit(
            "chip_smoke: jax found no TPU (devices: %r) — this script has "
            "no CPU mode" % (devices,))
    peaks = device_peaks(d0.device_kind)     # unknown kind raises
    return {"platform": d0.platform, "device_kind": d0.device_kind,
            "count": len(devices),
            "versions": {"jax": jax.__version__,
                         "jaxlib": jaxlib.__version__,
                         "libtpu": md.version("libtpu"),
                         "python": sys.version.split()[0]},
            "peaks": {k: v for k, v in peaks.items() if k != "source"}}


def _build_transformer(model_kw, **extra):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import transformer

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup), \
            fluid.unique_name.guard():
        avg_cost, (src, label), _ = transformer.get_model(
            **model_kw, **extra)
    fluid.transpiler.Float16Transpiler().transpile(main_prog)
    return main_prog, startup, avg_cost, src, label


def _lm_batch(model_kw, batch, src, label):
    rng = np.random.RandomState(0)
    vocab, seq = model_kw["vocab_size"], model_kw["seq_len"]
    return {src.name: rng.randint(0, vocab, (batch, seq)).astype(np.int64),
            label.name: rng.randint(0, vocab,
                                    (batch, seq, 1)).astype(np.int64)}


def phase_train_transformer(target, meter, model_kw, batch,
                            run_steps=5, prepared_steps=3):
    """Startup, then >= 5 steps through exe.run and >= 3 through the
    prepared hot path, on one fixed batch."""
    import jax

    import paddle_tpu.fluid as fluid

    layers = model_kw["n_layers"]
    t0 = time.perf_counter()
    m0, k0, ir0 = meter.mark(), kernel_mark(), ir_files(target)
    main_prog, startup, avg_cost, src, label = _build_transformer(model_kw)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(target.place)
        exe.run(startup)
        dev = target.place.jax_device()
        feed = {k: jax.device_put(v, dev)
                for k, v in _lm_batch(model_kw, batch, src, label).items()}
        losses = [_scalar(exe.run(main_prog, feed=feed,
                                  fetch_list=[avg_cost])[0])]
        setup = dict(meter.since(m0),
                     setup_s=round(time.perf_counter() - t0, 2))

        # the Pallas path was TAKEN: flash forward + dQ + dK/dV per layer
        kernels = kernel_delta(k0)
        assert_kernels(kernels, {"flash_attention_fwd_lse.pallas": layers,
                                 "flash_attention_bwd.pallas": layers})
        mosaic = mosaic_kernels(ir_files(target) - ir0)
        if target.platform == "tpu":
            assert mosaic == {"flash_fwd": layers,
                              "flash_bwd_dq": layers,
                              "flash_bwd_dkv": layers}, mosaic

        # steady window 1: exe.run, no compile after the first step
        m1 = meter.mark()
        t1 = time.perf_counter()
        fetched = [exe.run(main_prog, feed=feed, fetch_list=[avg_cost],
                           return_numpy=False)[0]
                   for _ in range(run_steps)]
        losses += [_scalar(x) for x in fetched]   # drains
        run_s = time.perf_counter() - t1
        steady = meter.since(m1)
        assert steady["compiles"] == 0, steady
        assert_on_device(target, "fetched loss", fetched)

        # steady window 2: the prepared hot path (prepare + its first
        # step stage the device-resident state: set-up)
        prepared = exe.prepare(main_prog, feed_specs=feed,
                               fetch_list=[avg_cost])
        losses.append(_scalar(prepared.run_prepared(feed)[0]))
        m2 = meter.mark()
        t2 = time.perf_counter()
        fetched = [prepared.run_prepared(feed)[0]
                   for _ in range(prepared_steps)]
        losses += [_scalar(x) for x in fetched]
        prepared_s = time.perf_counter() - t2
        steady2 = meter.since(m2)
        assert steady2["compiles"] == 0, steady2
        prepared.sync_scope()

        assert_loss_falls(losses)
        n_state = assert_on_device(target, "params + optimizer state",
                                   scope_arrays(scope))
        assert_on_device(target, "feed", feed.values())
        n_params = sum(int(np.prod(p.shape)) for p in
                       main_prog.global_block().all_parameters())

    tokens = batch * model_kw["seq_len"]
    return {"model": dict(model_kw, batch=batch, amp="bf16"),
            "params_m": round(n_params / 1e6, 1),
            "setup": setup, "steady_s": round(run_s + prepared_s, 3),
            "steps": {"run": run_steps, "prepared": prepared_steps},
            "steady_compiles": 0, "losses": [round(x, 4) for x in losses],
            "state_arrays_on_device": n_state,
            "kernels": kernels, "mosaic": mosaic,
            "asserted": ["arrays on %s" % target.platform,
                         "flash fwd+dq+dkv per layer", "loss falls",
                         "no compile in steady window"],
            "orientation": {
                "run_step_ms": round(run_s / run_steps * 1e3, 1),
                "prepared_step_ms": round(
                    prepared_s / prepared_steps * 1e3, 1),
                "run_tok_per_s": round(tokens * run_steps / run_s),
                "prepared_tok_per_s": round(
                    tokens * prepared_steps / prepared_s)}}


def _generate_round(srv, name, prompts, new_tokens, timeout=600):
    futures = [srv.generate(name, p, max_new_tokens=new_tokens)
               for p in prompts]
    results = [f.result(timeout) for f in futures]
    for r in results:
        assert len(r["tokens"]) == new_tokens, (name, len(r["tokens"]))
    return [r["tokens"] for r in results]


def phase_serve_generate(target, meter, lm_kw, prompt_lens, new_tokens,
                         kv_blocks):
    """One InferenceServer, an fp32 and an int8 tenant of the same LM;
    concurrent generate() requests; greedy tokens must be bit-identical
    solo, batched and repeated."""
    from paddle_tpu.serving import InferenceServer, tiny_lm

    layers = lm_kw["n_layers"]
    cfg, params = tiny_lm(21, **lm_kw)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab, n).tolist() for n in prompt_lens]
    tenants = {}
    srv = InferenceServer(place=target.place)
    try:
        for name, quant in (("lm_fp32", ""), ("lm_int8", "int8")):
            t0 = time.perf_counter()
            m0, k0, ir0 = meter.mark(), kernel_mark(), ir_files(target)
            engine = srv.load_generative(name, cfg, params, quant=quant,
                                         kv_blocks=kv_blocks)
            load_s = time.perf_counter() - t0
            # warm-up: rounds of 1, 2, 4 and all prompts leave every
            # batch bucket warm at the block-count bucket these prompts
            # live in (the engine compiles tighter buckets in the
            # background on first miss); then join those compiles
            for k in sorted({1, 2, 4, len(prompts)}):
                _generate_round(srv, name, prompts[:k], new_tokens)
            engine.drain_compiles()
            setup = dict(meter.since(m0), load_s=round(load_s, 2),
                         setup_s=round(time.perf_counter() - t0, 2))

            # which kernels each compiled bucket took (the engine pads
            # matmul rows to 8, so the int8 kernel tiles in every bucket)
            # (a fresh prompt's program is a decode step that carries
            # it: a paged call and a flash call a layer)
            decode = engine.warm_decode_buckets
            prefill = engine.prompt_ladder
            kernels = kernel_delta(k0)
            want = {"paged_attention.pallas": layers * (len(decode)
                                                         + len(prefill)),
                    "flash_attention.pallas": layers * len(prefill)}
            if quant:
                want["matmul_int8_dequant.pallas"] = 4 * layers * (
                    len(prefill) + len(decode))
            assert_kernels(kernels, want)
            mosaic = mosaic_kernels(ir_files(target) - ir0)
            if target.platform == "tpu":
                want_mosaic = {"_paged_kernel": want["paged_attention.pallas"],
                               "flash_fwd": want["flash_attention.pallas"]}
                if quant:
                    want_mosaic["_matmul_int8_kernel"] = \
                        want["matmul_int8_dequant.pallas"]
                assert mosaic == want_mosaic, (mosaic, want_mosaic)

            # steady window: batched, solo, solo again — no compile of
            # any kind, background threads included
            m1 = meter.mark()
            t1 = time.perf_counter()
            batched = _generate_round(srv, name, prompts, new_tokens)
            solo = _generate_round(srv, name, prompts[:1], new_tokens)
            again = _generate_round(srv, name, prompts[:1], new_tokens)
            steady_s = time.perf_counter() - t1
            engine.drain_compiles()
            steady = meter.since(m1)
            assert steady["compiles"] == 0, steady
            assert engine.warm_decode_buckets == decode
            assert solo[0] == batched[0], (name, "solo != batched",
                                           solo[0], batched[0])
            assert again[0] == solo[0], (name, "repeat != solo")

            assert engine.device.platform == target.platform
            kp, vp, _ = engine.kv_pages()
            assert_on_device(target, "kv pages", [kp, vp])
            n_tok = new_tokens * (len(prompts) + 2)
            tenants[name] = {
                "quant": quant or "fp32", "setup": setup,
                "steady_s": round(steady_s, 3), "steady_compiles": 0,
                "decode_buckets": [list(b) for b in decode],
                "prefill_buckets": list(prefill),
                "kernels": kernels, "mosaic": mosaic,
                "orientation": {"window_tok_per_s": round(n_tok / steady_s)}}
    finally:
        srv.close()
    left = [t.name for t in threading.enumerate()
            if t.is_alive() and t.name.startswith(
                tuple("serve-%s-%s" % (kind, name)
                      for kind in ("decode", "stepcompile")
                      for name in tenants))]
    assert not left, "server threads outlived close(): %r" % left
    return {"model": dict(lm_kw), "requests": {
                "prompt_lens": list(prompt_lens),
                "new_tokens": new_tokens, "kv_blocks": kv_blocks},
            "tenants": tenants,
            "asserted": ["engine + kv pages on %s" % target.platform,
                         "paged/flash/int8 kernels taken per bucket",
                         "every future returns the requested tokens",
                         "solo == batched == repeat (bit-identical)",
                         "no compile in steady window",
                         "close() joins every server thread"]}


def phase_train_resnet50(target, meter, batch, depth=50, steps=3,
                         data_set="flowers"):
    """ResNet bs256 bf16 224x224, NCHW, bench.py's accelerator defaults
    (bf16 pass-through batch norm); one fixed batch of synthetic images."""
    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.flags import FLAGS
    from paddle_tpu.models import resnet

    t0 = time.perf_counter()
    m0, k0 = meter.mark(), kernel_mark()
    bn_bf16, FLAGS.bn_bf16 = FLAGS.bn_bf16, True
    try:
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup), \
                fluid.unique_name.guard():
            avg_cost, (data, label), _ = resnet.get_model(
                data_set=data_set, depth=depth, data_format="NCHW")
        fluid.transpiler.Float16Transpiler().transpile(main_prog)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(target.place)
            exe.run(startup)
            rng = np.random.RandomState(0)
            shape = [batch] + list(data.shape[1:])
            classes = 10 if data_set == "cifar10" else 102
            dev = target.place.jax_device()
            feed = {
                data.name: jax.device_put(
                    rng.rand(*shape).astype(np.float32), dev),
                label.name: jax.device_put(
                    rng.randint(0, classes, (batch, 1)).astype(np.int64),
                    dev)}
            losses = [_scalar(exe.run(main_prog, feed=feed,
                                      fetch_list=[avg_cost])[0])]
            setup = dict(meter.since(m0),
                         setup_s=round(time.perf_counter() - t0, 2))
            m1 = meter.mark()
            t1 = time.perf_counter()
            fetched = [exe.run(main_prog, feed=feed,
                               fetch_list=[avg_cost],
                               return_numpy=False)[0]
                       for _ in range(steps)]
            losses += [_scalar(x) for x in fetched]
            steady_s = time.perf_counter() - t1
            steady = meter.since(m1)
            assert steady["compiles"] == 0, steady
            assert_loss_falls(losses)
            n_state = assert_on_device(target, "params + optimizer state",
                                       scope_arrays(scope))
            assert_on_device(target, "fetched loss", fetched)
    finally:
        FLAGS.bn_bf16 = bn_bf16
    # the NCHW default program has no hand-written kernel on its path
    kernels = kernel_delta(k0)
    assert kernels == {}, kernels
    return {"model": {"depth": depth, "batch": batch, "amp": "bf16",
                      "image": list(data.shape[1:]), "layout": "NCHW"},
            "setup": setup, "steady_s": round(steady_s, 3),
            "steps": steps, "steady_compiles": 0,
            "losses": [round(x, 4) for x in losses],
            "state_arrays_on_device": n_state,
            "asserted": ["arrays on %s" % target.platform, "loss falls",
                         "no compile in steady window",
                         "no Pallas kernel on the NCHW path"],
            "orientation": {
                "step_ms": round(steady_s / steps * 1e3, 1),
                "img_per_s": round(batch * steps / steady_s)}}


def _mesh_layout(target, scope, pe, annotated):
    """How the train state landed on the mesh: every state array spans
    all the mesh's devices (size-1 arrays the step only reads, like the
    learning rate, stay where startup put them); an annotated
    (tensor-parallel) parameter holds a proper slice per device,
    anything else a full replica.  Reading the scope flushes the
    prepared path's device-resident state.  Returns (sliced parameter
    count, state bytes resident per device)."""
    import jax

    sliced = 0
    resident = {}
    for name in scope.local_var_names():
        v = scope.find_var(name)
        if not isinstance(v, jax.Array) or v.size <= 1:
            continue
        shards = v.addressable_shards
        devs = {s.device for s in shards}
        assert len(devs) == pe.device_count, (name, len(devs))
        assert {d.platform for d in devs} == {target.platform}
        for s in shards:
            resident[s.device.id] = resident.get(s.device.id, 0) + \
                s.data.nbytes
        shard = shards[0].data.shape
        if any(annotated.get(name, ())):
            assert np.prod(shard) < v.size, (name, shard)
            sliced += 1
        else:
            assert tuple(shard) == tuple(v.shape), (name, shard)
    return sliced, [resident[k] for k in sorted(resident)]


def phase_multichip(target, meter, model_kw, batch_per_dp, one_chip_step_ms,
                    meshes=({"dp": 4}, {"dp": 2, "tp": 2}), steps=3):
    """The transformer under ParallelExecutor on a four-chip host:
    dp4 and dp2 x tp2."""
    import jax

    import paddle_tpu.fluid as fluid

    if jax.device_count() < 4:
        return {"skipped": "%d device" % jax.device_count()}
    layers = model_kw["n_layers"]
    out = {}
    for axes in meshes:
        tag = "x".join("%s%d" % kv for kv in axes.items())
        t0 = time.perf_counter()
        m0, k0 = meter.mark(), kernel_mark()
        tp = axes.get("tp", 1) > 1
        main_prog, startup, avg_cost, src, label = _build_transformer(
            model_kw, tp=tp)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            fluid.Executor(target.place).run(startup)
            pe = fluid.ParallelExecutor(
                use_tpu=target.platform == "tpu", loss_name=avg_cost.name,
                main_program=main_prog, scope=scope, mesh_axes=axes,
                num_devices=int(np.prod(list(axes.values()))))
            batch = batch_per_dp * axes.get("dp", 1)
            feed = _lm_batch(model_kw, batch, src, label)
            losses = [_scalar(pe.run([avg_cost], feed=feed)[0])]
            setup = dict(meter.since(m0),
                         setup_s=round(time.perf_counter() - t0, 2))
            kernels = kernel_delta(k0)
            assert_kernels(kernels,
                           {"flash_attention_fwd_lse.pallas": layers,
                            "flash_attention_bwd.pallas": layers})
            m1 = meter.mark()
            t1 = time.perf_counter()
            fetched = [pe.run([avg_cost], feed=feed,
                              return_numpy=False)[0] for _ in range(steps)]
            losses += [_scalar(x) for x in fetched]
            step_ms = (time.perf_counter() - t1) / steps * 1e3
            steady = meter.since(m1)
            assert steady["compiles"] == 0, steady
            assert_loss_falls(losses)
            sliced, resident = _mesh_layout(
                target, scope, pe,
                getattr(main_prog.desc, "var_shardings", {}))
            if tp:
                assert sliced, "no tensor-parallel parameter was sliced"
            # the allocator of every mesh device holds at least its
            # share of the state: not everything sits on device 0
            in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                      for d in jax.devices()[:pe.device_count]]
            if target.platform == "tpu":
                assert all(u >= r for u, r in zip(in_use, resident)), (
                    in_use, resident)
        out[tag] = {"batch": batch, "setup": setup,
                    "step_ms": round(step_ms, 1), "steady_compiles": 0,
                    "losses": [round(x, 4) for x in losses],
                    "tp_sliced_params": sliced,
                    "state_bytes_per_device": resident,
                    "bytes_in_use_per_device": in_use}
    if one_chip_step_ms and "dp4" in out:
        # four chips take four times the batch: a dp4 step near 4x the
        # one-chip step means the work was not divided
        ratio = out["dp4"]["step_ms"] / one_chip_step_ms
        out["dp4"]["step_vs_one_chip"] = round(ratio, 2)
        assert ratio < 2.5, ratio
    out["asserted"] = ["state spread over every mesh device as annotated",
                       "flash kernels taken per layer", "loss falls",
                       "no compile in steady window",
                       "dp4 step < 2.5x the one-chip step"]
    return out


# ---------------------------------------------------------------------------

def main():
    if not __debug__:
        raise SystemExit("chip_smoke: the checks are assert statements; "
                         "do not run it with python -O")
    t_start = time.perf_counter()
    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.flags import ensure_compile_cache

    meter = CompileMeter()
    results = {}

    def run(name, fn, *args, **kwargs):
        """Run one phase, print its line; a failure prints the failing
        line and ends the run (re-raised: non-zero exit, no result)."""
        t0 = time.perf_counter()
        mark = meter.mark()
        try:
            rec = fn(*args, **kwargs)
        except BaseException as e:
            emit({"phase": name, "ok": False,
                  "error": "%s: %s" % (type(e).__name__, str(e)[:2000])})
            raise
        head = {"phase": name, "ok": True, **device,
                "wall_s": round(time.perf_counter() - t0, 2),
                **meter.since(mark)}
        emit({**head, **rec})
        results[name] = "skipped" if "skipped" in rec else "ok"
        return rec

    device = {}
    info = run("device", phase_device)
    device = {"platform": info["platform"],
              "device_kind": info["device_kind"], "count": info["count"]}
    cache_dir = ensure_compile_cache()
    # jax writes every module it lowers here: the Mosaic custom calls
    # are counted from what was actually handed to the compiler
    ir_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          ".jax_cache", "chip_smoke_ir")
    os.makedirs(ir_dir, exist_ok=True)
    for stale in glob.glob(os.path.join(ir_dir, "*.mlir")):
        os.remove(stale)
    jax.config.update("jax_dump_ir_to", ir_dir)
    target = Target(fluid.TPUPlace(), "tpu", ir_dir)

    def in_budget(name):
        left = WALL_BUDGET_S - (time.perf_counter() - t_start)
        if left > 0:
            return True
        emit({"phase": name, "ok": True, **device,
              "skipped": "wall budget (%.0f s) spent" % WALL_BUDGET_S})
        results[name] = "skipped"
        return False

    tr = run("train_transformer", phase_train_transformer, target, meter,
             TRANSFORMER, TRANSFORMER_BATCH)
    run("serve_generate", phase_serve_generate, target, meter, SERVE_LM,
        SERVE_PROMPT_LENS, SERVE_NEW_TOKENS, SERVE_KV_BLOCKS)
    if in_budget("train_resnet50"):
        run("train_resnet50", phase_train_resnet50, target, meter,
            RESNET_BATCH)
    if in_budget("multichip"):
        run("multichip", phase_multichip, target, meter, TRANSFORMER,
            TRANSFORMER_BATCH, tr["orientation"]["prepared_step_ms"])

    total = meter.mark()
    emit({"phase": "summary", "ok": True, **device,
          "versions": info["versions"],
          "phases": results,
          "compile_cache_dir": cache_dir,
          "compiles": total[0], "compile_s": round(total[1], 1),
          "cache_hits": total[2],
          "wall_s": round(time.perf_counter() - t_start, 1),
          "claim": None})
    emit(result_line(True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
