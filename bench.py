#!/usr/bin/env python
"""Headline benchmark: ResNet-50 ImageNet-shape training throughput.

Mirrors the reference harness's metric — examples/sec over timed
iterations (reference benchmark/fluid/fluid_benchmark.py:297-301) — on the
fluid-style ResNet-50 (benchmark/fluid/models/resnet.py) built with
paddle_tpu and compiled by XLA onto whatever accelerator is attached
(one TPU chip; a CPU run is one started with JAX_PLATFORMS=cpu).

Accelerator runs default to bf16 mixed precision (Float16Transpiler —
the TPU analog of reference paddle/contrib/float16/float16_transpiler.py)
at batch 256; BENCH_AMP=0 / BENCH_BATCH override.

Convnet layout/fusion knobs (ISSUE 5; see README "Convolution layout &
fusion"): BENCH_LAYOUT=NHWC runs the LayoutTranspiler pipeline (NHWC
end-to-end, HWIO-pinned weights, Pallas fused conv stages;
BENCH_FUSED_STAGES=0 for the layout pass alone), BENCH_DEPTH overrides
the ResNet depth, and FLAGS_xla_latency_hiding_scheduler=1 /
FLAGS_xla_extra_flags="..." plumb XLA scheduler experiments — applied
before backend init and recorded in the JSON (xla_flags) plus the
executor compile-cache key.  The headline JSON carries data_format,
fused_stages and (under BENCH_PROFILE) xplane-sourced per_category_ms
so every BENCH_*.json row names the experiment that produced it.

Prints ONE json line:
  {"metric": ..., "value": N, "unit": "images/sec", "vs_baseline": N,
   "tflops": N, "mfu": N, "amp": bool}

vs_baseline: the only in-repo published ResNet-50 training number is the
MKL-DNN CPU baseline, 81.69 images/sec at bs=64
(reference benchmark/IntelOptimizedPaddle.md:39-45); value/81.69.

tflops/mfu: delivered training FLOP/s from the standard analytic count
(~4.1 GFLOPs/image forward at 224x224, x3 for fwd+bwd ~= 12.3e9), against
the device's bf16 peak from the one peaks table (paddle_tpu/core/peaks.py,
keyed by device_kind; an unknown device is an error).  Only reported for
224x224 datasets where the analytic count applies.

The default (accelerator) run also embeds a ``secondary`` metric: the
compute-bound transformer-LM flagship (d1024 L6, flash attention), whose
MFU shows the stack's ceiling when the workload is not HBM-bound the way
ResNet-50 is on v5e (see the roofline fields on the headline metric).
BENCH_SECONDARY=0 skips it.
"""
import contextlib
import json
import os
import signal
import sys
import threading
import time

import numpy as np

TRAIN_FLOPS_PER_IMG_224 = 12.3e9
TRAIN_FLOPS_PER_IMG_VGG16_224 = 46.5e9  # ~15.5 GF fwd x3


@contextlib.contextmanager
def _wall_budget(seconds, what):
    """SIGALRM wall-clock budget: a hung device call inside ``what``
    degrades to a TimeoutError the caller turns into an ``*_error``
    JSON field, instead of wedging the whole bench into the driver's
    rc:124 with no artifact at all.  No-op off the main thread or with
    a non-positive budget."""
    if seconds <= 0 or \
            threading.current_thread() is not threading.main_thread():
        yield
        return

    def _handler(signum, frame):
        # flight recorder FIRST: the artifact must exist even if the
        # TimeoutError is swallowed or the process dies during unwind
        path = None
        try:
            from paddle_tpu.observability import flight
            path = flight.dump("wall_budget:%s" % what,
                               blocked={"op": what,
                                        "budget_s": int(seconds)})
        except Exception:
            path = None
        msg = "%s exceeded its %ds wall budget" % (what, int(seconds))
        if path:
            msg += " (flight recorder: %s)" % path
        raise TimeoutError(msg)

    prev = signal.signal(signal.SIGALRM, _handler)
    # never truncate a sub-second budget to alarm(0) == "no alarm"
    signal.alarm(max(1, int(seconds)))
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, prev)


def _probe_backend(timeout):
    """Up-front liveness probe: one tiny jit, watched from the OUTSIDE.
    An unreachable accelerator fails HERE, in seconds and explicitly,
    instead of hanging the first 100-layer compile until the driver
    kills the run.  The probe runs in a daemon thread because a wedged
    PJRT call never returns to the interpreter — a SIGALRM handler
    could not interrupt it; the main thread just stops waiting.
    BENCH_FAKE_DEAD=1 simulates the hang (test hook for the error
    artifact path)."""
    result = {}

    def probe():
        try:
            if os.environ.get("BENCH_FAKE_DEAD") == "1":
                time.sleep(timeout + 30)   # hang like a wedged backend
            import jax
            import jax.numpy as jnp
            jax.jit(lambda x: x + 1)(
                jnp.zeros((8,), jnp.float32)).block_until_ready()
            result["ok"] = True
        except Exception as e:   # a fast, explicit failure also counts
            result["error"] = str(e)[:200]

    t = threading.Thread(target=probe, daemon=True)
    t.start()
    t.join(timeout)
    if result.get("ok"):
        return
    raise TimeoutError(
        result.get("error") or
        "no response from the backend within %ds (liveness probe)"
        % int(timeout))


def _exit_with_error_artifact(metric, err):
    """Print the explicit JSON error line and LEAVE with a failing exit
    code — os._exit, because a wedged runtime thread would otherwise
    hang interpreter teardown and turn this fast failure back into the
    driver's rc:124.  A flight-recorder dump rides along
    (who-was-waiting-on-whom instead of a bare error string; ISSUE 6
    tentpole d)."""
    rec = {
        "metric": metric,
        "error": "backend unreachable: %s" % str(err)[:200],
    }
    try:
        from paddle_tpu.observability import flight
        path = flight.dump("backend_unreachable",
                           blocked={"op": "liveness_probe",
                                    "error": str(err)[:200]})
        if path:
            rec["flight_recorder"] = path
    except Exception:
        pass
    print(json.dumps(rec), flush=True)
    sys.stdout.flush()
    os._exit(1)


def _ensure_bench_recordio(img_shape, data_set, n=2048):
    """Synthesize (once) an uncompressed recordio of uint8 images +
    int64 labels in the given CHW shape; returns its path.  Record
    format: label:i64le + image bytes (C-order)."""
    import struct

    import paddle_tpu as pt
    from paddle_tpu import recordio as rio

    # under the checkout's ignored cache dir, named by everything that
    # decides its content: a file another checkout (or another n) left
    # behind is never picked up
    data_dir = os.environ.get("BENCH_DATA_DIR") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".bench_data")
    os.makedirs(data_dir, exist_ok=True)
    path = os.path.join(
        data_dir, "paddle_tpu_bench_%s_%s_n%d.rio" % (
            data_set, "x".join(map(str, img_shape)), n))
    if os.path.exists(path):
        return path
    if data_set == "cifar10":
        base = pt.dataset.cifar.train10()

        def samples():
            for a, lab in base():
                yield (np.asarray(a, np.float32).reshape(img_shape), lab)
    else:
        samples = pt.dataset.flowers.train()
    tmp = path + ".tmp"
    with rio.Writer(tmp, compressor=rio.NO_COMPRESS) as w:
        k = 0
        for img, lab in samples():
            u8 = np.clip(np.asarray(img) * 255.0, 0, 255).astype(np.uint8)
            w.write(struct.pack("<q", int(lab)) + u8.tobytes())
            k += 1
            if k >= n:
                break
    os.replace(tmp, path)
    return path


def _xplane_categories(profile_dir):
    """xplane-sourced per-category device ms for a bench JSON (ISSUE
    5/7): where the step's bytes actually go.  Table goes to stderr;
    returns the dict (or an error marker — profile parse never sinks a
    bench)."""
    import glob

    import jax

    from paddle_tpu.utils.xplane import print_category_profile
    pbs = sorted(glob.glob(os.path.join(
        profile_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime)
    if not pbs:
        return None
    stdout, sys.stdout = sys.stdout, sys.stderr
    try:
        print("category profile (%s):" % pbs[-1])
        cats = print_category_profile(pbs[-1],
                                      jax.devices()[0].device_kind)
        return {c["category"]: round(c["time_ps"] / 1e9, 1)
                for c in cats[:8]}
    except Exception as e:
        return {"error": str(e)[:120]}
    finally:
        sys.stdout = stdout


def transformer_bench(on_accel, as_dict=False):
    """BENCH_MODEL=transformer: bf16 LM training tokens/sec (flash
    attention on the TPU path; second headline next to ResNet-50).

    ``as_dict``: run with the compute-bound flagship dims (d1024 L6 —
    0.55 MFU measured on v5e) and return the result instead of printing,
    for embedding as the ``secondary`` metric of the default bench.

    ISSUE 7 knobs: BENCH_FUSED_TRANSFORMER=1 runs
    FuseTransformerBlockPass at build time (fused QKV / matmul
    epilogues / add+LN backed by kernels/matmul_fused.py) — the JSON
    then reports ``fused_stages`` + per-category counts; BENCH_PROFILE
    adds xplane-sourced ``per_category_ms``.  FLAGS_autotune_cache_dir
    (or BENCH_AUTOTUNE_CACHE) points the kernels at the persistent
    tile cache the tune tools write."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.flags import FLAGS
    from paddle_tpu.core.peaks import device_peaks
    from paddle_tpu.models import transformer

    if os.environ.get("BENCH_FUSED_TRANSFORMER") is not None:
        FLAGS.transformer_fuse = \
            os.environ["BENCH_FUSED_TRANSFORMER"] == "1"
    if os.environ.get("BENCH_AUTOTUNE_CACHE"):
        FLAGS.autotune_cache_dir = os.environ["BENCH_AUTOTUNE_CACHE"]

    if as_dict:
        bs, seq, iters = 16, 2048, 10
        d_model, n_layers, n_head = 1024, 6, 8
    elif on_accel:
        bs = int(os.environ.get("BENCH_BATCH", "16"))
        seq = int(os.environ.get("BENCH_SEQ", "2048"))
        iters = int(os.environ.get("BENCH_ITERS", "30"))
        d_model = int(os.environ.get("BENCH_DMODEL", "1024"))
        n_layers = int(os.environ.get("BENCH_LAYERS", "6"))
        n_head = int(os.environ.get("BENCH_HEADS", "8"))
    else:
        # CPU tier: tiny defaults, but explicit BENCH_* dims are
        # honored so the fused-vs-unfused comparison can run at a
        # noise-resistant shape
        bs = int(os.environ.get("BENCH_BATCH", "2"))
        seq = int(os.environ.get("BENCH_SEQ", "128"))
        iters = int(os.environ.get("BENCH_ITERS", "3"))
        d_model = int(os.environ.get("BENCH_DMODEL", "64"))
        n_layers = int(os.environ.get("BENCH_LAYERS", "2"))
        n_head = int(os.environ.get("BENCH_HEADS", "4"))
    vocab = 8192
    amp = os.environ.get("BENCH_AMP", "1" if on_accel else "0") == "1"

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        avg_cost, (src, label), _ = transformer.get_model(
            vocab_size=vocab, seq_len=seq, d_model=d_model,
            n_head=n_head, n_layers=n_layers, d_ff=4 * d_model)
    if amp:
        fluid.transpiler.Float16Transpiler().transpile(main_prog)
    place = fluid.TPUPlace() if on_accel else fluid.CPUPlace()
    exe = fluid.Executor(place)
    exe.run(startup)

    rng = np.random.RandomState(0)
    feed = {src.name: rng.randint(0, vocab, (bs, seq)).astype(np.int64),
            label.name: rng.randint(0, vocab,
                                    (bs, seq, 1)).astype(np.int64)}
    import jax
    dev = place.jax_device()
    feed = {k: jax.device_put(v, dev) for k, v in feed.items()}
    for _ in range(2):
        exe.run(main_prog, feed=feed, fetch_list=[avg_cost])
    import contextlib
    prof_ctx = contextlib.nullcontext()
    profile_dir = None
    if os.environ.get("BENCH_PROFILE"):
        # own subdir: the headline loop's capture globs the same root
        profile_dir = os.path.join(os.environ["BENCH_PROFILE"],
                                   "transformer")
        prof_ctx = jax.profiler.trace(profile_dir)
    from paddle_tpu.observability import metrics as obs_metrics
    h_step = obs_metrics.histogram(
        "bench_transformer_step_ms",
        "per-step wall of the transformer bench loop")
    with prof_ctx:
        t0 = time.time()
        for _ in range(iters):
            ts_step = time.time()
            loss, = exe.run(main_prog, feed=feed,
                            fetch_list=[avg_cost], return_numpy=False)
            h_step.observe((time.time() - ts_step) * 1e3)
        loss = np.asarray(loss)
        elapsed = time.time() - t0
    tokens_per_sec = bs * seq * iters / elapsed
    # fused-stage evidence (ISSUE 7): the JSON row names the program it
    # measured, like the headline's data_format/fused_stages fields
    fwd_fused = [op.type for op in main_prog.desc.blocks[0].ops
                 if op.type.startswith("fused_") and
                 not op.type.endswith("_grad")]
    fused_counts = {}
    for t in fwd_fused:
        fused_counts[t] = fused_counts.get(t, 0) + 1
    out = {
        "metric": "transformer_lm_d%d_L%d_train_bs%d_seq%d%s" % (
            d_model, n_layers, bs, seq, "_bf16" if amp else ""),
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        "vs_baseline": 0.0,  # no reference transformer baseline exists
        "amp": amp,
        "step_ms_p50": round(h_step.percentile(50), 3),
        "step_ms_p90": round(h_step.percentile(90), 3),
        "step_ms_p99": round(h_step.percentile(99), 3),
        "fused_stages": len(fwd_fused),
    }
    if fused_counts:
        out["fused_stage_counts"] = fused_counts
    if FLAGS.autotune_cache_dir:
        from paddle_tpu import tuning
        out["autotune_cache_dir"] = FLAGS.autotune_cache_dir
        out["autotune_cache_entries"] = len(tuning.entries())
    if profile_dir:
        cats = _xplane_categories(profile_dir)
        if cats:
            out["per_category_ms"] = cats
    if on_accel:
        # standard analytic count: 6*N_params FLOPs/token (fwd+bwd) +
        # causal attention 6*L*d_model*T (the scaling-book estimate)
        n_params = sum(
            int(np.prod(p.shape))
            for p in main_prog.global_block().all_parameters())
        flops_tok = 6.0 * n_params + 6.0 * n_layers * d_model * seq
        tflops = tokens_per_sec * flops_tok / 1e12
        out["params_m"] = round(n_params / 1e6, 1)
        out["tflops"] = round(tflops, 1)
        if amp:
            out["mfu"] = round(
                tflops / device_peaks(dev.device_kind)["bf16_tflops"], 3)
    if as_dict:
        return out
    print(json.dumps(out))


def lstm_bench(on_accel):
    """BENCH_MODEL=lstm: the stacked dynamic-LSTM text classifier
    (fluid-benchmark stacked_dynamic_lstm).  Reports ms/batch alongside
    examples/sec — the reference's legacy LSTM numbers are ms/batch
    (benchmark/README.md:113-135: 184 ms at bs64/hidden512 on a K40m)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import stacked_dynamic_lstm

    if on_accel:
        bs = int(os.environ.get("BENCH_BATCH", "64"))
        hidden = int(os.environ.get("BENCH_HIDDEN", "512"))
        seq = int(os.environ.get("BENCH_SEQ", "80"))
        iters = int(os.environ.get("BENCH_ITERS", "30"))
    else:
        bs, hidden, seq, iters = 4, 32, 16, 3
    amp = os.environ.get("BENCH_AMP", "1" if on_accel else "0") == "1"

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        avg_cost, (words, label), _ = stacked_dynamic_lstm.get_model(
            dict_dim=5000, hidden_dim=hidden)
    if amp:
        fluid.transpiler.Float16Transpiler().transpile(main_prog)
    place = fluid.TPUPlace() if on_accel else fluid.CPUPlace()
    exe = fluid.Executor(place)
    exe.run(startup)

    rng = np.random.RandomState(0)
    feeder = fluid.DataFeeder([words, label], program=main_prog)
    batch = [(rng.randint(0, 5000, seq).tolist(), [int(rng.randint(2))])
             for _ in range(bs)]
    feed = feeder.feed(batch)
    for _ in range(2):
        exe.run(main_prog, feed=feed, fetch_list=[avg_cost])
    t0 = time.time()
    for _ in range(iters):
        loss, = exe.run(main_prog, feed=feed, fetch_list=[avg_cost],
                        return_numpy=False)
    np.asarray(loss)
    elapsed = time.time() - t0
    ms_per_batch = elapsed / iters * 1000
    # K40m, bs64 hidden512 (benchmark/README.md:113-119 via
    # BASELINE.md:20).  Indicative: that net is a 2-layer LSTM stack,
    # this model is the fluid-benchmark 3-stack — and the ratio is only
    # emitted when the run matches the baseline's bs/hidden config.
    baseline_ms = 184.0
    vs = (round(baseline_ms / ms_per_batch, 3)
          if (bs, hidden) == (64, 512) else 0.0)
    print(json.dumps({
        "metric": "stacked_lstm_train_bs%d_h%d_seq%d%s" % (
            bs, hidden, seq, "_bf16" if amp else ""),
        "value": round(ms_per_batch, 2),
        "unit": "ms/batch",
        "vs_baseline": vs,
        "examples_per_sec": round(bs * iters / elapsed, 1),
        "amp": amp,
    }))


def main():
    model_name = os.environ.get("BENCH_MODEL", "resnet50")
    if model_name not in ("resnet50", "resnet32", "vgg", "transformer",
                          "lstm", "alexnet", "googlenet"):
        raise SystemExit(
            "BENCH_MODEL must be resnet50|resnet32|vgg|transformer|"
            "lstm|alexnet|googlenet, got %r" % model_name)
    # Scheduler-flag knobs (ISSUE 5 lever c) must hit XLA_FLAGS BEFORE
    # the first backend touch (the liveness probe below initializes
    # jax); FLAGS_xla_latency_hiding_scheduler=1 / FLAGS_xla_extra_flags
    # env vars flow through the flag registry into apply_xla_flags, and
    # the same values ride the executor compile-cache key.
    from paddle_tpu.core.flags import (FLAGS, apply_xla_flags,
                                       ensure_compile_cache)
    xla_tokens = apply_xla_flags()
    ensure_compile_cache()
    # a driver SIGTERM (wall-clock kill) leaves a flight-recorder JSON
    # naming the open span every thread was blocked in, instead of
    # nothing (ISSUE 6 tentpole d).  SIGALRM stays with _wall_budget,
    # whose handler dumps before raising.
    try:
        from paddle_tpu.observability import flight
        flight.install_signal_handlers(("SIGTERM",))
    except Exception:
        pass
    # Watchtower (ISSUE 13): with FLAGS_tsdb_dir set, a bench run
    # retains its whole metric history (bench_step_ms, compile-cache
    # counters, numerics gauges) as durable time series the perf
    # sentinel and watchtower report read afterwards
    try:
        from paddle_tpu.observability import tsdb as _tsdb
        _tsdb.ensure_sampler()
    except Exception:
        pass
    # liveness first: an unreachable backend yields a fast, explicit
    # JSON error artifact (and a failing exit code) instead of an rc:124
    # with nothing on stdout
    try:
        _probe_backend(float(os.environ.get("BENCH_LIVENESS_TIMEOUT",
                                            "90")))
    except Exception as e:
        _exit_with_error_artifact("%s_train" % model_name, e)
    import jax
    on_accel = any(d.platform != "cpu" for d in jax.devices())
    # a CPU run is one STARTED with JAX_PLATFORMS=cpu; landing on the
    # host because jax found no chip is a failed run, not a slow one
    if not on_accel and os.environ.get("JAX_PLATFORMS", "") != "cpu":
        print(json.dumps({
            "metric": "%s_train" % model_name,
            "error": "no accelerator: jax found %r (a CPU run sets "
                     "JAX_PLATFORMS=cpu)" % (jax.devices(),)}),
            flush=True)
        return 1
    if model_name == "transformer":
        return transformer_bench(on_accel)
    if model_name == "lstm":
        return lstm_bench(on_accel)
    # Keep CPU smoke-runs fast; real run uses ImageNet shapes.
    if on_accel:
        batch_size = int(os.environ.get("BENCH_BATCH", "256"))
        data_set = os.environ.get("BENCH_DATASET", "flowers")
        iters = int(os.environ.get("BENCH_ITERS", "60"))
    else:
        batch_size = int(os.environ.get("BENCH_BATCH", "16"))
        data_set = os.environ.get("BENCH_DATASET", "cifar10")
        iters = int(os.environ.get("BENCH_ITERS", "5"))
    amp = os.environ.get("BENCH_AMP", "1" if on_accel else "0") == "1"
    # Real data is the accelerator default for the ResNet headline (the
    # only mode with the uint8 device-normalize input); BENCH_FAKE
    # overrides either way.
    use_fake = os.environ.get(
        "BENCH_FAKE",
        "0" if (on_accel and model_name == "resnet50") else "1") == "1"
    uint8_input = not use_fake and model_name == "resnet50"

    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import alexnet, googlenet, resnet, vgg

    # measured knobs (see PROFILE_r04.md for the numbers behind the
    # defaults): bf16 pass-through batch_norm and NHWC conv lowering
    if os.environ.get("BENCH_BN_BF16", "1" if amp else "0") == "1":
        FLAGS.bn_bf16 = True
    if os.environ.get("BENCH_NHWC", "0") == "1":
        FLAGS.conv_nhwc = True
    # ISSUE 5 levers a/b: the layout-pinned NHWC pipeline + Pallas
    # fused conv stages (models/resnet.py runs the LayoutTranspiler
    # pre-minimize when the flag says NHWC).  BENCH_LAYOUT=NHWC /
    # BENCH_FUSED_STAGES=0 control them; FLAGS_conv_layout env works
    # too.  NCHW default — the bisection baseline.
    data_format = os.environ.get("BENCH_LAYOUT", FLAGS.conv_layout or
                                 "NCHW").upper()
    FLAGS.conv_layout = data_format
    if os.environ.get("BENCH_FUSED_STAGES") is not None:
        FLAGS.conv_fused_stages = \
            os.environ["BENCH_FUSED_STAGES"] == "1"
    bench_depth = int(os.environ.get("BENCH_DEPTH", "0"))
    # numerics observatory (ISSUE 8): BENCH_CHECK_NUMERICS=metrics runs
    # the headline WITH the fused health fetch (grad-norm / absmax /
    # nonfinite stats in the always-on registry) — the measured
    # overhead per mode is recorded in PROFILE_r08.md, and the JSON row
    # carries the mode so A/B rows stay self-describing
    if os.environ.get("BENCH_CHECK_NUMERICS"):
        FLAGS.check_numerics = os.environ["BENCH_CHECK_NUMERICS"]

    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        if model_name == "vgg":
            # vgg16_bn_drop — the fluid-benchmark VGG config; the only
            # published reference number is legacy VGG-19 on CPU
            avg_cost, (data, label), (acc,) = vgg.get_model(
                data_set=data_set)
        elif model_name in ("alexnet", "googlenet"):
            # legacy-benchmark families: 224x224 only (googlenet's final
            # 7x7 avg pool requires it), so BENCH_DATASET is ignored and
            # the CPU smoke path shrinks batch/iters instead of shapes
            data_set = "flowers"
            if not on_accel:
                batch_size, iters = min(batch_size, 4), min(iters, 2)
            mod = alexnet if model_name == "alexnet" else googlenet
            avg_cost, (data, label), (acc,) = mod.get_model()
        else:
            avg_cost, (data, label), (acc,) = resnet.get_model(
                data_set=data_set,
                depth=bench_depth or (50 if model_name == "resnet50"
                                      else 32),
                input_dtype="uint8" if uint8_input else "float32")
    if amp:
        fluid.transpiler.Float16Transpiler().transpile(main_prog)

    place = fluid.TPUPlace() if on_accel else fluid.CPUPlace()
    exe = fluid.Executor(place)
    exe.run(startup)

    dshape = [batch_size] + list(data.shape[1:])
    rng = np.random.RandomState(0)
    if uint8_input:  # warmup must compile the same (uint8) feed spec
        images = rng.randint(0, 256, dshape).astype(np.uint8)
    else:
        images = rng.rand(*dshape).astype(np.float32)
    class_dim = 102 if data_set == "flowers" else 10
    labels = rng.randint(0, class_dim, (batch_size, 1)).astype(np.int64)
    feed = {data.name: images, label.name: labels}

    # Real-data mode: a flowers-shaped recordio file feeds training.
    # Images travel uint8 and are cast+scaled on device (get_model
    # input_dtype='uint8') — the TPU-native version of the reference's
    # host-side normalize, at a quarter of the f32 link bytes.
    #
    # Datasets that fit in HBM go through DeviceDatasetCache (recordio
    # scanner -> stage once -> per-epoch jitted shuffle + gather, zero
    # per-step host traffic — the tf.data cache()-on-accelerator idiom).
    # Larger datasets stream through the decorated chain — recordio ->
    # shuffle -> batch -> double-buffered DeviceLoader (reference reader
    # decorators + create_recordio_file_reader /
    # create_double_buffer_reader_op).  The post-loop stream probe
    # measures both on the run's own device (h2d_mb_per_sec_idle,
    # streaming_imgs_per_sec).
    loader_iter = None
    device_cached = False
    if not use_fake:
        import paddle_tpu as pt
        from paddle_tpu.reader import creator

        rio_path = _ensure_bench_recordio(dshape[1:], data_set)
        img_elems = int(np.prod(dshape[1:]))

        def _deser(rec):
            lab = np.frombuffer(rec, np.int64, count=1)
            img = np.frombuffer(rec, np.uint8, offset=8,
                                count=img_elems).reshape(dshape[1:])
            if not uint8_input:  # program without the uint8 front-end
                img = img.astype(np.float32) / 255.0
            return img, lab

        base = creator.recordio(rio_path, _deser)
        try:
            loader = pt.reader.DeviceDatasetCache(
                base, [data.name, label.name], place, batch_size,
                max_bytes=int(os.environ.get("BENCH_CACHE_BUDGET",
                                             str(4 << 30))))
            device_cached = True
        except pt.reader.DatasetExceedsBudget:
            loader = pt.reader.DeviceLoader(
                pt.batch(pt.reader.shuffle(base, buf_size=batch_size * 4),
                         batch_size=batch_size),
                [data.name, label.name], place, capacity=3)

        def forever():
            while True:
                n = 0
                for d in loader:  # each epoch reshuffles (+restages)
                    n += 1
                    yield d
                if n == 0:
                    raise RuntimeError("reader yielded no batches")

        loader_iter = forever()
        # warm up (compile) with a real loader batch: its feed spec is
        # what the timed loop sees (device-resident, int32 labels after
        # the x64-off conversion) — warming with the synthetic host
        # batch would compile a second program inside the timed loop
        feed = next(loader_iter)

    # Pre-stage the batch on device (the reference reads from a
    # double-buffered reader; a constant device-resident batch is the
    # use_fake_data analog) and warm up compile + autotuning.
    dev = place.jax_device()
    feed = {k: jax.device_put(v, dev) for k, v in feed.items()}
    for _ in range(2):
        exe.run(main_prog, feed=feed, fetch_list=[avg_cost])

    # Timed loop: steps are dispatched asynchronously (XLA execution is
    # async like the reference's CUDA streams); one sync at the end.
    # BENCH_PROFILE=<dir> wraps the loop in jax.profiler.trace and
    # prints the per-hlo-category breakdown (utils/xplane.py) to stderr.
    import contextlib
    profile_dir = os.environ.get("BENCH_PROFILE")
    prof_ctx = contextlib.nullcontext()
    if profile_dir:
        prof_ctx = jax.profiler.trace(profile_dir)
    # Prepared hot path (Executor.prepare / run_prepared): per-step cost
    # is feed staging + one dispatch — parameters/optimizer state stay
    # device-resident instead of round-tripping the Scope every step.
    # BENCH_PREPARED=0 times the classic run() path instead.
    prepared = None
    if os.environ.get("BENCH_PREPARED", "1") == "1":
        try:
            prepared = exe.prepare(main_prog, feed_specs=feed,
                                   fetch_list=[avg_cost])
        except ValueError:
            prepared = None  # host ops in the block: run() path
    # per-step wall times land in an always-on metrics histogram; the
    # JSON's step_ms_p50/p90/p99 come from ITS snapshot (ISSUE 6).
    # Steps are dispatched async, so per-step wall is host-side issue
    # time except the final step, which absorbs the drain — the
    # percentiles catch host-side stalls (recompiles, loader hiccups)
    # the mean hides.
    from paddle_tpu.observability import metrics as obs_metrics
    h_step = obs_metrics.histogram(
        "bench_step_ms", "per-step wall of the timed bench loop")
    with prof_ctx:  # exception-safe: a mid-run OOM still finalizes
        t0 = time.time()
        t_host = 0.0  # host-side dispatch time (wall minus run-ahead)
        prepared_steps = 0
        loss = None
        from paddle_tpu.core.executor_impl import PreparedShapeMismatch
        for _ in range(iters):
            ts_step = time.time()
            step_feed = next(loader_iter) if loader_iter is not None \
                else feed
            td = time.time()
            if prepared is not None:
                try:
                    loss, = prepared.run_prepared(step_feed)
                    prepared_steps += 1
                except PreparedShapeMismatch:
                    # AOT fixed-shape entry + a drifted (partial) batch:
                    # flush the device state BEFORE dropping the last
                    # reference, then finish the loop via run().  The
                    # sync is transition cost, not dispatch cost — keep
                    # it out of t_host so step_host_ms stays steady-state
                    prepared.sync_scope()
                    prepared = None
                    td = time.time()
            if prepared is None:
                loss, = exe.run(main_prog, feed=step_feed,
                                fetch_list=[avg_cost],
                                return_numpy=False)
            t_host += time.time() - td
            h_step.observe((time.time() - ts_step) * 1e3)
        loss = np.asarray(loss)  # blocks until the chain has drained
        elapsed = time.time() - t0
    if prepared is not None:
        prepared.sync_scope()
    # xplane-sourced per-category device ms for the headline JSON
    # (ISSUE 5): where the step's bytes actually go — the "data
    # formatting" row is lever (a)'s target
    per_category_ms = _xplane_categories(profile_dir) if profile_dir \
        else None

    images_per_sec = batch_size * iters / elapsed

    # Streaming-input evidence: measure the host-to-device link and the
    # streaming DeviceLoader path so the cache-vs-stream decision above
    # cites numbers, not an assertion.  Runs AFTER the timed loop so the
    # headline is undisturbed.  BENCH_STREAM_PROBE=0 skips.
    stream_stats = {}

    def _stream_probe():
        import paddle_tpu as pt
        from paddle_tpu.reader import creator

        dev = place.jax_device()
        # (a) idle-device h2d bandwidth: one big uint8 buffer, drained
        # by a 1-element d2h fetch
        nbytes = 64 << 20
        buf = np.ones(nbytes, np.uint8)
        t0 = time.time()
        x = jax.device_put(buf, dev)
        _ = np.asarray(x[:1])
        stream_stats["h2d_mb_per_sec_idle"] = round(
            nbytes / (time.time() - t0) / 1e6, 1)
        del x
        # (b) the streaming DeviceLoader path end-to-end (recordio ->
        # shuffle -> batch -> double-buffered h2d overlapped with the
        # training step): images/sec over a short run
        base = creator.recordio(rio_path, _deser)
        sloader = pt.reader.DeviceLoader(
            pt.batch(pt.reader.shuffle(base, buf_size=batch_size * 4),
                     batch_size=batch_size),
            [data.name, label.name], place, capacity=3)
        sit = iter(sloader)
        sfeed = next(sit)
        exe.run(main_prog, feed=sfeed, fetch_list=[avg_cost])  # warm
        s_iters = int(os.environ.get("BENCH_STREAM_ITERS", "8"))
        t0 = time.time()
        sloss = None
        n_done = 0
        for sfeed in sit:
            sloss, = exe.run(main_prog, feed=sfeed,
                             fetch_list=[avg_cost], return_numpy=False)
            n_done += 1
            if n_done >= s_iters:
                break
        np.asarray(sloss)
        t_stream = time.time() - t0
        stream_stats["streaming_imgs_per_sec"] = round(
            batch_size * n_done / t_stream, 1)
        # (c) overlap evidence: does the double buffer hide transfer
        # behind compute?  Per-step wall of the streamed run vs the
        # sum of its parts (compute-only step at the headline rate +
        # this batch's bytes at the idle h2d rate).  ratio ->
        # ~(a+b)/max(a,b) means full overlap, ~1.0 means serialized;
        # tests/test_data_pipeline.py proves the loader overlaps where
        # the transport allows it.
        batch_mb = sum(v.nbytes for v in sfeed.values()) / 1e6 \
            if hasattr(next(iter(sfeed.values())), "nbytes") else 0.0
        t_compute = batch_size / max(images_per_sec, 1e-9)
        t_h2d = batch_mb / max(
            stream_stats.get("h2d_mb_per_sec_idle", 1e9), 1e-9)
        t_step = t_stream / max(n_done, 1)
        stream_stats["stream_overlap_ratio"] = round(
            (t_compute + t_h2d) / max(t_step, 1e-9), 3)

    if model_name == "vgg":
        # closest published number: legacy VGG-19 train, MKL-DNN CPU,
        # bs256 (IntelOptimizedPaddle.md:36) — vgg16 here, so the ratio
        # is indicative, not exact
        baseline = 30.44
    elif model_name == "alexnet":
        baseline = 626.53  # MKL-DNN CPU bs256 (IntelOptimizedPaddle.md:63)
    elif model_name == "googlenet":
        baseline = 269.50  # MKL-DNN CPU bs256 (IntelOptimizedPaddle.md:54)
    else:
        baseline = 81.69  # MKL-DNN CPU ResNet-50 bs64 (IntelOptimizedPaddle.md:41)
    out = {
        "metric": "%s_%s_train_bs%d%s" % (
            model_name, data_set, batch_size, "_bf16" if amp else ""),
        "value": round(images_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(images_per_sec / baseline, 3),
        "amp": amp,
        "fake_data": use_fake,
        # dispatch-cost tracking (ISSUE 2): per-step wall, the host
        # time spent issuing the step (wall minus the device run-ahead
        # the async dispatch buys), and its share of the step — future
        # BENCH_*.json watch this for host-side regressions.
        # prepared_steps < iters means a mid-loop fallback to run()
        # (AOT shape drift) mixed the timings.
        "prepared": prepared_steps == iters,
        "prepared_steps": prepared_steps,
        "step_wall_ms": round(elapsed / iters * 1e3, 3),
        "step_host_ms": round(t_host / iters * 1e3, 3),
        "host_overhead_frac": round(t_host / max(elapsed, 1e-9), 4),
        # per-step distribution, sourced from the telemetry histogram
        # (ISSUE 6): tail stalls (recompiles, loader hiccups) show in
        # p99 where the mean hides them.  The last step absorbs the
        # async drain, so p99 ~ the device step time.
        "step_ms_p50": round(h_step.percentile(50), 3),
        "step_ms_p90": round(h_step.percentile(90), 3),
        "step_ms_p99": round(h_step.percentile(99), 3),
        # numerics observatory mode this row ran under (ISSUE 8); with
        # 'metrics' on, grad_global_norm percentiles ride along below
        # so the bench doubles as a training-health probe
        "check_numerics": str(FLAGS.check_numerics or "off"),
        # ISSUE 5 lever evidence: layout, fused stage count and the
        # scheduler flags the run compiled under — BENCH_*.json rows
        # are self-describing experiments, not env archaeology.
        # data_format reflects the PROGRAM (only models that honor
        # FLAGS_conv_layout transpile; vgg/alexnet/googlenet stay NCHW)
        "data_format": ("NHWC" if any(
            op.attr("data_format", op.attr("data_layout", "NCHW"))
            == "NHWC" for op in main_prog.desc.blocks[0].ops)
            else "NCHW"),
        "fused_stages": sum(
            1 for op in main_prog.desc.blocks[0].ops
            if op.type == "fused_conv2d_bn_act"),
        "xla_flags": xla_tokens,
    }
    if per_category_ms:
        out["per_category_ms"] = per_category_ms
    if out["check_numerics"] not in ("", "off"):
        # training-health evidence from the always-on registry
        # (observability/numerics.py): the run's grad-norm distribution
        # + any nonfinite sightings
        from paddle_tpu.observability import metrics as _metrics
        snap = _metrics.snapshot()
        gh = snap.get("grad_global_norm", {})
        out["grad_global_norm_p50"] = gh.get("p50", 0.0)
        out["grad_global_norm_p99"] = gh.get("p99", 0.0)
        out["nonfinite_total"] = snap.get(
            "numerics_nonfinite_total", {}).get("value", 0)
    if bench_depth:
        out["depth"] = bench_depth  # non-default model size: mark it
    if not use_fake:
        out["device_cached"] = device_cached
    # 224x224 only: that's what the analytic FLOP counts are for
    per_img = {"resnet50": TRAIN_FLOPS_PER_IMG_224,
               "vgg": TRAIN_FLOPS_PER_IMG_VGG16_224}.get(model_name)
    if data_set in ("flowers", "imagenet") and per_img:
        tflops = images_per_sec * per_img / 1e12
        out["tflops"] = round(tflops, 1)
        if amp:  # MFU only vs the bf16 peak the run actually targets
            from paddle_tpu.core.peaks import V5E, device_peaks
            out["mfu"] = round(
                tflops / device_peaks(dev.device_kind)["bf16_tflops"], 3)
            # Roofline context, measured via utils/xplane.py category
            # profiles committed in PROFILE_r04.md (v5e, bs256): ResNet-50 bf16 is HBM-bound — 94% of
            # device step time runs inside XLA fusions at 82-85% of the
            # 819 GB/s HBM peak (conv fusions: 85% HBM, 38% MXU),
            # because the model's arithmetic intensity sits far below
            # the chip's ridge point (197e12/819e9 ≈ 240 FLOP/byte).
            # At 100% HBM for the bytes XLA actually schedules the
            # analytic-FLOP MFU caps at ~0.20 (0.167/0.85); bf16-BN,
            # NHWC and bs512 are all measured ≤±1% (PROFILE_r04.md
            # knob table).  A compute-bound workload on the same stack
            # reaches 0.52 (see secondary).  Only emitted for the
            # measured config so another chip/batch never inherits it.
            if (model_name == "resnet50" and batch_size == 256
                    and dev.device_kind == V5E):
                out["hbm_bound"] = True
                out["mfu_roofline_cap"] = 0.20
                out["profile_evidence"] = "PROFILE_r04.md"
    # the headline is UN-LOSABLE: emit it the moment it exists, BEFORE
    # the stream probe / secondary bench — if either wedges past its
    # budget or the process dies, the driver still has this line.  The
    # enriched line at exit repeats it with the evidence fields.
    print(json.dumps(dict(out, partial=True)), flush=True)

    if (not use_fake and on_accel
            and os.environ.get("BENCH_STREAM_PROBE", "1") == "1"):
        try:
            with _wall_budget(
                    float(os.environ.get("BENCH_STREAM_BUDGET", "180")),
                    "stream probe"):
                _stream_probe()
        except Exception as e:
            # the headline above is already out; the failure rides the
            # final line AND the exit code
            stream_stats["stream_probe_error"] = str(e)[:200]
    if not use_fake:
        out.update(stream_stats)
    if on_accel and model_name == "resnet50" and \
            os.environ.get("BENCH_SECONDARY", "1") == "1":
        try:
            with _wall_budget(
                    float(os.environ.get("BENCH_SECONDARY_BUDGET",
                                         "420")),
                    "secondary transformer bench"):
                out["secondary"] = transformer_bench(True, as_dict=True)
        except Exception as e:  # the headline is out; fail the exit code
            out["secondary_error"] = str(e)[:200]
    print(json.dumps(out))
    return 1 if ("stream_probe_error" in out
                 or "secondary_error" in out) else 0


if __name__ == "__main__":
    sys.exit(main())
