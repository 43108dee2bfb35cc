"""Runtime flag registry — the gflags analog.

Parity: reference platform/enforce + gflags flags (FLAGS_check_nan_inf
in framework/operator.cc:590, FLAGS_benchmark in executor.cc, plus the
env forwarding done by python/paddle/fluid/__init__.py:__bootstrap__,
which passes selected FLAGS_* env vars to InitGflags).  Here flags are
plain Python with the same ``FLAGS_<name>`` environment override.
"""
from __future__ import annotations

import os

__all__ = ["FLAGS", "define_flag", "apply_xla_flags",
           "ensure_compile_cache"]


def _parse(raw, default):
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


class _Flags:
    """Attribute-style access; unknown flags raise AttributeError."""

    def __init__(self):
        object.__setattr__(self, "_defs", {})
        object.__setattr__(self, "_watchers", {})

    def define(self, name, default, help=""):
        raw = os.environ.get("FLAGS_" + name)
        value = _parse(raw, default) if raw is not None else default
        self._defs[name] = {"value": value, "default": default,
                            "help": help}

    def __getattr__(self, name):
        try:
            return self._defs[name]["value"]
        except KeyError:
            raise AttributeError("undefined flag %r (define it with "
                                 "flags.define_flag)" % name)

    def __setattr__(self, name, value):
        if name not in self._defs:
            raise AttributeError("undefined flag %r" % name)
        self._defs[name]["value"] = value
        for fn in self._watchers.get(name, ()):
            fn(value)

    def watch(self, name, fn):
        """Call ``fn(value)`` now and again on every later
        ``FLAGS.<name> = value`` assignment — for flags whose value is
        mirrored into a hot-path attribute (e.g. FLAGS_telemetry ->
        observability TRACER.on: the mirror keeps the per-step check to
        one attribute read, the watcher keeps a runtime flag flip from
        being silently ignored)."""
        self._watchers.setdefault(name, []).append(fn)
        if name in self._defs:
            fn(self._defs[name]["value"])

    def flags(self):
        return {k: v["value"] for k, v in self._defs.items()}


FLAGS = _Flags()


def define_flag(name, default, help=""):
    FLAGS.define(name, default, help)


def apply_xla_flags():
    """Materialize the FLAGS_xla_* scheduler knobs into XLA_FLAGS.

    XLA parses XLA_FLAGS exactly once, at first backend creation, so
    call this BEFORE the first jax device touch (bench.py does; the
    executor calls it defensively at first compile).  Returns the tokens
    applied.  The same values ride the executor compile-cache key, so an
    in-process flag flip can never serve a stale executable — but it
    still needs a fresh process to reach XLA itself (MIGRATION.md)."""
    tokens = []
    if FLAGS.xla_latency_hiding_scheduler:
        tokens.append("--xla_tpu_enable_latency_hiding_scheduler=true")
    if FLAGS.xla_extra_flags:
        tokens.extend(str(FLAGS.xla_extra_flags).split())
    if not tokens:
        return []
    cur = os.environ.get("XLA_FLAGS", "")
    have = set(cur.split())
    missing = [t for t in tokens if t not in have]
    if missing:
        os.environ["XLA_FLAGS"] = (cur + " " + " ".join(missing)).strip()
    return tokens


# <checkout>/.jax_cache: fixed, because the directory is part of the
# cache key — one built from a temp dir, a pid or a time never hits
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_compile_cache():
    """Turn on jax's persistent compilation cache where it can be found
    again: the directory JAX_COMPILATION_CACHE_DIR names when that is
    set (jax reads it itself — nothing is set here), else
    COMPILE_CACHE_DIR.  Every executable is kept, however quick its
    compile: the serving ladders are dozens of few-second compiles.
    Called wherever the framework first touches jax (ExecutorCore,
    GenerativeEngine, the entry scripts); returns the directory.

    A CPU run — one started with JAX_PLATFORMS=cpu: the tests, the CPU
    tools — keeps jax's default (no cache unless the variable asks for
    one): compiles are cheap there, and XLA:CPU's loader logs a
    spurious machine-feature error on every cache hit."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        if jax.config.jax_platforms == "cpu":
            return None
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    # (the entry-size threshold already defaults to 0 in this jax)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir


# core runtime flags (reference analogs cited above)
define_flag("check_nan_inf", False,
            "raise on the first op producing nan/inf, naming it "
            "(reference FLAGS_check_nan_inf).  run() executes op-by-op "
            "like the reference; the prepared hot path instead maps "
            "this onto the ISSUE 8 numerics observatory (fused health "
            "fetch + bisect re-run of a tripped step — same first-bad-"
            "op answer, one-dispatch steps; see FLAGS_check_numerics "
            "in observability/numerics.py and MIGRATION.md)")
define_flag("benchmark", False,
            "print per-run wall time (reference FLAGS_benchmark)")
define_flag("check_program", "warn",
            "ahead-of-time program verification (paddle_tpu/analysis): "
            "'off' never verifies; 'warn' (default) verifies each "
            "program once per (uid, version) — i.e. only on a "
            "compile-cache miss — and warns on error-severity "
            "diagnostics; 'error' raises ProgramVerificationError "
            "instead.  Zero per-step cost: steady-state training never "
            "re-verifies")
define_flag("check_suppress", "",
            "comma-separated checker names the default verification "
            "pipeline skips (e.g. 'lifetime,numerics'): applies to the "
            "executor verify hook and any verify_program call that "
            "does not name explicit checkers.  The escape hatch for "
            "FLAGS_check_program=error users when a new checker lands "
            "— see MIGRATION.md 'Donation-lifetime checker'")
define_flag("sanitizer", "off",
            "runtime sanitizers (core/sanitizer.py): 'off' (default; "
            "the instrumented hot paths pay ONE module-attribute read, "
            "gated < 2% by tools/telemetry_overhead.py), 'buffers' "
            "(use-after-donate checking: every donation swaps the "
            "aliasing scope slot to a poisoned husk that raises "
            "BufferLifetimeError naming var/op/step/site on any host "
            "access before re-bind), 'locks' (lock-discipline "
            "checking: instrumented locks record per-thread "
            "acquisition order, detect order-inversion cycles and "
            "non-reentrant acquisition on signal-handler-reachable "
            "paths, reported as lockgraph_<pid>.json), 'all', or "
            "'weaver' (deterministic-schedule exploration: make_lock/"
            "make_event/make_condition hand out analysis/weaver.py "
            "primitives whose every acquire/release/wait/notify is a "
            "scheduling decision under the active Weaver's virtual "
            "clock; implies buffer checking so scenario invariants "
            "can trip; see tools/weaver.py).  "
            "Lock instrumentation is chosen at lock CREATION time — "
            "set the flag (or FLAGS_sanitizer env) before the "
            "subsystems under test construct their locks.  Every trip "
            "increments sanitizer_trips_total and leaves a flight "
            "dump when FLAGS_telemetry_dump_dir is set")
define_flag("conv_nhwc", False,
            "lower conv2d through NHWC (MXU-preferred layout); the "
            "boundary transposes cancel across conv chains in XLA")
define_flag("bn_bf16", False,
            "under AMP, let batch_norm consume/produce bf16 (statistics "
            "stay f32 internally, like layer_norm) instead of casting "
            "its inputs to f32; halves BN-chain activation bytes on "
            "HBM-bound conv nets")
define_flag("matmul_precision", "",
            "XLA dot/conv precision for f32 operands: '' (backend "
            "default: TPU multiplies f32 in bf16 passes, the fast "
            "mode), 'float32'/'highest' (exact f32, ~3-6x slower "
            "matmuls on TPU).  The TPU analog of the reference's "
            "cuDNN math-mode control; see MIGRATION.md 'float32 "
            "matmul precision on TPU'")
define_flag("conv_layout", "NCHW",
            "convnet pipeline layout: 'NCHW' (reference contract; the "
            "default) or 'NHWC' — models that honor the flag (e.g. "
            "models/resnet.py get_model) run the LayoutTranspiler NHWC "
            "pass: data_format propagated through conv/pool/bn/"
            "elementwise chains, conv weights pinned HWIO at creation, "
            "and conv+BN+act stages fused into the Pallas conv-stage "
            "kernel.  Acts at PROGRAM BUILD time (get_model) — flip it "
            "before building, not on a built program; the NCHW program "
            "stays selectable for bisection")
define_flag("conv_fused_stages", True,
            "with conv_layout=NHWC, also run FuseConvBNActPass "
            "(conv+BN(+residual)(+relu) -> fused_conv2d_bn_act backed "
            "by kernels/conv_fused.py); off = layout pass alone, for "
            "attributing wins between the two levers")
define_flag("transformer_fuse", False,
            "transformer block fusion (ISSUE 7): models that honor the "
            "flag (models/transformer.py get_model) run "
            "FuseTransformerBlockPass before backward generation — the "
            "QKV projections collapse to one wide matmul, "
            "matmul+bias(+gelu/relu)(+dropout)(+residual) chains and "
            "residual-add+layer_norm chains become fused ops backed by "
            "kernels/matmul_fused.py (f32 VMEM accumulator epilogues, "
            "explicit saved-activation grad lowerings, identical-math "
            "XLA fallback off-TPU / over-budget).  Acts at PROGRAM "
            "BUILD time, like conv_layout; the unfused program stays "
            "the default for bisection")
define_flag("autotune_cache_dir", "",
            "persistent shape-keyed autotune cache directory "
            "(paddle_tpu/tuning): sweep tools (conv_tune/flash_tune/"
            "matmul_tune) record their best tile configs per (kernel, "
            "shape, dtype, backend) into autotune_cache.json here, and "
            "kernel lowerings consult it at compile time — every "
            "future model inherits the best tiles instead of "
            "re-sweeping.  Unset (default) = built-in defaults; a "
            "corrupt/missing cache file degrades to defaults without "
            "error.  The cache fingerprint rides the executor "
            "compile-cache key, so re-tuning never serves a stale "
            "executable")
define_flag("xla_latency_hiding_scheduler", False,
            "enable XLA's latency-hiding scheduler "
            "(--xla_tpu_enable_latency_hiding_scheduler): overlaps "
            "async copies/collectives with compute when scheduling "
            "fusions.  Applied to XLA_FLAGS by apply_xla_flags() "
            "(bench.py calls it before backend init; flipping it in a "
            "live process needs a restart — XLA parses XLA_FLAGS once) "
            "and part of the executor compile-cache key")
define_flag("xla_extra_flags", "",
            "extra raw XLA_FLAGS tokens appended by apply_xla_flags() "
            "(e.g. '--xla_tpu_enable_async_collective_fusion=true'); "
            "reproducible-experiment plumbing for scheduler knobs — "
            "part of the executor compile-cache key")
define_flag("telemetry", False,
            "span tracing (paddle_tpu/observability): per-step executor "
            "spans, RPC round spans with (round, sender, seq) "
            "correlation ids, Pallas launch-site spans.  Off (the "
            "default) the instrumented hot paths pay one attribute "
            "read — tools/telemetry_overhead.py gates this at < 2% of "
            "the prepared step.  Metrics (counters/histograms) are "
            "ALWAYS on; this flag gates tracing only")
define_flag("telemetry_ring_size", 4096,
            "completed-span ring capacity of the process tracer; the "
            "same ring is the flight recorder's history (oldest spans "
            "evict first)")
define_flag("telemetry_dump_dir", "",
            "when set: processes with tracing on write "
            "trace_<label>_<pid>.json here at exit (merge them with "
            "tools/trace_report.py), flight-recorder dumps "
            "(flight_<pid>_<n>.json) land here instead of the system "
            "temp dir, and injected faults leave one dump per fault "
            "point (tools/fault_matrix.py asserts it)")
define_flag("moe_metrics", True,
            "MoE routing observability (ISSUE 15 rider): the moe_ffn "
            "routing shard emits per-expert load, dropped-token "
            "fraction and router entropy into the always-on metrics "
            "registry via a host callback (one small transfer per "
            "step; tools/trace_report.py --moe rolls them up).  Off "
            "removes the callback from the traced program entirely")
define_flag("serve_max_batch", 16,
            "serving tier (paddle_tpu/serving): cap of the power-of-2 "
            "shape-bucket ladder (1, 2, 4, ... serve_max_batch).  The "
            "continuous batcher assembles at most this many rows per "
            "dispatch; each bucket is backed by its own pre-compiled "
            "AOT executable (compiled at model load for the warm set, "
            "in the background on a bucket miss)")
define_flag("serve_max_wait_us", 2000,
            "serving tier: continuous-batching deadline, microseconds, "
            "anchored at the FIRST queued request's arrival.  The "
            "scheduler launches a batch the moment it is full OR this "
            "deadline expires — it never waits for a full batch, and a "
            "request that arrived while the device was busy ships on "
            "the very next dispatch (its deadline already passed).  "
            "0 = never coalesce-wait: launch whatever is queued")
define_flag("serve_warm_buckets", "",
            "serving tier: comma-separated bucket sizes to pre-compile "
            "at model load (e.g. '1,8').  Empty (default) warms the "
            "whole ladder up to serve_max_batch.  A cold bucket hit at "
            "runtime falls to the nearest warm bucket while a "
            "background thread compiles the missed one")
define_flag("serve_kv_block_size", 16,
            "generative serving (serving/generative.py): tokens per KV "
            "cache block.  Power of two; every sequence's K/V occupies "
            "ceil(context/block_size) blocks of the tenant's paged "
            "pool, gathered through a per-sequence block table by the "
            "decode-mode flash attention kernel "
            "(kernels/flash_attention.paged_attention)")
define_flag("serve_kv_blocks", 512,
            "generative serving: KV cache blocks in a tenant's "
            "device-resident pool (one is reserved as the padding "
            "scratch block).  Memory = 2 x layers x blocks x "
            "block_size x d_model x 4 bytes.  When admission or "
            "mid-decode growth would exceed the pool, the scheduler "
            "counts serve_kv_alloc_failures_total and preempts the "
            "youngest sequence (serve_kv_preemptions_total) — "
            "recompute-style eviction, requeued at the queue front")
define_flag("serve_prefix_cache", False,
            "generative serving (ISSUE 19): copy-on-write prefix KV "
            "reuse.  On, a tenant keeps a radix index over prompt "
            "token ids at block granularity: admission shares the "
            "cached prefix blocks by refcount (serve_kv_blocks_shared "
            "gauge), prefill computes and stores ONLY the un-cached "
            "suffix (serve_kv_prefix_hits gauge / "
            "serve_prefix_tokens_* counters), a shared block written "
            "mid-block is copied first (COW, "
            "serve_kv_cow_copies_total), and finished prompts' blocks "
            "park in a refcount-zero LRU instead of the free list — "
            "evicted only under allocation pressure.  Per-tenant "
            "override: load_generative(prefix_cache=...)")
define_flag("serve_spec_k", 0,
            "generative serving (ISSUE 19): speculative decoding "
            "draft depth.  k > 0 makes the decode loop propose k "
            "tokens per iteration from the tenant's draft LM (a "
            "load_generative(draft=...) requirement) and verify all "
            "k in ONE batched target dispatch — greedy acceptance "
            "keeps the longest matching prefix plus the target's "
            "correction token, so output stays bit-identical to "
            "non-speculative greedy decode (the certificate in "
            "tools/serve_bench.py).  0 (default) is plain one-token "
            "decode.  Per-tenant override: load_generative(spec_k=...)")
define_flag("dist_compress", "",
            "gradient compression codec for the pserver wire "
            "(distributed/compress.py): '' (raw frames, the default), "
            "'fp16' (half-precision dense grads, bit-exact on fp16-"
            "representable values), 'int8' (per-chunk linear "
            "quantization with a trainer-side error-feedback residual "
            "so the quantization bias cancels across steps), or "
            "'topk' (top-k magnitude sparsification with error "
            "feedback; ratio via FLAGS_dist_topk_ratio).  SelectedRows "
            "grads additionally ship int8 rows + delta-encoded int32 "
            "ids under ANY non-empty mode.  Compressed frames are "
            "wire-format v2: the client negotiates per endpoint "
            "(WireVersion RPC) and falls back to raw frames against an "
            "old server — see MIGRATION.md")
define_flag("dist_topk_ratio", 0.01,
            "fraction of dense-grad elements kept by the 'topk' codec "
            "(indices + values of the largest-|g| entries; the rest "
            "accumulates in the error-feedback residual)")
define_flag("dist_staleness", 0,
            "bounded-staleness sync training: a trainer's barrier for "
            "round r acks once round r-k is applied+durable, so "
            "trainers run up to k rounds ahead of the slowest peer "
            "(param gets accept k-stale values).  0 (default) is "
            "today's fully-synchronous round — bit-exact with the "
            "k-unaware wire.  With k>0 the client retains k+1 rounds "
            "of replay cache; a server crash can lose at most the k "
            "un-acked rounds (bounded loss, like bounded staleness)")
define_flag("dist_hier_local", 0,
            "hierarchical gradient aggregation: number of trainers "
            "per host group (0 disables).  Group leader (lowest "
            "trainer id in the group) pre-reduces the group's grads "
            "locally and makes ONE upload + ONE barrier per round, "
            "cutting pserver ingress and fanin by this factor; "
            "followers talk to the leader over a loopback fastwire "
            "channel (distributed/hierarchy.py) and keep reading "
            "params directly.  Requires PADDLE_TRAINER_ID and "
            "trainers %% dist_hier_local == 0")
define_flag("dist_hier_port", 18970,
            "base TCP port of the host-local aggregation channel; "
            "group g listens on dist_hier_port + g")
define_flag("ledger_sample_ms", 250,
            "resource-ledger sampling interval, milliseconds "
            "(observability/ledger.py): a background collector reads "
            "every registered per-subsystem probe (pserver pending "
            "grads, reply/replay caches, barrier quorum, apply "
            "backlog, hier fan-in buffers, fastwire sockets) at this "
            "rate, exports the values as ledger_* gauges, and appends "
            "them to a bounded time-series ring that rides every "
            "flight-recorder dump.  0 disables the collector (probes "
            "still answer on-demand snapshots).  Overhead gated < 2% "
            "by tools/telemetry_overhead.py")
define_flag("ledger_ring", 2048,
            "samples retained by the resource-ledger time-series ring "
            "(oldest evict first); the flight recorder embeds the "
            "newest slice of it")
define_flag("ledger_watch", "",
            "collapse tripwires: comma-separated 'resource>value' "
            "terms (e.g. 'pserver_pending_grad_bytes>100000000').  "
            "When a sampled ledger value crosses its threshold the "
            "collector writes ONE flight-recorder dump per resource "
            "per process (reason 'ledger:<resource>') carrying the "
            "full ledger series — the scale lab's collapse forensics "
            "(tools/scale_bench.py --collapse)")
define_flag("pserver_reply_cache_mb", 256,
            "byte cap (MB) of the pserver per-shard reply cache "
            "(encoded param frames served to every trainer's get).  "
            "Least-recently-used entries evict past the cap "
            "(pserver_reply_cache_evictions_total counts them) — an "
            "eviction only costs a re-encode on the next get, so a "
            "256-trainer run cannot OOM the server through cached "
            "replies.  0 = unbounded (the pre-ISSUE-12 behavior)")
define_flag("rpc_replay_cache_mb", 512,
            "byte cap (MB) of the trainer-side per-endpoint replay "
            "cache (post-codec grads retained for reconnect replay; "
            "k+1 rounds under bounded staleness).  Oldest non-current "
            "rounds evict first (rpc_replay_cache_evictions_total); "
            "an evicted round is unrecoverable on a server restart "
            "and walks forward as an empty apply, exactly like a "
            "round outside the staleness window — see MIGRATION.md.  "
            "0 = unbounded (the pre-ISSUE-12 behavior)")
define_flag("barrier_rescan", False,
            "legacy O(trainers) barrier-quorum bookkeeping: rescan "
            "the whole sender map on every ack instead of maintaining "
            "the quorum count incrementally.  Exists for the scale "
            "lab's before/after A/B (tools/scale_bench.py "
            "--before-after) — never enable in production")
define_flag("tsdb_dir", "",
            "root directory of the Watchtower time-series store "
            "(observability/tsdb.py).  When set, a background sampler "
            "appends a fixed-interval snapshot of EVERY always-on "
            "metric (counters/gauges + histogram percentiles, with "
            "the resource ledger refreshed into the same row) to a "
            "per-(label, pid) subdirectory of append-only binary "
            "segments — the durable history the SLO engine "
            "(FLAGS_slo_spec), tools/watchtower.py and "
            "tools/perf_sentinel.py read.  Empty disables (the "
            "default: nothing is written)")
define_flag("tsdb_sample_ms", 250,
            "Watchtower sampler interval, milliseconds; 0 disables "
            "the background sampler (explicit "
            "tsdb.sample_registry() calls still work).  Overhead "
            "gated < 2% of the interval by "
            "tools/telemetry_overhead.py")
define_flag("tsdb_segment_bytes", 1 << 20,
            "active tsdb segment seals and rotates at this size; "
            "each sealed segment is one mmap-friendly fixed-width "
            "binary file plus a row in the JSON meta index")
define_flag("tsdb_retention_mb", 64,
            "per-process tsdb byte budget: oldest sealed segments "
            "drop once the store exceeds it (the active segment "
            "always survives).  0 = unbounded")
define_flag("slo_spec", "",
            "SLO specs for the Watchtower burn-rate engine "
            "(observability/slo.py): a .json/.toml file path or an "
            "inline comma-separated objective list "
            "('serve_request_ms.p99<=10,"
            "pserver_rounds_applied_total.rate>=1').  With "
            "FLAGS_tsdb_dir set, a background evaluator checks every "
            "spec against the store on FLAGS_slo_eval_ms cadence; a "
            "window whose burn rate crosses its threshold increments "
            "slo_alerts_total and writes ONE flight dump per "
            "(slo, window) with the offending series embedded")
define_flag("slo_eval_ms", 1000,
            "SLO evaluation cadence, milliseconds; 0 disables the "
            "background evaluator (slo.evaluate_once() still works)")
define_flag("auto_layout", False,
            "single-device accelerator path: AOT-compile with XLA-chosen "
            "(AUTO) parameter layouts and keep persistable buffers in "
            "them across steps.  Experimental knob: measured neutral on "
            "ResNet-50/transformer (XLA's default argument layouts "
            "already match; the profile's relayout copies are internal "
            "to conv scheduling), but it removes boundary copies when a "
            "model's parameters do want non-default layouts")
