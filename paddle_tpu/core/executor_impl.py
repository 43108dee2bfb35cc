"""Core Executor: runs a block of a ProgramDesc against a Scope.

Parity: reference framework/executor.cc:127 (Executor::Run / Prepare /
RunPreparedContext).  Two paths:

- **Compiled path** (the normal one): the block is functionalized and lowered
  to a single jitted XLA computation (see lowering.py), cached on
  (program uid+version, block, feed specs, fetch list, mode).  Persistable
  inputs that the block writes (optimizer in-place updates) are donated so
  XLA reuses their buffers — the analog of the reference's buddy-allocator
  reuse + in-place optimizer ops.
- **Interpreted path**: if host ops (save/load/print/readers/RPC) appear
  between device ops, ops run one-by-one eagerly — the "graceful fallback"
  for ops XLA cannot express.  Host ops at the head/tail of a block (feed /
  read / fetch) are peeled off and the middle still compiles.
"""
from __future__ import annotations

import sys
import time
import warnings

import jax
import numpy as np

from . import lowering
from . import sanitizer as _san
from .lod import LoDTensor
from .lowering import LoweringContext, run_ops, run_op
from .registry import get_op_info
from .place import placed_on
from .scope import Scope
from .types import proto_to_np_dtype, VarKind

from .flags import FLAGS, ensure_compile_cache

from paddle_tpu.observability import metrics as _obs_metrics
from paddle_tpu.observability import numerics as _num
from paddle_tpu.observability.trace import TRACER as _TRC

# always-on metrics (one short lock per step — see
# tools/telemetry_overhead.py for the hot-path overhead gate); span
# tracing below is additionally gated on _TRC.live() (FLAGS_telemetry,
# or a jax profiler session: the spans are then in its capture)
_M_STEPS = _obs_metrics.counter(
    "executor_steps_total", "executor steps (run + run_prepared)")
_M_CACHE_HITS = _obs_metrics.counter(
    "compile_cache_hits_total", "compiled-entry cache hits")
_M_CACHE_MISSES = _obs_metrics.counter(
    "compile_cache_misses_total", "compiled-entry cache misses (builds)")
_M_FLUSHES = _obs_metrics.counter(
    "prepared_flushes_total",
    "PreparedProgram.sync_scope write-backs of device state")
_H_STEP_MS = _obs_metrics.histogram(
    "step_wall_ms",
    "per-step wall of traced executor steps (FLAGS_telemetry on)")


def _matmul_precision_ctx():
    """jax.default_matmul_precision(FLAGS.matmul_precision) when set —
    must wrap jit CALLS (the config participates in jax's jit cache and
    applies at (re)lowering time)."""
    import contextlib

    p = FLAGS.matmul_precision
    if p:
        return jax.default_matmul_precision(str(p))
    return contextlib.nullcontext()

class EOFException(Exception):
    """A program-level reader has no next batch (parity: the enforce
    the reference's read op raises at end-of-data — callers catch it
    and reset the reader, reader/read_op.cc)."""


LEN_SUFFIX = "@LEN"
# pad ragged batches' time dim up to a multiple of this so the number of
# distinct compiled shapes stays bounded (bucketing)
LOD_PAD_MULTIPLE = 8
# level-2 feeds also bucket the outer (sentence-count) dim
LOD_SEQ_PAD_MULTIPLE = 4


def _prepare_lod_feeds(feed):
    """LoDTensor feeds -> padded dense array + '<name>@LEN' lengths.
    Level-2 LoD pads to [N, S, W, ...] with '@LEN' = outer sentence
    lengths and '@LEN@1' = [N, S] inner sub-sequence lengths; deeper
    LoD generalizes recursively — one padded dim and one '@LEN@j'
    array per level (reference lod_tensor.h:58 depth-unbounded LoD)."""
    # hot-path fast exit: dense-only feeds (the overwhelmingly common
    # case in a training loop) skip the per-item padding scan entirely
    for v in feed.values():
        if isinstance(v, LoDTensor) and v.lod:
            break
    else:
        return feed

    for name, v in list(feed.items()):
        if not (isinstance(v, LoDTensor) and v.lod):  # dense rides along
            continue
        if len(v.lod) > 2:
            # level-k (k>=3): general recursive pad — outer ragged dims
            # bucket to LOD_SEQ_PAD_MULTIPLE, the innermost time dim to
            # LOD_PAD_MULTIPLE; '@LEN@j' carries level-j lengths
            # (reference lod_tensor.h:58 depth-unbounded LoD)
            k = len(v.lod)
            # padded fan-out per level: max segment length, bucketed
            max_dims = []
            for j in range(k):
                mult = LOD_PAD_MULTIPLE if j == k - 1 \
                    else LOD_SEQ_PAD_MULTIPLE
                mx = max(v.sequence_lengths(j), default=1)
                max_dims.append(-(-max(mx, 1) // mult) * mult)
            padded, lens = v.to_padded_klevel(max_dims=max_dims)
            feed[name] = padded
            feed[name + LEN_SUFFIX] = lens[0].astype(np.int32)
            for j in range(1, k):
                feed[name + LEN_SUFFIX + "@%d" % j] = \
                    lens[j].astype(np.int32)
            continue
        if len(v.lod) == 2:
            # bucket both ragged dims so compiled shapes stay bounded.
            # This is the FEED bridge (pad + expose '@LEN' outer and
            # '@LEN@1' inner lengths); sequence_pool/softmax/conv
            # consume '@LEN@1' and operate at the FINEST level
            # (ops/sequence.py _fold_level2, reference
            # lod_tensor.h:58-110 semantics).
            s_max = max((v.lod[0][i + 1] - v.lod[0][i]
                         for i in range(len(v.lod[0]) - 1)), default=1)
            w_max = max((v.lod[1][j + 1] - v.lod[1][j]
                         for j in range(len(v.lod[1]) - 1)), default=1)
            s_max = -(-max(s_max, 1) // LOD_SEQ_PAD_MULTIPLE) * \
                LOD_SEQ_PAD_MULTIPLE
            w_max = -(-max(w_max, 1) // LOD_PAD_MULTIPLE) * \
                LOD_PAD_MULTIPLE
            padded, outer, inner = v.to_padded_2level(
                max_seq=s_max, max_word=w_max)
            feed[name] = padded
            feed[name + LEN_SUFFIX] = outer.astype(np.int32)
            feed[name + LEN_SUFFIX + "@1"] = inner.astype(np.int32)
            continue
        lens = v.sequence_lengths(0)
        t = max(lens) if lens else 1
        t = -(-max(t, 1) // LOD_PAD_MULTIPLE) * LOD_PAD_MULTIPLE
        padded, lengths = v.to_padded(max_len=t)
        feed[name] = padded
        feed[name + LEN_SUFFIX] = lengths.astype(np.int32)
    return feed


def _tuning_fingerprint():
    try:
        from paddle_tpu import tuning
        return tuning.fingerprint()
    except Exception:
        return ("", 0, 0)


def _cache_key(program, block_id, feed_spec, fetch_list, mode,
               numerics=None):
    """The ONE compiled-entry cache key — shared by run()'s per-feed
    path and prepare(), so a prepared program and run() with the same
    signature reuse a single executable.  Trace-time flag reads are part
    of the key: toggling them must not hit a stale executable.
    ``numerics`` pins the health-fetch variant explicitly (the prepared
    path caches BOTH twins of one signature); None reads the flag."""
    return (program.uid, program.version, block_id, feed_spec,
            tuple(fetch_list), mode,
            bool(getattr(program, "amp_bf16", False)),
            bool(FLAGS.auto_layout),
            # read at trace time (_amp_cast_ins / conv2d lowering)
            bool(FLAGS.bn_bf16), bool(FLAGS.conv_nhwc),
            str(FLAGS.matmul_precision),
            # scheduler-flag experiments must recompile, never reuse a
            # stale executable (ISSUE 5 lever c; see flags.py
            # apply_xla_flags for the process-lifetime caveat)
            bool(FLAGS.xla_latency_hiding_scheduler),
            str(FLAGS.xla_extra_flags),
            # autotune-cache state (ISSUE 7): lowerings consult the
            # cache at trace time, so a re-tuned cache (new file, new
            # dir, or an in-process record()) must recompile
            _tuning_fingerprint(),
            # numerics observatory (ISSUE 8): any mode but 'off' adds
            # the fused health reduction as an extra step output —
            # toggling it must never serve an executable without (or
            # with) the fetch.  The plain twin of a health entry keys
            # identically to the flag-off build, so toggling the
            # observatory never recompiles the common executable.
            _num.trace_enabled() if numerics is None else bool(numerics))


class _CacheEntry:
    __slots__ = ("fn", "input_names", "persist_outs", "fetch_names",
                 "input_shardings", "jit_fn", "watched", "monitor")

    def __init__(self, fn, input_names, persist_outs, fetch_names,
                 input_shardings=None, jit_fn=None, watched=()):
        self.fn = fn
        self.input_names = input_names
        self.persist_outs = persist_outs
        self.fetch_names = fetch_names
        self.input_shardings = input_shardings
        self.jit_fn = jit_fn  # the raw jax.jit object (AOT lower/compile)
        # numerics observatory (ISSUE 8): names whose health stats ride
        # the step as an extra output when FLAGS_check_numerics is on;
        # the monitor owns the read-back cadence + escalation
        self.watched = tuple(watched)
        self.monitor = _num.HealthMonitor(self.watched, "executor.run") \
            if self.watched else None


def flush_prepared(scope, exclude=None):
    """sync_scope() every dirty prepared program registered on ``scope``
    or any ancestor (parity role: reference RunPreparedContext keeps
    scope authoritative between prepared runs; here state lives on
    device and this is the on-demand write-back)."""
    s = scope
    while s is not None:
        if getattr(s, "_prepared_registry", None):
            s.flush_prepared(exclude)
        s = s._parent


def seen_entry(scope, name):
    """(owning scope, write version) snapshot of ``name`` — the shared
    primitive of the external-write-wins protocol (PreparedProgram and
    PipelineProgram): record it when you read or install a value,
    compare later to tell your own writes apart from someone else's."""
    s = scope.find_scope_of(name)
    return (s, s._write_versions.get(name) if s is not None else None)


def seen_changed(scope, name, seen):
    """True when ``name`` was written since ``seen`` was recorded (or
    was never recorded): the scope's value wins over device state."""
    if seen is None:
        return True
    cur = seen_entry(scope, name)
    return cur[0] is not seen[0] or cur[1] != seen[1]


class PreparedShapeMismatch(ValueError):
    """A feed's shape drifted from an AOT (auto-layout) prepared
    signature — the caller should run() this batch or re-prepare."""


class PreparedProgram:
    """Reference Executor::Prepare + RunPreparedContext
    (framework/executor.cc:127): the per-step cost is dispatch, not
    re-analysis.  Owns the compiled entry plus a device-resident state
    map of every non-feed input and written persistable; the state is
    threaded step-to-step so donated parameter/optimizer buffers never
    round-trip through the Scope.  ``run_prepared`` does feed staging +
    one dispatch and returns fetches as UN-CONVERTED device arrays;
    ``sync_scope`` flushes the written persistables back on demand
    (called automatically by every run()/io-save path via
    ``flush_prepared`` and on context exit).

    Interleaving contract: every read path on the same scope — run(),
    the io save programs, and plain ``Scope.find_var`` — flushes this
    state first (Scope.flush_prepared), so readers never observe a
    stale value or a donated (invalidated) buffer; and any scope write
    bumps the scope's version counter, which makes the next
    ``run_prepared`` re-stage its state from the scope.  Per-name write
    versions tell our own sync-backs apart from external writes: a name
    someone else wrote always wins over our device copy.
    """

    def __init__(self, core, program, block_id, entry, scope, mode,
                 feed_specs, entry_health=None):
        self._core = core
        self._program = program
        self._block_id = block_id
        self._entry = entry
        # health-instrumented twin (ISSUE 8): same signature + state
        # contract, plus the packed health output; dispatched instead
        # of the plain entry on numerics cadence steps
        self._entry_health = entry_health
        self._scope = scope
        self._mode = mode
        self._feed_names = frozenset(feed_specs)
        self._program_version = program.version
        # AOT entries (auto-layout) executed for FIXED argument shapes:
        # a shape drift (final partial batch) must fail with guidance,
        # not a deep XLA mismatch.  jit entries are shape-polymorphic
        # (retrace per new shape) so no per-step check is paid there.
        self._fixed_shapes = None
        if entry.jit_fn is None and hasattr(feed_specs, "items"):
            self._fixed_shapes = {
                name: tuple(v.shape)
                for name, v in feed_specs.items() if v is not None}
        block = program.blocks[block_id]
        dev = core.place.jax_device()
        self._targets = []      # per input index: sharding/Format/device
        self._feed_dtypes = {}  # feed name -> np dtype for coercion
        self._state_targets = {}
        for i, name in enumerate(entry.input_names):
            target = (entry.input_shardings[i]
                      if entry.input_shardings is not None else dev)
            if target is None:
                target = dev
            self._targets.append(target)
            if name in self._feed_names:
                vd = block.find_var_recursive(name)
                self._feed_dtypes[name] = (proto_to_np_dtype(vd.dtype)
                                           if vd is not None else None)
            else:
                self._state_targets[name] = target
        self._state = {}
        self._seen = {}  # name -> (owning scope, write version) we read
        self._read_only = [n for n in self._state_targets
                           if n not in set(entry.persist_outs)]
        # numerics observatory (ISSUE 8): own monitor = own read-back
        # cadence per prepared program (the entries may be shared)
        self._monitor = _num.HealthMonitor(entry_health.watched,
                                           "step.prepared") \
            if entry_health is not None and entry_health.watched else None
        # another prepared program/pipeline may hold newer values for
        # the persistables we are about to stage
        flush_prepared(scope)
        self._refresh_from_scope()
        self._dirty = False
        self._scope_epoch = scope.chain_version()
        # register on every scope that OWNS one of our resident names
        # (plus the lookup root): a reader rooted at an ancestor that
        # holds the persistables must hit the registry even though it
        # never walks down to the training scope
        owners = {id(scope): scope}
        for name in list(self._state_targets) + list(entry.persist_outs):
            s = scope.find_scope_of(name)
            if s is not None:
                owners.setdefault(id(s), s)
        for s in owners.values():
            s.attach_prepared(self)

    @property
    def fetch_names(self):
        return self._entry.fetch_names

    @property
    def is_stale(self):
        """True once the program mutated after prepare() (its version
        bumped): the compiled entry no longer matches — sync_scope and
        re-prepare.  run_prepared refuses stale entries loudly."""
        return self._program.version != self._program_version

    def _refresh_from_scope(self):
        """Re-stage resident inputs from the scope (after a run()/load
        wrote new values).  device_put is a no-op for arrays already
        committed to their target.  Values are read via the owning
        scope's raw storage — callers flushed other prepared programs
        already, and the per-name write versions recorded here let
        sync_scope detect external writes later."""
        scope = self._scope
        local = getattr(scope, "_reader_batch_vars", ())
        for name, target in self._state_targets.items():
            s = scope.find_scope_of(name)
            if s is None:
                raise KeyError(name)
            v = s._vars[name]
            if _san.is_husk(v):
                # sanitizer husk: re-raise with the donation's full
                # story (var, op, step, site) instead of the generic
                # consumed-buffer message below
                v._trip()
            if callable(getattr(v, "is_deleted", None)) and \
                    v.is_deleted():
                # the buffer was donated and consumed — by a failed
                # step, or by training that never synced back before
                # this program was dropped: the VALUE is gone
                raise RuntimeError(
                    "persistable %r in the scope is a donated buffer "
                    "whose value was consumed (a failed prepared step, "
                    "or a PreparedProgram dropped without sync_scope); "
                    "restore it (io.load_persistables / a checkpoint) "
                    "before continuing" % name)
            self._state[name] = _put(v, target, local_rows=name in local)
            self._seen[name] = (s, s._write_versions.get(name))
        # write-only persistables are rebuilt by the next step; drop
        # stale copies so sync_scope can't resurrect them, but KEEP a
        # write-version baseline so an external write to them between
        # now and the next sync is still detected (scope wins)
        for name in self._entry.persist_outs:
            if name not in self._state_targets:
                self._state.pop(name, None)
                self._seen[name] = seen_entry(scope, name)

    def run_prepared(self, feed=None):
        """Feed staging + one dispatch.  Returns the fetch list as
        device arrays — host conversion is the CALLER's choice (defer
        np.asarray until the value is actually consumed).

        Telemetry: one step counter per COMPLETED step; with
        FLAGS_telemetry on or under a jax profiler session, a
        'step.prepared' span with 'step.feed' / 'step.dispatch' phases
        and a step_wall_ms histogram observation.  A failed attempt
        records neither (the PreparedShapeMismatch fallback re-runs the
        step through run(), which does its own counting — inc-ing up
        front would count such a step twice).  Dead cost: the counter
        inc plus ONE liveness check, handed down so the phase sites
        test a local (the < 2% overhead gate in
        tools/telemetry_overhead.py)."""
        if not _TRC.live():
            out = self._run_prepared_impl(feed, None)
            _M_STEPS.inc()
            return out
        span = _TRC.begin("step.prepared")
        try:
            out = self._run_prepared_impl(feed, _TRC)
        except BaseException:
            # keep the trace evidence, but under a name the phase
            # table won't mix into real step stats
            span.name = "step.prepared.failed"
            raise
        finally:
            _TRC.end(span)
        _M_STEPS.inc()
        _H_STEP_MS.observe((span.t1 - span.t0) / 1e6)
        return out

    def _run_prepared_impl(self, feed, _tr):
        if self.is_stale:
            raise RuntimeError(
                "program mutated since prepare() (version %d -> %d): the "
                "compiled entry is stale — re-prepare" %
                (self._program_version, self._program.version))
        scope = self._scope
        # another prepared program (or pipeline) may hold newer values
        flush_prepared(scope, exclude=self)
        if scope.chain_version() != self._scope_epoch:
            # someone wrote the scope since our last sync.  Flush OUR
            # updates first: our written persistables in the scope are
            # older than the state (and may be donated husks) — syncing
            # makes the scope whole before we re-stage from it.
            if self._dirty:
                self.sync_scope()
            self._refresh_from_scope()
            self._scope_epoch = scope.chain_version()
        sp_feed = _tr.begin("step.feed") if _tr is not None else None
        feed = _prepare_lod_feeds(dict(feed or {}))
        if feed.keys() != self._feed_names:
            self._check_feed_names(feed)
        entry = self._entry
        state = self._state
        fixed = self._fixed_shapes
        args = []
        for i, name in enumerate(entry.input_names):
            # feed precedence for names both fed AND written by the
            # block, exactly like run(): the device copy of such a name
            # exists only for sync_scope, never shadows the feed
            if name in state and name not in self._feed_names:
                args.append(state[name])
                continue
            val = feed[name]
            if fixed is not None:
                exp = fixed.get(name)
                if exp is not None and tuple(np.shape(val)) != exp:
                    raise PreparedShapeMismatch(
                        "feed %r shape %s != prepared signature %s: "
                        "this entry was AOT-compiled for fixed shapes "
                        "(FLAGS.auto_layout) — re-prepare for the new "
                        "batch shape or use run()" %
                        (name, tuple(np.shape(val)), exp))
            dtype = self._feed_dtypes.get(name)
            if dtype is not None and not hasattr(val, "dtype"):
                val = np.asarray(val, dtype=dtype)
            args.append(_put(val, self._targets[i], local_rows=True))
        seed, counter = self._core._rng_counter(self._program, scope)
        if sp_feed is not None:
            _tr.end(sp_feed)
        # numerics (ISSUE 8): pick the health-instrumented twin on
        # cadence steps (bisect: every step), the plain executable
        # otherwise — both share the signature and state contract.
        # Bisect additionally snapshots the resident state BEFORE the
        # dispatch consumes the donated buffers: the forensic re-run of
        # a tripped step must start from the exact pre-step values (the
        # expensive debug tier; metrics/guard pay nothing here).
        snap = None
        use_health = self._monitor is not None and \
            self._monitor.want_health()
        if use_health:
            entry = self._entry_health
            if _num.effective_mode() == "bisect":
                snap = {name: _snapshot_value(v)
                        for name, v in self._state.items()}
        # buffer sanitizer (ISSUE 14): the dispatch donates the
        # device-resident persistables it overwrites.  On step 1 the
        # scope slots still alias these exact arrays; poisoning them
        # after the dispatch turns any host read that bypasses the
        # flush protocol into a named BufferLifetimeError instead of a
        # bare jax deleted-array error.  Later steps find the slots
        # already husked (or externally rewritten) and skip in O(1).
        donated_map = None
        if _san._BUFFERS_ON:
            # donated = resident INPUTS the block overwrites (the
            # _build donate_argnums set); a write-only persist_out is
            # rebuilt, not donated — poisoning it would husk the live
            # value sync_scope installed last flush
            donated_map = {n: state[n] for n in entry.persist_outs
                           if n in state and n in self._state_targets
                           and n not in self._feed_names}
            don_site = "prepared block %d of program %s" % (
                self._block_id, getattr(self._program, "uid", "?"))
        sp_disp = _tr.begin("step.dispatch") if _tr is not None else None
        try:
            out = entry.fn(tuple(args), seed, counter)
            if entry.watched:
                fetches, persists, health = out
            else:
                fetches, persists = out
            if sp_disp is not None:
                _tr.end(sp_disp)
        except Exception:
            if sp_disp is not None:
                _tr.end(sp_disp, args={"failed": True})
            if donated_map:
                # name the scope slots a failed EXECUTE consumed
                # (trace failures consume nothing: only_dead)
                _san.poison_donated(scope, donated_map,
                                    op="run_prepared",
                                    step=int(counter), site=don_site,
                                    only_dead=True)
            # an execute-time failure may have consumed the donated
            # inputs: drop exactly the deleted buffers so a finally/
            # context-exit sync installs only values that survived
            # (trace-time failures consume nothing and lose nothing)
            dead = False
            for name in list(state):
                v = state[name]
                if callable(getattr(v, "is_deleted", None)) \
                        and v.is_deleted():
                    del state[name]
                    self._seen.pop(name, None)
                    dead = True
            if dead:
                self._scope_epoch = None  # re-stage dropped names
            raise
        if donated_map:
            _san.poison_donated(scope, donated_map, op="run_prepared",
                                step=int(counter), site=don_site)
        for name, val in zip(entry.persist_outs, persists):
            state[name] = val
        self._dirty = True
        if self._monitor is not None:
            rerun = None
            if snap is not None:
                def rerun(_snap=snap, _feed=feed, _seed=seed,
                          _counter=counter):
                    self._restore_snapshot(_snap)
                    block = self._program.blocks[self._block_id]
                    return self._core._bisect_rerun(
                        self._program, self._block_id, list(block.ops),
                        self._scope, _feed, _seed, _counter, self._mode)
            self._monitor.observe(health if use_health else None,
                                  rerun=rerun,
                                  checked=True if use_health else None)
        return list(fetches)

    def _restore_snapshot(self, snap):
        """Rewind to the pre-step state (numerics bisect): the tripped
        step's device results are discarded, the scope gets the host
        snapshot back, and the next step (if any) re-stages from it."""
        scope = self._scope
        for name, arr in snap.items():
            (scope.find_scope_of(name) or scope).set(name, arr)
        self._state.clear()
        self._seen.clear()
        self._dirty = False
        self._scope_epoch = None

    def _check_feed_names(self, feed):
        missing = self._feed_names - feed.keys()
        if missing:
            raise KeyError(
                "prepared program expects feed(s) %s (prepared "
                "signature: %s)" % (sorted(missing),
                                    sorted(self._feed_names)))
        resident = feed.keys() & self._state_targets.keys()
        if resident:
            raise ValueError(
                "feed(s) %s are device-resident state of this prepared "
                "program; sync_scope() + run(), or re-prepare with them "
                "in feed_specs" % sorted(resident))
        # extra never-read feeds are ignored, like run()

    def sync_scope(self):
        """Flush written persistables back to the scope.  The scope then
        holds the CURRENT device arrays; a later step donates them
        again, which re-marks this program dirty so the next flush
        rewrites fresh buffers.  A name written EXTERNALLY since we last
        read/installed it (scope.set by user code, a load, another
        executor) wins: the device copy is dropped and re-staged from
        the scope instead of clobbering the newer value."""
        _M_FLUSHES.inc()
        if _TRC.live():
            with _TRC.span("step.sync_scope"):
                return self._sync_scope_impl()
        return self._sync_scope_impl()

    def _sync_scope_impl(self):
        scope = self._scope
        stale = False
        for name in self._entry.persist_outs:
            val = self._state.get(name)
            if val is None:
                continue
            if seen_changed(scope, name, self._seen.get(name)):
                # external write since our last read/install: scope wins
                self._state.pop(name, None)
                self._seen.pop(name, None)
                stale = True
                continue
            s = scope.find_scope_of(name) or scope
            s.set(name, val)
            self._seen[name] = (s, s._write_versions[name])
        # READ-ONLY resident state (e.g. a learning-rate var) can also
        # have been written externally; installing our persist_outs
        # fast-forwards the epoch past that write, so it must be
        # detected HERE or the next step would silently keep the stale
        # device copy
        if not stale:
            for name in self._read_only:
                if seen_changed(scope, name, self._seen.get(name)):
                    stale = True
                    break
        self._dirty = False
        # anything stale must be re-staged before the next step even if
        # nothing else touches the scope: poison the epoch
        self._scope_epoch = None if stale else scope.chain_version()

    # context manager: `with core.prepare(...) as prep:` syncs on exit
    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._dirty:
            self.sync_scope()
        return False


class ExecutorCore:
    """place: target device.  mesh: optional jax.sharding.Mesh — when set,
    the block is compiled as ONE SPMD program: feed (batch-dim) inputs are
    sharded over `dp_axis`, parameters replicated, and XLA's SPMD partitioner
    inserts the gradient all-reduces over ICI that the reference implemented
    as NCCL AllReduceOpHandles (details/multi_devices_graph_builder.cc:232)."""

    def __init__(self, place, mesh=None, dp_axis="dp"):
        self.place = place
        self.mesh = mesh
        self.dp_axis = dp_axis
        self._cache = {}
        ensure_compile_cache()

    # ------------------------------------------------------------------
    def _maybe_verify(self, program):
        """Ahead-of-time verification (paddle_tpu/analysis), paid ONLY
        when this program version has never been verified — the same
        cadence as a compile-cache miss, since the compiled-entry key
        includes program.version.  The verified marker lives on the
        program (not this executor) so nested executors (go routines,
        pserver serve loops) and run()/prepare() share one verification
        per mutation."""
        level = FLAGS.check_program
        if level == "off":
            return
        key = (program.version, level)
        if getattr(program, "_verified_key", None) == key:
            return
        from paddle_tpu import analysis
        try:
            analysis.verify_and_enforce(program, level=level,
                                        source="executor")
        except analysis.ProgramVerificationError:
            raise  # error mode: every run on the bad version re-raises
        except Exception as e:
            # a checker crash must never take down training: report it
            # and keep running (the program may still be fine)
            warnings.warn("program verification itself failed (%s: %s); "
                          "continuing unverified" % (type(e).__name__, e),
                          analysis.ProgramLintWarning)
        program._verified_key = key

    # ------------------------------------------------------------------
    def run(self, program, scope, block_id=0, feed=None, fetch_list=None,
            mode="train", return_numpy=True):
        # step metrics on COMPLETION only, mirroring run_prepared: a
        # raising run is not a step, and its aborted duration must not
        # land in the histogram.  Neither is a sub-block run — a
        # pserver's listen_and_serv applies each shard's optimize block
        # through here (ops/distributed_ops apply_block), and counting
        # those would report shard-apply time as the process's step
        # stats (10 shards x 100 rounds = 1000 phantom "steps").
        is_step = block_id == 0
        if not _TRC.live():
            out = self._run_impl(program, scope, block_id, feed,
                                 fetch_list, mode, return_numpy)
            if is_step:
                _M_STEPS.inc()
            return out
        span = _TRC.begin("executor.run", None, {"block": block_id})
        try:
            out = self._run_impl(program, scope, block_id, feed,
                                 fetch_list, mode, return_numpy)
        except BaseException:
            span.name = "executor.run.failed"
            raise
        finally:
            _TRC.end(span)
        if is_step:
            _M_STEPS.inc()
            # a blocking serve (listen_and_serv) is not a training
            # step either: one minutes-long observation would wreck
            # the step_wall_ms sum/mean/percentiles.  The executor.run
            # span still records it for the trace.
            if not _block_serves(program, block_id):
                _H_STEP_MS.observe((span.t1 - span.t0) / 1e6)
        return out

    def _run_impl(self, program, scope, block_id, feed, fetch_list,
                  mode, return_numpy):
        self._maybe_verify(program)
        # device-resident prepared state (run_prepared) must land in the
        # scope before this unprepared path reads or overwrites it
        flush_prepared(scope)
        feed = _prepare_lod_feeds(dict(feed or {}))
        fetch_list = list(fetch_list or [])
        block = program.blocks[block_id]
        # host ops with sub-block access (listen_and_serv) read this
        self._current_program = program

        t0 = time.perf_counter() if FLAGS.benchmark else None

        prelude, core_ops, postlude, mixed = _segment(block)
        if FLAGS.check_nan_inf:
            # legacy debug mode: run op-by-op eagerly so EVERY op's
            # outputs are validated and the first bad op is named
            # (reference FLAGS_check_nan_inf, operator.cc:590 — checks
            # even transients a downstream op would mask).  The ISSUE 8
            # observatory (FLAGS_check_numerics=bisect) keeps run()
            # compiled instead and re-runs only a TRIPPED step op-by-op;
            # the prepared path uses that machinery for this flag too.
            mixed = True
        if mixed:
            # the interpreted path executes EVERY op of the block itself
            # (host ops included) — running prelude/postlude here too
            # would execute them twice (e.g. double-send to a pserver)
            fetches = self._run_interpreted(program, block, scope, feed,
                                            fetch_list, mode)
        else:
            for op in prelude:
                _run_host_op(self, op, scope, feed)
            # postlude host ops may read non-persistable temps the block
            # computed (e.g. print of an activation): fetch those too and
            # hand them over via env instead of polluting the scope.
            # Conversely, fetches PRODUCED by postlude host ops (e.g. a
            # chunk_eval metric) come out of that env afterwards.
            post_writes = {n for op in postlude
                           for n in op.output_arg_names() if n}
            core_fetch = [n for n in fetch_list if n not in post_writes]
            post_in = [n for op in postlude for n in op.input_arg_names()
                       if n]
            # '@LEN' companions ride along so host ops (chunk_eval &c.)
            # see real sequence lengths, not the padded T
            post_in += [n + LEN_SUFFIX for n in list(post_in)]
            post_reads = sorted({
                n for n in post_in
                if n not in feed and not scope.has_var(n)
                and n not in post_writes})
            if core_ops or core_fetch or post_reads:
                outs = self._run_compiled(program, block_id, core_ops,
                                          scope, feed,
                                          core_fetch + post_reads, mode)
            else:
                outs = []  # all-host program (save/load/...): nothing to
                #            compile — don't jit an empty computation
            by_name = dict(zip(core_fetch, outs[:len(core_fetch)]))
            post_env = dict(zip(post_reads, outs[len(core_fetch):]))
            for op in postlude:
                _run_host_op(self, op, scope, feed,
                             post_env if (post_reads or post_writes)
                             else None)
            fetches = [by_name[n] if n in by_name else post_env.get(n)
                       for n in fetch_list]

        if t0 is not None:
            # reference FLAGS_benchmark (executor.cc): per-run wall time
            print("[benchmark] block %d ran in %.3f ms" %
                  (block_id, (time.perf_counter() - t0) * 1e3),
                  file=sys.stderr)

        if return_numpy:
            fetches = fetches_to_host(fetches)
        return fetches

    # ------------------------------------------------------------------
    def prepare(self, program, feed_specs, fetch_list, mode="train",
                scope=None, block_id=0):
        """Reference Executor::Prepare (executor.cc:127): pay program
        analysis once, get a PreparedProgram whose per-step cost is feed
        staging + one dispatch (RunPreparedContext).

        ``feed_specs`` is either a sample feed dict ({name: array-like /
        LoDTensor}, e.g. the first minibatch — its shapes/dtypes let the
        compiled entry share the run() cache) or a bare iterable of feed
        names.  Raises ValueError for blocks the compiled path cannot
        own whole (host ops) — callers fall back to run()."""
        if scope is None:
            raise ValueError(
                "prepare() requires the scope holding the program's "
                "persistables (run the startup program into it first)")
        self._maybe_verify(program)
        if feed_specs is None:  # zero-feed program (scope-resident data)
            feed_specs = {}
        fetch_list = list(fetch_list or [])
        block = program.blocks[block_id]
        prelude, core_ops, postlude, mixed = _segment(block)
        if mixed or prelude or postlude:
            host = sorted({op.type for op in block.ops
                           if get_op_info(op.type).host_op})
            raise ValueError(
                "block %d has host op(s) %s; the prepared hot path "
                "compiles the whole block — use run()" % (block_id, host))
        # FLAGS.check_nan_inf no longer refuses the prepared path
        # (ISSUE 8): the legacy flag maps onto the numerics guard+bisect
        # machinery — the step stays one dispatch with the fused health
        # fetch, and a trip re-runs THAT step op-by-op to name the first
        # bad op, preserving the reference semantics on both paths
        # (MIGRATION.md "check_nan_inf on the prepared path").
        if hasattr(feed_specs, "keys"):
            sample = _prepare_lod_feeds(dict(feed_specs))
            # the SAME cache key _run_compiled builds from a real feed,
            # so prepare() and run() share one compiled executable
            key_spec = tuple(sorted(
                (name, tuple(np.shape(v)),
                 str(v.dtype) if hasattr(v, "dtype") else
                 str(np.asarray(v).dtype))
                for name, v in sample.items()))
            stub = {
                name: jax.ShapeDtypeStruct(
                    np.shape(v), v.dtype if hasattr(v, "dtype")
                    else np.asarray(v).dtype)
                for name, v in sample.items()}
        else:
            # names-only signature: membership is enough to build; the
            # entry cannot alias run()'s per-shape keys, but repeated
            # prepare() calls (re-prepare after staleness, sibling
            # PreparedPrograms) must not re-trace
            stub = {name: None for name in feed_specs}
            key_spec = ("names-only",) + tuple(sorted(stub))
        key = _cache_key(program, block_id, key_spec, fetch_list, mode)
        entry = self._cache.get(key)
        if entry is None:
            _M_CACHE_MISSES.inc()
            entry = self._build(program, block_id, core_ops, scope,
                                stub, fetch_list, mode)
            self._cache[key] = entry
        else:
            _M_CACHE_HITS.inc()
        # Numerics observatory (ISSUE 8): with a mode on, the entry
        # above carries the health output — also compile the PLAIN twin
        # (keyed exactly like the flag-off build, so it is usually a
        # cache hit) and let run_prepared dispatch the health twin only
        # on cadence steps: the stats pass costs one memory pass over
        # the watched bytes, and amortizing it by 1/every is what keeps
        # metrics mode under tools/telemetry_overhead.py's 2% gate.
        entry_health = None
        if entry.watched:
            entry_health = entry
            key_plain = _cache_key(program, block_id, key_spec,
                                   fetch_list, mode, numerics=False)
            entry = self._cache.get(key_plain)
            if entry is None:
                _M_CACHE_MISSES.inc()
                entry = self._build(program, block_id, core_ops, scope,
                                    stub, fetch_list, mode,
                                    with_health=False)
                self._cache[key_plain] = entry
            else:
                _M_CACHE_HITS.inc()
        return PreparedProgram(self, program, block_id, entry, scope,
                               mode, stub, entry_health=entry_health)

    # ------------------------------------------------------------------
    def _rng_key(self, program, scope):
        seed, counter = self._rng_counter(program, scope)
        return jax.random.fold_in(jax.random.PRNGKey(seed), counter)

    def _rng_counter(self, program, scope):
        """Step counter fed to the compiled fn; the PRNGKey derivation
        happens inside the jitted computation so no eager dispatches are
        paid per step."""
        counter = getattr(scope, "_rng_counter", 0)
        scope._rng_counter = counter + 1
        seed = getattr(program, "random_seed", 0) or 0
        return np.uint32(seed), np.uint32(counter)

    def _run_compiled(self, program, block_id, core_ops, scope, feed,
                      fetch_list, mode):
        block = program.blocks[block_id]
        # NB: use .dtype when present — np.asarray on a jax.Array would be
        # a blocking device-to-host copy in the hot path.
        feed_spec = tuple(sorted(
            (name, tuple(np.shape(v)),
             str(v.dtype) if hasattr(v, "dtype") else
             str(np.asarray(v).dtype))
            for name, v in feed.items()))
        key = _cache_key(program, block_id, feed_spec, fetch_list, mode)
        entry = self._cache.get(key)
        if entry is None:
            _M_CACHE_MISSES.inc()
            entry = self._build(program, block_id, core_ops, scope, feed,
                                fetch_list, mode)
            self._cache[key] = entry
        else:
            _M_CACHE_HITS.inc()

        dev = self.place.jax_device()
        args = []
        for i, name in enumerate(entry.input_names):
            target = (entry.input_shardings[i]
                      if entry.input_shardings is not None else dev)
            if target is None:  # auto-layout path: feeds use the device
                target = dev
            if name in feed:
                val = feed[name]
                vd = block.find_var_recursive(name)
                if vd is not None and not hasattr(val, "dtype"):
                    val = np.asarray(val, dtype=proto_to_np_dtype(vd.dtype))
                args.append(_put(val, target, local_rows=True))
            else:
                # Always commit to the target device: mixing committed and
                # uncommitted arrays across steps would miss jit's C++ cache
                # and recompile (device_put is a no-op when already there).
                # reader-op batches in the scope are per-process LOCAL
                # rows, not global values (see reader_ops._read)
                args.append(_put(
                    scope.find_var(name), target,
                    local_rows=name in getattr(scope,
                                               "_reader_batch_vars", ())))
        seed, counter = self._rng_counter(program, scope)

        # numerics bisect (ISSUE 8): host snapshot of the scope-read
        # inputs BEFORE the dispatch consumes the donated persistable
        # buffers — from step 2 on, the scope's persistables ARE the
        # arrays donated to this dispatch, so the forensic re-run of a
        # tripped step must start from copies taken now (mirrors the
        # prepared path's per-step snapshot; the expensive debug tier)
        snap = None
        if entry.watched and _num.effective_mode() == "bisect":
            snap = {name: _snapshot_value(args[i])
                    for i, name in enumerate(entry.input_names)
                    if name not in feed}
        # buffer sanitizer (ISSUE 14): the dispatch donates the scope-
        # resident persistables it overwrites — the consumed map names
        # var -> the exact argument handed over, so poisoning swaps
        # only slots that still alias the dying buffer
        donated_map = None
        if _san._BUFFERS_ON:
            persist_set = set(entry.persist_outs)
            donated_map = {
                n: args[i] for i, n in enumerate(entry.input_names)
                if n in persist_set and n not in feed}
            don_site = "block %d of program %s" % (
                block_id, getattr(program, "uid", "?"))
        try:
            if _TRC.live():
                sp = _TRC.begin("executor.dispatch")
                try:
                    out = entry.fn(tuple(args), seed, counter)
                finally:
                    _TRC.end(sp)
            else:
                out = entry.fn(tuple(args), seed, counter)
        except Exception:
            # a failed EXECUTE consumed the donated inputs; a failed
            # trace consumed nothing — only_dead tells them apart, so
            # a trace failure never husks a live value
            if donated_map:
                _san.poison_donated(scope, donated_map,
                                    op="executor.run",
                                    step=int(counter), site=don_site,
                                    only_dead=True)
            raise
        if donated_map:
            _san.poison_donated(scope, donated_map, op="executor.run",
                                step=int(counter), site=don_site)
        if entry.watched:
            fetches, persists, health = out
        else:
            fetches, persists = out
        # write-back BEFORE the health check: on a guard trip the scope
        # then holds the post-step (poisoned but LIVE) values, never
        # donated husks — post-mortem reads and skip-batch continuation
        # keep working; bisect restores its pre-step snapshot instead.
        # The scope.set here is also the sanitizer's RE-BIND: it
        # replaces the poisoned husks with the fresh buffers.
        for name, val in zip(entry.persist_outs, persists):
            (scope.find_scope_of(name) or scope).set(name, val)
        if entry.watched:
            def _rerun(_snap=snap):
                if _snap is not None:
                    for name, v in _snap.items():
                        (scope.find_scope_of(name) or scope).set(name, v)
                return self._bisect_rerun(program, block_id, core_ops,
                                          scope, feed, seed, counter,
                                          mode)
            entry.monitor.observe(health, rerun=_rerun)
        return list(fetches)

    def _build(self, program, block_id, core_ops, scope, feed, fetch_list,
               mode, with_health=None):
        block = program.blocks[block_id]
        written = set()
        external = []  # ordered reads satisfied by feed or scope
        seen_ext = set()
        for op in core_ops:
            for name in op.input_arg_names():
                if (name and name not in written and name not in seen_ext):
                    seen_ext.add(name)
                    external.append(name)
            for name in op.output_arg_names():
                if name:
                    written.add(name)
        # fetching an un-written var (e.g. a parameter) reads it too.
        # '@LEN' fetches are env-internal sequence lengths produced by the
        # trace itself (or absent -> fetched as None), never external.
        for name in fetch_list:
            if (name and name not in written and name not in seen_ext
                    and not (name.endswith(LEN_SUFFIX)
                             and not scope.has_var(name)
                             and name not in feed)):
                seen_ext.add(name)
                external.append(name)
        # ragged feeds travel as (padded, lengths) pairs: pull in the
        # device-side length vector of every LoD input (SURVEY §5.7 —
        # ragged->dense bucketing bridge to XLA static shapes)
        for name in list(external):
            suffixes = [LEN_SUFFIX]
            j = 1
            while name + LEN_SUFFIX + "@%d" % j in feed:
                suffixes.append(LEN_SUFFIX + "@%d" % j)
                j += 1
            for suffix in suffixes:
                if name + suffix in feed and name + suffix not in seen_ext:
                    seen_ext.add(name + suffix)
                    external.append(name + suffix)

        input_names = []
        for name in external:
            if name in feed or scope.has_var(name):
                input_names.append(name)
            else:
                raise RuntimeError(
                    "variable %r is read by block %d but is neither fed nor "
                    "initialized in the scope (run the startup program first)"
                    % (name, block_id))

        persist_outs = []
        for name in written:
            vd = block.find_var_recursive(name)
            if vd is not None and vd.persistable:
                persist_outs.append(name)
        persist_outs.sort()

        ops = list(core_ops)

        # numerics observatory (ISSUE 8): the watch list is fixed BEFORE
        # tracing so the packed health rows align with entry.watched;
        # the reduction is part of the jitted step (one dispatch).
        # Sub-block runs (block_id != 0) are NOT watched — a pserver's
        # listen_and_serv applies each shard's optimize block through
        # here, and a guard trip raising mid-apply (lock released
        # around the block) would wedge the serve loop with every
        # trainer stuck in retry; poisoned inbound grads are the wire
        # health check's job (numerics.server_check_grad names the
        # (round, sender) cid), and the trainer's own guard trips on
        # the poisoned params it fetches back.  Mirrors the
        # executor_steps_total sub-block exclusion.
        watched = ()
        if block_id == 0 and (_num.trace_enabled() if with_health is None
                              else with_health):
            watched = _num.select_watched(program, block, ops,
                                          persist_outs, fetch_list)

        # the device kernels dispatch for (kernels/dispatch.py): the
        # mesh's devices under SPMD, else the place's
        target = (self.mesh.devices.flat[0] if self.mesh is not None
                  else self.place.jax_device())

        def fn(inputs, seed, counter):
            env = dict(zip(input_names, inputs))
            rng = jax.random.fold_in(jax.random.PRNGKey(seed), counter)
            ctx = LoweringContext(program, block_id, env, rng, mode)
            ctx.block = block
            ctx.mesh = self.mesh
            # the block's ops carry its name scope in their HLO metadata
            # (op_name "jit(fn_flat)/block0/..."); the executable keeps
            # its name, which the benchmark's readers match
            with placed_on(target), jax.named_scope("block%d" % block_id):
                for op in ops:
                    run_op(ctx, op)
            fetches = tuple(env.get(n) for n in fetch_list)
            persists = tuple(env[n] for n in persist_outs)
            if watched:
                return fetches, persists, _num.pack_health(env, watched)
            return fetches, persists

        # Donate persistable inputs that the block overwrites: XLA reuses
        # the parameter buffers across steps (in-place optimizer update).
        donate = tuple(
            i for i, n in enumerate(input_names)
            if n in persist_outs and not _in_feed_only(n, feed, scope))

        def fn_flat(*flat_args):
            return fn(tuple(flat_args[:-2]), flat_args[-2], flat_args[-1])

        jit_kwargs = {"donate_argnums": donate}
        input_shardings = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            repl = NamedSharding(self.mesh, P())
            annotated = getattr(program, "var_shardings", {})

            axis_names = set(self.mesh.axis_names)

            reader_vars = getattr(scope, "_reader_batch_vars", ())

            def shard_of(name):
                if name in annotated:
                    spec = tuple(a if a in axis_names else None
                                 for a in annotated[name])
                    return NamedSharding(self.mesh, P(*spec))
                vd = block.find_var_recursive(name)
                # batch-dim data shards over dp whether it arrives as a
                # feed or from a program-level reader chain (the read
                # host op tags its outputs in the scope)
                if ((name in feed or name in reader_vars)
                        and vd is not None and len(vd.shape) >= 1
                        and vd.shape[0] == -1 and self.dp_axis in axis_names):
                    return NamedSharding(self.mesh, P(
                        self.dp_axis, *([None] * (len(vd.shape) - 1))))
                return repl

            input_shardings = [shard_of(n) for n in input_names]
            jit_kwargs["in_shardings"] = tuple(input_shardings) + (repl, repl)
            # Fetches come back replicated (they are consumed on host);
            # written persistables keep their annotated placement so e.g.
            # tensor-parallel weights never gather.  The health array is
            # tiny and host-consumed: replicated.
            out_sh = (tuple(repl for _ in fetch_list),
                      tuple(shard_of(n) for n in persist_outs))
            if watched:
                out_sh = out_sh + (repl,)
            jit_kwargs["out_shardings"] = out_sh
        # Scheduler-flag knobs (FLAGS_xla_*): best-effort late application
        # — a no-op once a backend exists; bench.py applies them before
        # backend init, which is the supported path (MIGRATION.md).
        from .flags import apply_xla_flags
        apply_xla_flags()
        # Pin trace/compile/execute to the place's device: with zero inputs
        # (every startup program) nothing else commits the computation, and
        # jit would otherwise compile for the process-default backend — e.g.
        # a CPUPlace startup run landing on the host's TPU.
        pin = None if self.mesh is not None else self.place.jax_device()

        if (pin is not None and pin.platform == "tpu" and FLAGS.auto_layout
                and input_names):
            return self._build_auto_layout(
                fn_flat, jit_kwargs, input_names, persist_outs, fetch_list,
                block, feed, scope, pin, watched)

        jflat = jax.jit(fn_flat, **jit_kwargs)

        def jfn(inputs, seed, counter):
            with _matmul_precision_ctx():
                if pin is None:
                    return jflat(*inputs, seed, counter)
                with jax.default_device(pin):
                    return jflat(*inputs, seed, counter)

        return _CacheEntry(jfn, input_names, persist_outs, tuple(fetch_list),
                           input_shardings, jit_fn=jflat, watched=watched)

    def _build_auto_layout(self, fn_flat, jit_kwargs, input_names,
                           persist_outs, fetch_list, block, feed, scope,
                           dev, watched=()):
        """Single-chip experiment path: AOT-compile with AUTO argument
        layouts.  AUTO lets XLA's layout assignment pick the parameter
        layouts; donation then aliases input and output buffers in that
        SAME layout, so weights stay in whatever form the compiler
        prefers across steps with no boundary relayouts.  Measured
        NEUTRAL on ResNet-50 and the transformer LM (the profile's
        relayout copies turned out to be internal to conv scheduling,
        not argument-boundary conversions — XLA's default argument
        layouts already matched), hence FLAGS.auto_layout defaults off;
        kept for models whose parameters do want non-default layouts.
        device_put into the chosen Format is a one-time cost (a no-op
        once the scope holds the formatted buffer)."""
        from jax.experimental.layout import Format, Layout

        fmt = Format(Layout.AUTO)
        specs = []
        for name in input_names:
            val = feed.get(name)
            if val is None:
                val = scope.find_var(name)
            if not hasattr(val, "dtype"):
                vd = block.find_var_recursive(name)
                val = np.asarray(val, dtype=proto_to_np_dtype(vd.dtype)
                                 if vd is not None else None)
            specs.append(jax.ShapeDtypeStruct(np.shape(val), val.dtype))
        specs += [jax.ShapeDtypeStruct((), np.uint32)] * 2
        kw = dict(jit_kwargs)
        # feeds keep default layouts (host arrays stream in each step);
        # persistables get AUTO
        feed_only = {n for n in input_names
                     if _in_feed_only(n, feed, scope)}
        kw["in_shardings"] = tuple(
            (None if n in feed_only else fmt) for n in input_names
        ) + (None, None)
        # fetches need AUTO too: donated AUTO inputs with a
        # default-layout output subtree is rejected by jax ("Input
        # layout being donated was AUTO while output layout was
        # None"); host reads convert on transfer regardless
        kw["out_shardings"] = ((fmt, fmt, fmt) if watched
                               else (fmt, fmt))  # (+ health)
        with _matmul_precision_ctx(), jax.default_device(dev):
            compiled = jax.jit(fn_flat, **kw).lower(*specs).compile()
        in_fmts = compiled.input_formats[0]
        input_shardings = [
            (None if n in feed_only else in_fmts[i])
            for i, n in enumerate(input_names)]

        def jfn(inputs, seed, counter):
            with jax.default_device(dev):
                return compiled(*inputs, seed, counter)

        return _CacheEntry(jfn, input_names, persist_outs,
                           tuple(fetch_list), input_shardings,
                           watched=watched)

    def _run_interpreted(self, program, block, scope, feed, fetch_list, mode):
        dev = self.place.jax_device()
        env = _ScopeEnv(scope, dev)
        for name, val in feed.items():
            vd = block.find_var_recursive(name)
            dtype = (proto_to_np_dtype(vd.dtype) if vd is not None else None)
            env[name] = jax.device_put(
                np.asarray(val, dtype=dtype) if dtype else np.asarray(val),
                dev)
        ctx = LoweringContext(program, block.idx, env,
                              self._rng_key(program, scope), mode)
        check_ops = FLAGS.check_nan_inf or \
            _num.effective_mode() == "bisect"
        with jax.default_device(dev):
            for oi, op in enumerate(block.ops):
                info = get_op_info(op.type)
                if info.host_op:
                    _run_host_op(self, op, scope, feed, env)
                else:
                    run_op(ctx, op)
                    if check_ops:
                        _num.check_op_outputs(op, env, block.idx, oi)
        # sync written persistables back
        for name in env.written:
            vd = block.find_var_recursive(name)
            if vd is not None and vd.persistable:
                s = scope.find_scope_of(name) or scope
                s.set(name, env[name])
        return [env.get(n) for n in fetch_list]

    def _bisect_rerun(self, program, block_id, ops, scope, feed, seed,
                      counter, mode):
        """Forensic re-run of ONE already-dispatched step, op by op,
        with per-op output checks (numerics bisect): expected to raise
        NumericsError naming the FIRST offending op, its input stats
        and program location.  The caller guarantees the scope holds
        the step's PRE-dispatch state (both run() and the prepared
        path restore their per-step host snapshot before calling),
        and ``(seed, counter)`` replay the dispatched step's exact RNG
        stream, so stateful ops (dropout) reproduce bit-for-bit.  Host
        ops are skipped — prelude/postlude already ran — and nothing is
        written back: this is evidence collection, not execution."""
        block = program.blocks[block_id]
        dev = self.place.jax_device()
        env = _ScopeEnv(scope, dev)
        for name, val in feed.items():
            vd = block.find_var_recursive(name)
            dtype = (proto_to_np_dtype(vd.dtype) if vd is not None
                     else None)
            env[name] = jax.device_put(
                np.asarray(val, dtype=dtype) if dtype
                else np.asarray(val), dev)
        rng = jax.random.fold_in(jax.random.PRNGKey(seed), counter)
        ctx = LoweringContext(program, block.idx, env, rng, mode)
        ctx.mesh = self.mesh
        with jax.default_device(dev):
            for oi, op in enumerate(ops):
                if get_op_info(op.type).host_op:
                    continue
                run_op(ctx, op)
                _num.check_op_outputs(op, env, block.idx, oi)
        return None  # did not reproduce — the monitor reports that


class _ScopeEnv(dict):
    """dict-like env that falls back to Scope lookups (interpreted path)."""

    def __init__(self, scope, device):
        super().__init__()
        self.scope = scope
        self.device = device
        self.written = set()

    def __contains__(self, name):
        return super().__contains__(name) or self.scope.has_var(name)

    def __missing__(self, name):
        val = self.scope.find_var(name)  # KeyError if absent
        super().__setitem__(name, val)
        return val

    def __setitem__(self, name, val):
        self.written.add(name)
        super().__setitem__(name, val)

    def get(self, name, default=None):
        try:
            return self[name]
        except KeyError:
            return default


def _snapshot_value(v):
    """Host copy of one resident value that survives buffer donation
    (numerics bisect pre-step snapshots).  jax.Arrays copy to host;
    SelectedRows copies its parts — keeping the object by reference
    would hand the restore a consumed values buffer."""
    if hasattr(v, "rows") and hasattr(v, "values"):
        from .selected_rows import SelectedRows
        return SelectedRows(np.array(np.asarray(v.rows), copy=True),
                            np.array(np.asarray(v.values), copy=True),
                            v.height)
    return np.asarray(v)


def _in_feed_only(name, feed, scope):
    return name in feed and not scope.has_var(name)


def fetches_to_host(outs):
    """Fetch-list values -> host numpy (None and list/tuple fetches —
    absent vars, LoD pairs — pass through untouched)."""
    return [_to_host_numpy(v) if v is not None and
            not isinstance(v, (list, tuple)) else v for v in outs]


def _to_host_numpy(v):
    """np.asarray that also handles multi-host global arrays: fetches
    are replicated (out_shardings in _build), so this process's first
    addressable shard IS the value."""
    if isinstance(v, jax.Array) and not v.is_fully_addressable:
        return np.asarray(v.addressable_data(0))
    return np.asarray(v)


def _put(val, target, local_rows=False):
    """device_put that tolerates Format targets and multi-host shardings.

    Multi-host (jax.distributed) shardings span devices this process
    cannot address; host values carry one of two semantics:

    - ``local_rows=True`` (feeds): the value is this process's LOCAL
      batch shard (the reference nccl2 contract: every trainer feeds
      its own batch, parallel_executor.cc:84-95) — assembled with
      ``make_array_from_process_local_data``.
    - ``local_rows=False`` (scope values): the value is the FULL global
      array, identical in every process (deterministic startup); each
      process materializes its addressable shards from it via
      ``make_array_from_callback`` — which is also what makes SHARDED
      (tensor-parallel) parameters work across hosts, where treating
      the full value as a local shard would double the global shape.

    Already-global jax.Arrays (last step's persistables) pass through
    untouched.

    Format targets: the TPU runtime here rejects device_put of a
    jax.Array onto a Format EVEN when the array already has exactly that
    layout (the relayout-by-jit path fails on the backend), so the
    already-formatted steady-state case must be a true no-op, and a
    genuine relayout goes through the host."""
    from jax.sharding import Sharding
    if isinstance(target, Sharding) and not target.is_fully_addressable:
        if isinstance(val, jax.Array):
            if val.sharding == target:
                return val
            if not val.is_fully_addressable:  # global -> global reshard
                return jax.device_put(val, target)
            val = np.asarray(val)  # local array -> rebuild globally
        elif not isinstance(val, np.ndarray):
            val = np.asarray(val)  # scope value / list / scalar
        if local_rows:
            return jax.make_array_from_process_local_data(target, val)
        full = val
        return jax.make_array_from_callback(
            full.shape, target, lambda idx: full[idx])
    fmt_layout = getattr(target, "layout", None)
    if fmt_layout is not None and isinstance(val, jax.Array):
        try:
            if val.format == target:
                return val
        except Exception:
            pass
        try:
            return jax.device_put(val, target)
        except Exception:
            return jax.device_put(np.asarray(val), target)
    return jax.device_put(val, target)


def _block_serves(program, block_id):
    """True when the block contains a blocking serve op
    (listen_and_serv) — cached per (block, version) on the program, so
    the per-step cost after the first call is one dict lookup."""
    cache = getattr(program, "_serve_blocks", None)
    if cache is None:
        cache = program._serve_blocks = {}
    key = (block_id, program.version)
    v = cache.get(key)
    if v is None:
        v = cache[key] = any(op.type == "listen_and_serv"
                             for op in program.blocks[block_id].ops)
    return v


def _segment(block):
    """Split ops into host prelude / device core / host postlude.

    Returns (prelude, core, postlude, mixed): ``mixed`` is True when host ops
    are interleaved with device ops and the block must be interpreted.
    """
    ops = block.ops
    is_host = [get_op_info(op.type).host_op for op in ops]
    i = 0
    while i < len(ops) and is_host[i]:
        i += 1
    j = len(ops)
    while j > i and is_host[j - 1]:
        j -= 1
    mixed = any(is_host[i:j])
    return ops[:i], ops[i:j], ops[j:], mixed


def _run_host_op(executor, op, scope, feed, env=None):
    info = get_op_info(op.type)
    impl = getattr(info, "_host_impl", None) or getattr(info.lower,
                                                        "host_impl", None)
    if impl is None:
        impl = info.lower
    impl(executor, op, scope, feed, env)
