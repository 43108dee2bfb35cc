"""Step-scoped span tracing: the host half of the telemetry layer.

Role parity: reference platform/profiler.h RAII host events +
tools/timeline.py chrome-trace export, rebuilt as one process-wide
tracer the executor, RPC, fastwire and kernel layers emit into (the
reference scattered RecordEvent through operator.cc and the gRPC
client; here the instrumented sites are named in ISSUE 6).

Design constraints:

- **A site is live under the flag or under a profiler session.**
  ``TRACER.live()`` is ``TRACER.on`` (``FLAGS_telemetry``) or a running
  jax profiler session (``TraceAnnotation.is_enabled()``).  Under a
  session ``begin``/``end`` also enter/exit a
  ``jax.profiler.TraceAnnotation`` carrying the span's cid and args, so
  the span is an event of the capture's ``/host:`` plane, on the
  profiler's own host clock, beside the runtime's events and the
  device ops (whose plane the profiler converts from the device's
  clock to within a millisecond or two: ``export.clock_skew``).
- **Dead cost is one check per step.**  Hot paths ask ``live()`` once
  (per ``run_prepared``, per ``DecodeLoop`` iteration), hand the answer
  down and test a local at every inner site, so with neither the flag
  nor a session a step builds no span and reads no clock for one —
  tools/telemetry_overhead.py gates this at < 2% of the prepared hot
  path.
- **Completed spans land in a bounded ring** (``collections.deque`` with
  maxlen — append is GIL-atomic, so the record path takes no lock),
  sized by ``FLAGS_telemetry_ring_size``.  The same ring is the flight
  recorder's history (observability/flight.py).
- **Open spans are visible.**  Per-thread stacks register in a process
  map so a hang dump can name the span every thread is blocked in —
  the who-was-waiting-on-whom report a bare rc:124 never gives.
- **Cross-process correlation.**  Distributed spans carry a correlation
  id built from the wire's (round, sender, seq) identity
  (``round_cid``); a merged trace (observability/export.py) lines
  trainer and pserver timelines up by it.
- **Mergeable clocks.**  Timestamps are monotonic perf_counter_ns with
  a wall-clock anchor captured at tracer init; dumps convert to
  absolute microseconds, so traces from different processes share one
  timeline (chrome://tracing renders them side by side).
"""
from __future__ import annotations

import atexit
import functools
import json
import os
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation as _Annotation

from paddle_tpu.core.flags import FLAGS

__all__ = ["TRACER", "Tracer", "Span", "round_cid", "traced",
           "disabled_step_probe"]


def round_cid(round_):
    """Correlation id for one sync round: every span of that round —
    trainer send/barrier/get AND pserver scatter/apply — carries the
    same id, so a merged trace correlates them across processes.  The
    finer (sender, seq) identity rides the span's args."""
    return "round:%d" % int(round_)


class Span:
    """One host event.  ``t1 == 0`` means still open (the flight
    recorder reports such spans as where a thread is blocked)."""

    __slots__ = ("name", "t0", "t1", "tid", "cid", "args", "depth",
                 "ann")

    def __init__(self, name, t0, tid, cid, args, depth):
        self.name = name
        self.t0 = t0
        self.t1 = 0
        self.tid = tid
        self.cid = cid
        self.args = args
        self.depth = depth
        self.ann = None     # the profiler's annotation, under a session


class _NoopCtx:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _NoopCtx()


class _SpanCtx:
    __slots__ = ("_tr", "_span")

    def __init__(self, tr, span):
        self._tr = tr
        self._span = span

    def __enter__(self):
        return self._span

    def __exit__(self, *exc):
        self._tr.end(self._span)
        return False


class Tracer:
    """Thread-safe span recorder.  One process-wide instance (TRACER);
    private instances exist only for tests."""

    def __init__(self, ring_size=None, enabled=None):
        self.on = bool(FLAGS.telemetry) if enabled is None else enabled
        self.label = None
        self._ring = deque(maxlen=int(ring_size
                                      or FLAGS.telemetry_ring_size))
        self._stacks = {}   # tid -> list of open spans (own-thread only)
        # wall anchor: dumps convert monotonic stamps to absolute µs so
        # per-process traces merge onto one timeline
        self._anchor_wall_ns = time.time_ns()
        self._anchor_perf_ns = time.perf_counter_ns()

    # -- lifecycle ----------------------------------------------------
    def enable(self):
        self.on = True

    def disable(self):
        self.on = False

    def live(self):
        """Would a span opened now be kept: in the ring
        (``FLAGS_telemetry``) or in a running jax profiler session's
        capture.  Hot paths ask once a step and hand the answer down."""
        return self.on or _Annotation.is_enabled()

    def configure(self, ring_size):
        """Resize the ring (keeps the newest spans)."""
        self._ring = deque(self._ring, maxlen=int(ring_size))

    def set_label(self, label, force=False):
        """Process label for dumps/merges (e.g. 'trainer0@host:123',
        'pserver@127.0.0.1:6174').  First writer wins unless forced."""
        if force or not self.label:
            self.label = str(label)

    def clear(self):
        """Drop completed spans.  Open-span stacks are NOT touched:
        they are owned by live threads (a profiler-session reset must
        not blank the flight recorder's who-is-blocked report, and a
        still-open span's end() pops its own stack).  Only stacks left
        empty by finished threads are pruned."""
        self._ring.clear()
        for tid, stack in list(self._stacks.items()):
            if not stack:
                self._stacks.pop(tid, None)

    # -- record path --------------------------------------------------
    def begin(self, name, cid=None, args=None):
        """Open a span.  LIVE-path only: callers guard on ``live()``
        so the dead path never reaches here."""
        tid = threading.get_ident()
        stack = self._stacks.get(tid)
        if stack is None:
            stack = self._stacks[tid] = []
        span = Span(name, time.perf_counter_ns(), tid, cid, args,
                    len(stack))
        stack.append(span)
        if _Annotation.is_enabled():
            meta = dict(args) if args else {}
            if cid is not None:
                meta["cid"] = cid
            span.ann = _Annotation(name, **meta)
            span.ann.__enter__()
        return span

    def end(self, span, cid=None, args=None):
        """Close ``span`` and commit it to the ring.  Tolerates
        unbalanced nesting (an exception that unwound past un-ended
        children): the stack pops back to this span."""
        span.t1 = time.perf_counter_ns()
        ann = span.ann
        if ann is not None:
            if cid is not None:
                ann.set_metadata(cid=cid)
            if args:
                ann.set_metadata(**args)
            ann.__exit__(None, None, None)
        if cid is not None:
            span.cid = cid
        if args:
            span.args = dict(span.args or (), **args)
        stack = self._stacks.get(span.tid)
        if stack:
            while stack:
                if stack.pop() is span:
                    break
        self._ring.append(span)

    def span(self, name, cid=None, args=None):
        """Context-manager form for non-hot paths (RPC rounds, kernel
        lowering).  Returns a shared no-op when no site is live."""
        if not self.live():
            return _NOOP
        return _SpanCtx(self, self.begin(name, cid, args))

    # -- introspection ------------------------------------------------
    def wall_us(self, t_ns):
        return (self._anchor_wall_ns + (t_ns - self._anchor_perf_ns)) \
            / 1e3

    def _span_dict(self, s, now_ns=None):
        d = {"name": s.name, "ts_us": round(self.wall_us(s.t0), 3),
             "tid": s.tid, "depth": s.depth}
        if s.t1:
            d["dur_us"] = round((s.t1 - s.t0) / 1e3, 3)
        else:
            now_ns = now_ns or time.perf_counter_ns()
            d["open"] = True
            d["elapsed_us"] = round((now_ns - s.t0) / 1e3, 3)
        if s.cid is not None:
            d["cid"] = s.cid
        if s.args:
            d["args"] = dict(s.args)
        return d

    def completed(self, limit=None):
        """Snapshot of the ring, oldest first, as dicts.  ``limit``
        keeps only the newest N BEFORE dict conversion — the flight
        recorder dumps from signal handlers, where converting a
        100k-span ring to keep 1k would delay the very hang artifact
        it exists to produce."""
        spans = list(self._ring)
        if limit is not None and len(spans) > int(limit):
            spans = spans[-int(limit):]
        return [self._span_dict(s) for s in spans]

    def open_spans(self):
        """Every thread's currently-open span stack — the hang report:
        the deepest open span per thread is where it is blocked."""
        now = time.perf_counter_ns()
        out = []
        for stack in list(self._stacks.values()):
            for s in list(stack):
                if s.t1 == 0:
                    out.append(self._span_dict(s, now))
        out.sort(key=lambda d: d["ts_us"])
        return out

    # -- dumps --------------------------------------------------------
    def dump_dict(self):
        """The per-process trace artifact: identity + completed + open
        spans + an always-on metrics snapshot."""
        from . import metrics
        return {
            "label": self.label or _default_label(),
            "pid": os.getpid(),
            "spans": self.completed(),
            "open_spans": self.open_spans(),
            "metrics": metrics.snapshot(),
        }

    def dump(self, path):
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.dump_dict(), f)
        return path

    def dump_if_configured(self):
        """Write <FLAGS_telemetry_dump_dir>/trace_<label>_<pid>.json
        when tracing is on and a dump dir is set; returns the path or
        None.  Registered atexit, and called explicitly by the dist
        worker helpers (multiprocessing fork children skip atexit)."""
        if not (self.on and FLAGS.telemetry_dump_dir):
            return None
        label = (self.label or _default_label())
        safe = "".join(c if c.isalnum() or c in "-_." else "_"
                       for c in label)
        path = os.path.join(FLAGS.telemetry_dump_dir,
                            "trace_%s_%d.json" % (safe, os.getpid()))
        try:
            return self.dump(path)
        except Exception:
            return None


def _default_label():
    role = os.environ.get("PADDLE_TRAINING_ROLE", "").lower()
    if role == "trainer":
        return "trainer%s" % os.environ.get("PADDLE_TRAINER_ID", "")
    if role == "pserver":
        return "pserver"
    return "proc"


def traced(name, args_fn=None):
    """Decorator form: span the whole call when the site is live, a
    plain passthrough (one check) when not.  ``args_fn(*a, **kw)`` may
    build the span args lazily — it only runs on the live path.  Used
    at Pallas kernel launch sites: the span records the
    trace/lowering-time cost (inside jit, the launch itself happens on
    device: the capture's device plane has it).
    """
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*a, **kw):
            if not TRACER.live():
                return fn(*a, **kw)
            span = TRACER.begin(
                name, None, args_fn(*a, **kw) if args_fn else None)
            try:
                return fn(*a, **kw)
            finally:
                TRACER.end(span)
        return wrapper
    return deco


TRACER = Tracer()


def _sync_on(v):
    TRACER.on = bool(v)


def _sync_ring(v):
    if TRACER._ring.maxlen != int(v):
        TRACER.configure(v)


# FLAGS.telemetry / telemetry_ring_size assigned at runtime propagate
# into the tracer (the hot-path check stays one attribute read; the
# watcher keeps a programmatic `FLAGS.telemetry = True` from being
# silently ignored).  enable()/disable() still work directly — the
# profiler session uses them without touching the flag.
FLAGS.watch("telemetry", _sync_on)
FLAGS.watch("telemetry_ring_size", _sync_ring)


def disabled_step_probe(n, _counter=None):
    """Replicate the per-step work the instrumented-but-DISABLED
    executor hot path adds — one guard read plus one always-on step
    counter increment per iteration — ``n`` times.  The overhead gate
    (tools/telemetry_overhead.py) times this loop, and
    tests/test_telemetry.py asserts it allocates nothing."""
    trc = TRACER
    if _counter is None:
        from . import metrics
        _counter = metrics.counter(
            "telemetry_probe_total",
            "iterations of the disabled-path overhead probe")
    inc = _counter.inc
    for _ in range(n):
        if trc.on:
            trc.end(trc.begin("probe"))
        inc()


atexit.register(TRACER.dump_if_configured)
