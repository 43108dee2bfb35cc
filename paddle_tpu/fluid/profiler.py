"""Profiler: host events + device traces — now a thin view over the
telemetry layer (paddle_tpu/observability).

Parity: reference python/paddle/fluid/profiler.py:135 (profiler context
manager), platform/profiler.cc (RecordEvent host events + table dump),
tools/timeline.py (chrome://tracing export).  Device-side CUPTI capture
is replaced by jax.profiler (XPlane/Xprof), started alongside host
events.

The PUBLIC API is unchanged (MIGRATION.md); the backing store moved:

- ``RecordEvent`` opens a telemetry span (observability/trace.TRACER),
  so profiler events and the executor/RPC instrumentation land in ONE
  ring and one exported timeline;
- the old module-grown ``events`` list — which was UNBOUNDED and was
  appended under a lock whose ``enabled`` flag was read outside it —
  is gone: completed spans live in the tracer's bounded ring
  (``FLAGS_telemetry_ring_size``, oldest evict first), appends are
  GIL-atomic deque ops, and the enabled flag is a single bool with
  single-writer semantics (``start_profiler``/``stop_profiler`` flip
  it; concurrent RecordEvents may record one straggler span across the
  flip, never corrupt state or leak memory).
"""
from __future__ import annotations

import contextlib
import json
import os
import time

from paddle_tpu.observability.trace import TRACER as _TRC

__all__ = ["RecordEvent", "profiler", "start_profiler", "stop_profiler",
           "reset_profiler", "cuda_profiler", "export_chrome_tracing",
           "device_op_profile"]

_state = {
    "enabled": False,        # profiler session active (public contract)
    "owns_tracer": False,    # we enabled the tracer (vs FLAGS_telemetry)
    "start_us": 0.0,         # wall µs; stop_profiler tables spans >= it
    "jax_trace_dir": None,
}


class RecordEvent:
    """RAII host-event annotation (reference platform/profiler.h:72).
    Backed by a telemetry span: records whenever the TRACER is live —
    under a profiler session (this module's or jax's own) OR plain
    FLAGS_telemetry."""

    __slots__ = ("name", "_span")

    def __init__(self, name):
        self.name = name
        self._span = None

    def __enter__(self):
        if _TRC.live():
            self._span = _TRC.begin(self.name)
        return self

    def __exit__(self, *exc):
        # gate on the span we actually opened, not on a re-read of the
        # enabled flag: a stop_profiler between enter and exit must not
        # leave an open span (the old code's enabled re-read dropped
        # such events and left self.start dangling)
        if self._span is not None:
            _TRC.end(self._span)
            self._span = None
        return False


def reset_profiler():
    """Discard profiling data collected so far (public API).  The
    profiler's session view resets unconditionally (later tables and
    exports only see spans from now on); the shared tracer ring is
    cleared only when no FLAGS_telemetry session owns it — that ring
    is the flight recorder's pre-hang history, and the old
    session-local events list this API used to clear never touched
    framework-wide state either."""
    _state["start_us"] = _TRC.wall_us(time.perf_counter_ns())
    if _state["owns_tracer"] or not _TRC.on:
        _TRC.clear()


def start_profiler(state="All", trace_dir=None):
    if _state["enabled"]:
        return
    _state["enabled"] = True
    _state["owns_tracer"] = not _TRC.on
    _TRC.enable()
    # sets start_us (session isolation) and clears the ring only when
    # WE turned the tracer on: under a live FLAGS_telemetry session the
    # ring is the flight recorder's pre-hang history and must survive a
    # profiler session starting
    reset_profiler()
    if trace_dir and state in ("GPU", "All", "TPU"):
        try:
            import jax
            jax.profiler.start_trace(trace_dir)
            _state["jax_trace_dir"] = trace_dir
        except Exception:
            _state["jax_trace_dir"] = None


def device_op_profile(trace_dir, top=20):
    """Per-op device-time table from a captured trace dir (the XPlane
    files a ``start_profiler(trace_dir=...)`` / ``jax.profiler.trace``
    run leaves behind) — utils/xplane.py does the parsing."""
    from paddle_tpu.utils import xplane
    return xplane.print_op_profile(trace_dir, top=top)


def _session_spans():
    """Completed tracer spans belonging to this profiler session."""
    return [s for s in _TRC.completed()
            if s["ts_us"] >= _state["start_us"]]


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    if not _state["enabled"]:
        return
    _state["enabled"] = False
    if _state["jax_trace_dir"]:
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:
            pass
        _state["jax_trace_dir"] = None
    spans = _session_spans()
    if _state["owns_tracer"]:
        _TRC.disable()
        _state["owns_tracer"] = False
    # aggregate per name (reference prints a table sorted by sorted_key)
    agg = {}
    for s in spans:
        dur = s.get("dur_us", 0.0) / 1e3
        total, cnt, mx, mn = agg.get(s["name"],
                                     (0.0, 0, 0.0, float("inf")))
        agg[s["name"]] = (total + dur, cnt + 1, max(mx, dur),
                          min(mn, dur))
    rows = [(name, cnt, total, total / cnt, mn, mx)
            for name, (total, cnt, mx, mn) in agg.items()]
    key_idx = {"calls": 1, "total": 2, "ave": 3, "min": 4, "max": 5}.get(
        sorted_key, 2)
    rows.sort(key=lambda r: r[key_idx], reverse=True)
    if rows:
        print("%-40s %8s %12s %12s %12s %12s" %
              ("Event", "Calls", "Total(ms)", "Avg(ms)", "Min(ms)",
               "Max(ms)"))
        for r in rows:
            print("%-40s %8d %12.3f %12.3f %12.3f %12.3f" % r)
    if profile_path:
        export_chrome_tracing(profile_path, spans)


def export_chrome_tracing(path, events=None):
    """Dump events as a chrome://tracing JSON (reference
    tools/timeline.py).  ``events`` accepts the legacy
    (name, start_ns, end_ns, tid) tuples or telemetry span dicts;
    default: the current profiler session's spans (honoring
    reset_profiler's boundary, like stop_profiler's table — pass
    ``_TRC.completed()`` explicitly for the whole ring)."""
    from paddle_tpu.observability import export
    if events is None:
        events = _session_spans()
    # legacy (name, start_ns, end_ns, tid) tuples -> span dicts, then
    # one shared span-to-chrome conversion (observability/export.py)
    spans = []
    for ev in events:
        if isinstance(ev, dict):
            spans.append(ev)
        else:
            name, s, e, tid = ev
            spans.append({"name": name, "tid": tid, "ts_us": s / 1e3,
                          "dur_us": (e - s) / 1e3})
    trace = export.chrome_trace([{"pid": 0, "label": "profiler",
                                  "spans": spans}])
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(trace, f)


@contextlib.contextmanager
def profiler(state="CPU", sorted_key=None, profile_path="/tmp/profile",
             trace_dir=None):
    start_profiler(state, trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(*args, **kwargs):
    """Kept for API parity (reference profiler.py:36 wraps nvprof); on TPU
    use profiler(state='TPU', trace_dir=...) which starts an Xprof trace."""
    yield
