"""Always-on process metrics: counters, gauges, histograms.

Unlike tracing (gated by ``FLAGS_telemetry``), metrics stay on in
production: an update is one short lock + a few arithmetic ops, paid at
per-step / per-round cadence — never per byte.  Hot paths cache the
metric object at module level (registry lookup happens once, at
import).

Exports:
- ``prometheus_text()``: the Prometheus text exposition format (scrape
  it from a debug endpoint or dump it to a file);
- ``snapshot()``: one JSON-able dict of every metric — rides the trace
  dumps and the flight recorder, and bench.py sources its
  ``step_ms_p50/p90/p99`` fields from histogram snapshots.

Histogram percentiles are computed over a bounded reservoir of the most
recent observations (default 4096) — exact for short benches, a
recent-window estimate for long runs; the cumulative bucket counts are
exact forever.

Per-metric locks are REENTRANT (threading.RLock): the flight recorder
(observability/flight.py) snapshots every metric from SIGNAL handlers
(SIGTERM, the bench's SIGALRM wall budget), and a signal landing on the
very thread that is mid-``observe`` must read through the held lock
instead of deadlocking on it — a torn in-flight update in a crash dump
is acceptable; a diagnostic that hangs the process is not.
"""
from __future__ import annotations

import bisect
import re
import threading
from collections import deque

# lock-sanitizer adoption (ISSUE 14): every metric lock is created
# through make_lock — a plain threading lock in production, an
# instrumented acquisition-order-recording lock under
# FLAGS_sanitizer=locks|all.  signal_safe documents (and, under the
# sanitizer, enforces) the REENTRANT invariant explained above.
from paddle_tpu.core.sanitizer import make_lock

__all__ = ["counter", "gauge", "histogram", "snapshot",
           "prometheus_text", "zero_all", "Counter", "Gauge",
           "Histogram", "nearest_rank"]


def nearest_rank(sorted_vals, p):
    """Nearest-rank percentile (p in [0, 100]) over an already-sorted
    list; 0.0 when empty.  The ONE percentile definition shared by
    Histogram.percentile/.snapshot and export.phase_rows — keep them
    answering the same number for the same data."""
    if not sorted_vals:
        return 0.0
    k = min(len(sorted_vals) - 1,
            max(0, int(round(p / 100.0 * (len(sorted_vals) - 1)))))
    return sorted_vals[k]

_REGISTRY = {}
# reentrant: see the signal note above
_REG_LOCK = make_lock("metrics.registry", reentrant=True,
                      signal_safe=True)

# latency-oriented default bounds, in ms (also fine for counts/bytes
# at small scale; pass explicit bounds otherwise)
DEFAULT_BOUNDS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
                  100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0, 10000.0)

RESERVOIR = 4096


class Counter:
    __slots__ = ("name", "help", "_v", "_lock")

    kind = "counter"

    def __init__(self, name, help=""):
        self.name = name
        self.help = help
        self._v = 0
        self._lock = make_lock("metrics.counter.%s" % name,
                               reentrant=True, signal_safe=True)

    def inc(self, v=1):
        with self._lock:
            self._v += v

    @property
    def value(self):
        return self._v

    def zero(self):
        with self._lock:
            self._v = 0

    def snapshot(self):
        return {"type": "counter", "value": self._v}


class Gauge:
    __slots__ = ("name", "help", "_v", "_lock")

    kind = "gauge"

    def __init__(self, name, help=""):
        self.name = name
        self.help = help
        self._v = 0.0
        self._lock = make_lock("metrics.gauge.%s" % name,
                               reentrant=True, signal_safe=True)

    def set(self, v):
        with self._lock:
            self._v = v

    def inc(self, v=1):
        with self._lock:
            self._v += v

    @property
    def value(self):
        return self._v

    def zero(self):
        with self._lock:
            self._v = 0.0

    def snapshot(self):
        return {"type": "gauge", "value": self._v}


class Histogram:
    __slots__ = ("name", "help", "bounds", "_counts", "_sum", "_n",
                 "_recent", "_lock")

    kind = "histogram"

    def __init__(self, name, help="", bounds=None):
        self.name = name
        self.help = help
        self.bounds = tuple(bounds or DEFAULT_BOUNDS)
        self._counts = [0] * (len(self.bounds) + 1)  # +1 = +Inf
        self._sum = 0.0
        self._n = 0
        self._recent = deque(maxlen=RESERVOIR)
        self._lock = make_lock("metrics.histogram.%s" % name,
                               reentrant=True, signal_safe=True)

    def observe(self, v):
        v = float(v)
        with self._lock:
            self._counts[bisect.bisect_left(self.bounds, v)] += 1
            self._sum += v
            self._n += 1
            self._recent.append(v)

    def observe_many(self, values):
        """``observe`` each of ``values`` under one acquire of the lock
        (a loop that gathers a step's samples and flushes them)."""
        vals = list(map(float, values))
        bounds, where = self.bounds, bisect.bisect_left
        with self._lock:
            counts = self._counts
            for v in vals:
                counts[where(bounds, v)] += 1
            self._sum += sum(vals)
            self._n += len(vals)
            self._recent.extend(vals)

    @property
    def count(self):
        return self._n

    @property
    def sum(self):
        return self._sum

    def percentile(self, p):
        """p in [0, 100], over the recent-observation reservoir (exact
        when fewer than RESERVOIR observations were made)."""
        with self._lock:
            vals = sorted(self._recent)
        return nearest_rank(vals, p)

    def zero(self):
        with self._lock:
            self._counts = [0] * (len(self.bounds) + 1)
            self._sum = 0.0
            self._n = 0
            self._recent.clear()

    def snapshot(self):
        with self._lock:
            counts = list(self._counts)
            s, n = self._sum, self._n
            vals = sorted(self._recent)

        return {"type": "histogram", "count": n, "sum": round(s, 6),
                "mean": round(s / n, 6) if n else 0.0,
                "p50": nearest_rank(vals, 50),
                "p90": nearest_rank(vals, 90),
                "p99": nearest_rank(vals, 99),
                "buckets": {("%g" % b): c
                            for b, c in zip(self.bounds, counts)},
                "inf": counts[-1]}


def _get(name, cls, help, **kw):
    with _REG_LOCK:
        m = _REGISTRY.get(name)
        if m is None:
            m = _REGISTRY[name] = cls(name, help, **kw)
        elif not isinstance(m, cls):
            raise TypeError("metric %r already registered as %s"
                            % (name, type(m).__name__))
        return m


def counter(name, help=""):
    return _get(name, Counter, help)


def gauge(name, help=""):
    return _get(name, Gauge, help)


def histogram(name, help="", bounds=None):
    return _get(name, Histogram, help, bounds=bounds)


def snapshot():
    """{name: metric snapshot} over every registered metric."""
    with _REG_LOCK:
        items = sorted(_REGISTRY.items())
    return {name: m.snapshot() for name, m in items}


def zero_all():
    """Reset every metric's VALUE in place (tests; registered objects —
    and the module-level references hot paths cache — stay valid)."""
    with _REG_LOCK:
        items = list(_REGISTRY.values())
    for m in items:
        m.zero()


_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")


def _pname(name):
    return _NAME_RE.sub("_", name)


def _pnum(v):
    """Full-precision exposition value: '%g' would silently round to 6
    significant digits — the byte counters cross 1e6 within seconds and
    a monotonic counter must never appear frozen between scrapes."""
    if isinstance(v, int):
        return str(v)
    return repr(float(v))


def prometheus_text():
    """Prometheus text exposition format over every metric."""
    with _REG_LOCK:
        items = sorted(_REGISTRY.items())
    out = []
    for name, m in items:
        pn = _pname(name)
        if m.help:
            out.append("# HELP %s %s" % (pn, m.help))
        out.append("# TYPE %s %s" % (pn, m.kind))
        if isinstance(m, Histogram):
            snap = m.snapshot()
            acc = 0
            for b in m.bounds:
                acc += snap["buckets"]["%g" % b]
                out.append('%s_bucket{le="%g"} %d' % (pn, b, acc))
            acc += snap["inf"]
            out.append('%s_bucket{le="+Inf"} %d' % (pn, acc))
            out.append("%s_sum %s" % (pn, _pnum(snap["sum"])))
            out.append("%s_count %d" % (pn, snap["count"]))
        else:
            out.append("%s %s" % (pn, _pnum(m.value)))
    return "\n".join(out) + "\n"
