"""Flight recorder: turn a hang or a kill into a JSON artifact.

The last N spans live in the tracer's ring and metrics are always on —
this module is the DUMP path: on a watchdog timeout, a bench
wall-budget expiry, an injected fault, or SIGTERM/SIGALRM, write one
JSON file naming

- the blocked operation and the peers it was waiting on (the caller
  passes the watchdog's per-pserver barrier state),
- every thread's currently-open span stack (who is blocked where),
- the recent completed spans and the full metrics snapshot.

So the next hang produces a who-was-waiting-on-whom report instead of
a bare ``rc:124``.

Dumps land in ``FLAGS_telemetry_dump_dir`` when set, else the system
temp dir; the writer never raises (a diagnostic must not sink the
operation it is diagnosing).
"""
from __future__ import annotations

import json
import os
import signal as _signal
import tempfile
import threading
import time

from paddle_tpu.core.flags import FLAGS

from .trace import TRACER

__all__ = ["dump", "note_fault", "install_signal_handlers",
           "SCHEMA_VERSION"]

# Envelope version (ISSUE 13 satellite): the artifact is parsed by
# tools/fault_matrix.py, tools/watchtower.py, tools/trace_report.py
# and the scale/slo preset asserts — PR 12 embedded the ledger with no
# versioning and downstream parsers would break silently on shape
# changes.  Bump this WITH a tests/test_flight_schema.py golden update
# whenever a top-level key is added/removed/renamed.
SCHEMA_VERSION = 1

# keep the artifact bounded even with a huge ring configured
MAX_RECENT_SPANS = 1024
MAX_LEDGER_SAMPLES = 256

# RLock, same reasoning as metrics.py: a signal-handler dump (SIGTERM
# arriving during a SIGALRM dump, both on the main thread) must not
# self-deadlock inside its own hang diagnostic.  Sanitizer-adopted
# (ISSUE 14): make_lock(signal_safe=True) records — and under
# FLAGS_sanitizer=locks enforces — exactly that invariant.
from paddle_tpu.core.sanitizer import make_lock

_seq_lock = make_lock("flight.seq", reentrant=True, signal_safe=True)
_seq = 0
_noted_faults = set()


def _next_seq():
    global _seq
    with _seq_lock:
        _seq += 1
        return _seq


def dump(reason, blocked=None, directory=None, sections=None):
    """Write the flight-recorder artifact; returns its path, or None if
    the write failed (best-effort by design).  ``blocked`` is a
    JSON-able dict describing what the process was stuck on — e.g.
    {"op": "recv", "details": [per-pserver barrier state...]}.
    ``sections`` lets the caller enrich/override a top-level envelope
    section (the SLO engine embeds the offending series under "slo");
    envelope keys are pinned by tests/test_flight_schema.py."""
    try:
        directory = (directory or FLAGS.telemetry_dump_dir
                     or tempfile.gettempdir())
        os.makedirs(directory, exist_ok=True)
        from . import metrics
        spans = TRACER.completed(limit=MAX_RECENT_SPANS)
        # resource-ledger snapshot (ISSUE 12): current per-subsystem
        # values + the newest time-series slice, so a collapse
        # artifact shows the resource curve INTO the failure.  Best
        # effort like everything else here.
        try:
            from . import ledger as _ledger
            ledger_snap = _ledger.snapshot(limit=MAX_LEDGER_SAMPLES)
        except Exception:
            ledger_snap = None
        # SLO status (ISSUE 13): spec table + active burn-rate alerts
        # when an evaluator is installed; the key is present either
        # way so parsers never branch on existence
        try:
            from . import slo as _slo
            slo_snap = _slo.snapshot_for_flight()
        except Exception:
            slo_snap = None
        rec = {
            "kind": "flight_recorder",
            "schema_version": SCHEMA_VERSION,
            "reason": str(reason),
            "wall_time": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "pid": os.getpid(),
            "label": TRACER.label or "",
            "telemetry_on": TRACER.on,
            "blocked": blocked,
            "open_spans": TRACER.open_spans(),
            "recent_spans": spans,
            "metrics": metrics.snapshot(),
            "ledger": ledger_snap,
            "slo": slo_snap,
        }
        if sections:
            rec.update(sections)
        path = os.path.join(
            directory, "flight_%d_%d.json" % (os.getpid(), _next_seq()))
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(rec, f)
        os.replace(tmp, path)
        return path
    except Exception:
        return None


def note_fault(point):
    """Injected-fault hook (resilience.FaultInjector.fire): dump once
    per fault point per process, and ONLY when a dump dir is explicitly
    configured — tools/fault_matrix.py asserts the artifact exists
    after each injected-fault run, while ordinary fault tests don't
    litter the temp dir."""
    if not FLAGS.telemetry_dump_dir or point in _noted_faults:
        return None
    _noted_faults.add(point)
    return dump("fault:%s" % point, blocked={"fault_point": point})


def install_signal_handlers(signals=("SIGTERM", "SIGALRM")):
    """Chain a flight dump onto the named signals' existing handlers
    (previous handler still runs; SIG_DFL is re-raised so the process
    still dies).  Main-thread only; returns the installed signal names.
    """
    installed = []
    for name in signals:
        signum = getattr(_signal, name, None)
        if signum is None:
            continue
        try:
            prev = _signal.getsignal(signum)

            def _handler(sn, frame, _prev=prev, _name=name):
                dump("signal:%s" % _name)
                if callable(_prev):
                    _prev(sn, frame)
                elif _prev != _signal.SIG_IGN:
                    # SIG_DFL, or None (handler installed outside
                    # Python, uncallable from here): restore the
                    # default action and re-deliver so the process
                    # still dies — swallowing a fatal signal would
                    # reproduce the hang class this module diagnoses
                    _signal.signal(sn, _signal.SIG_DFL)
                    os.kill(os.getpid(), sn)

            _signal.signal(signum, _handler)
            installed.append(name)
        except (ValueError, OSError):
            pass  # non-main thread or unsupported signal
    return installed
