"""XPlane trace reader: aggregate per-op device time from a
``jax.profiler.trace`` capture (SURVEY §5.1 — the device-tracer half of
the profiling story; fluid/profiler.py covers the host half).

``jax.profiler.trace(dir)`` writes
``<dir>/plugins/profile/<run>/*.xplane.pb``; the usual viewer
(tensorboard-plugin-profile) needs a working TF protobuf stack, which
this environment lacks.  This module reads the XSpace container with a
minimal protobuf wire-format walker — no generated code, no
tensorflow — and reduces the "XLA Ops" line to per-op totals, which is
what perf work actually consumes (it found the flash-attention backward
and block-size wins).

Wire schema (public tensorflow/core/profiler/protobuf/xplane.proto):
XSpace.planes=1; XPlane{name=2, lines=3, event_metadata=4(map)};
XLine{name=2, events=4}; XEvent{metadata_id=1, duration_ps=3};
XEventMetadata{id=1, name=2}.
"""
from __future__ import annotations

import collections
import glob
import os
import re

__all__ = ["read_xspace", "op_totals", "print_op_profile",
           "op_profile", "category_profile", "print_category_profile",
           "kernel_profile", "print_kernel_profile"]


def _varint(buf, i):
    x = s = 0
    n = len(buf)
    while True:
        if i >= n:
            raise ValueError("truncated protobuf (varint past buffer)")
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << s
        if not b & 0x80:
            return x, i
        s += 7


def _fields(buf):
    """Yield (field_number, wire_type, value) over a message buffer;
    length-delimited values come back as memoryview slices.  Raises
    ValueError on truncation instead of silently under-reading — a
    half-written capture must not produce quietly-wrong totals."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        fno, wt = key >> 3, key & 7
        if wt == 0:                      # varint
            v, i = _varint(buf, i)
        elif wt == 1:                    # fixed64
            if i + 8 > n:
                raise ValueError("truncated protobuf (fixed64)")
            v = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wt == 2:                    # length-delimited
            ln, i = _varint(buf, i)
            if ln > n - i:
                raise ValueError(
                    "truncated protobuf (field of %d bytes, %d left)"
                    % (ln, n - i))
            v = buf[i:i + ln]
            i += ln
        elif wt == 5:                    # fixed32
            if i + 4 > n:
                raise ValueError("truncated protobuf (fixed32)")
            v = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError("unsupported wire type %d" % wt)
        yield fno, wt, v


def _parse_event(buf):
    """-> (metadata_id, duration_ps, offset_ps).  offset_ps (XEvent
    field 2) positions the event within its line's timeline — the
    chrome-trace export needs it; the aggregate profiles ignore it."""
    meta_id = 0
    dur_ps = 0
    off_ps = 0
    for fno, wt, v in _fields(buf):
        if fno == 1 and wt == 0:
            meta_id = v
        elif fno == 2 and wt == 0:
            off_ps = v
        elif fno == 3 and wt == 0:
            dur_ps = v
    return meta_id, dur_ps, off_ps


def _parse_line(buf):
    """-> (name, [(meta_id, dur_ps, off_ps)], timestamp_ns).
    XLine.timestamp_ns (field 3) is the line's start in unix-epoch ns,
    which is what lets device events merge onto the host spans'
    wall-clock timeline (observability/export.py)."""
    name = ""
    events = []
    ts_ns = 0
    for fno, wt, v in _fields(buf):
        if fno == 2 and wt == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif fno == 3 and wt == 0:
            ts_ns = v
        elif fno == 4 and wt == 2:
            events.append(_parse_event(v))
    return name, events, ts_ns


def _parse_stat(buf):
    """XStat: metadata_id=1, value oneof {double=2, uint64=3, int64=4,
    str=5, bytes=6, ref=7}.  ref values point at an XStatMetadata entry
    whose *name* holds the (deduplicated) string — the caller resolves
    them through the plane's stat-metadata table, so is_ref rides along.
    """
    mid = 0
    val = None
    is_ref = False
    for fno, wt, v in _fields(buf):
        if fno == 1 and wt == 0:
            mid = v
        elif fno in (3, 4) and wt == 0:
            val = v
        elif fno == 7 and wt == 0:
            val = v
            is_ref = True
        elif fno == 2 and wt == 1:
            import struct
            val = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif fno in (5, 6) and wt == 2:
            val = bytes(v).decode("utf-8", "replace")
    return mid, val, is_ref


def _parse_metadata_entry(buf):
    """map<int64, XEventMetadata> entry: key=1, value=2.
    XEventMetadata: id=1, name=2, display_name=4, stats=5."""
    key = 0
    name = ""
    stats = []
    for fno, wt, v in _fields(buf):
        if fno == 1 and wt == 0:
            key = v
        elif fno == 2 and wt == 2:
            for f2, w2, v2 in _fields(v):
                if f2 == 2 and w2 == 2:
                    name = bytes(v2).decode("utf-8", "replace")
                elif f2 == 5 and w2 == 2:
                    stats.append(_parse_stat(v2))
    return key, name, stats


def _parse_stat_metadata_entry(buf):
    """map<int64, XStatMetadata> entry: key=1, value=2{id=1, name=2}."""
    key = 0
    name = ""
    for fno, wt, v in _fields(buf):
        if fno == 1 and wt == 0:
            key = v
        elif fno == 2 and wt == 2:
            for f2, w2, v2 in _fields(v):
                if f2 == 2 and w2 == 2:
                    name = bytes(v2).decode("utf-8", "replace")
    return key, name


class _Plane(dict):
    """Plane dict whose legacy ``lines`` view — (name, [(meta_id,
    dur_ps)]) tuples — is derived from ``xlines`` on first access, so
    parsing doesn't materialize every event twice for consumers that
    never read it."""

    def __missing__(self, key):
        if key == "lines":
            v = [(ln["name"], [(m, d) for m, d, _ in ln["events"]])
                 for ln in self["xlines"]]
            self["lines"] = v
            return v
        raise KeyError(key)


def _parse_plane(buf):
    name = ""
    xlines = []     # timestamped: {name, timestamp_ns, events 3-tuples}
    metadata = {}
    stats_by_id = {}
    stat_names = {}
    for fno, wt, v in _fields(buf):
        if fno == 2 and wt == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif fno == 3 and wt == 2:
            lname, events, ts_ns = _parse_line(v)
            xlines.append({"name": lname, "timestamp_ns": ts_ns,
                           "events": events})
        elif fno == 4 and wt == 2:
            k, nm, stats = _parse_metadata_entry(v)
            metadata[k] = nm
            if stats:
                stats_by_id[k] = stats
        elif fno == 5 and wt == 2:
            k, nm = _parse_stat_metadata_entry(v)
            stat_names[k] = nm
    # resolve stat metadata_ids to names (and ref values to the
    # stat-metadata entry's name, the dedup convention for strings):
    # {event_metadata_id: {stat: value}}
    event_stats = {}
    for k, stats in stats_by_id.items():
        event_stats[k] = {
            stat_names.get(mid, "#%d" % mid):
                (stat_names.get(val, "#%d" % val) if is_ref else val)
            for mid, val, is_ref in stats}
    return _Plane(name=name, xlines=xlines,
                  event_metadata=metadata, event_stats=event_stats)


def read_xspace(path):
    """Parse .xplane.pb file(s) into [{name, lines: [(line_name,
    [(metadata_id, duration_ps)])], event_metadata: {id: name}}].

    Given a trace DIR, reads every host's .xplane.pb in the most
    recently modified run directory (multi-host captures write one file
    per host into the same plugins/profile/<run>/)."""
    if os.path.isdir(path):
        runs = glob.glob(os.path.join(path, "plugins", "profile", "*"))
        runs = [r for r in runs
                if glob.glob(os.path.join(r, "*.xplane.pb"))]
        if not runs:
            raise FileNotFoundError(
                "no .xplane.pb under %s (pass a jax.profiler.trace "
                "output dir)" % path)
        run = max(runs, key=os.path.getmtime)
        files = sorted(glob.glob(os.path.join(run, "*.xplane.pb")))
    else:
        files = [path]
    planes = []
    for f in files:
        buf = memoryview(open(f, "rb").read())
        try:
            for fno, wt, v in _fields(buf):
                if fno == 1 and wt == 2:
                    planes.append(_parse_plane(v))
        except ValueError as e:
            raise ValueError("%s: %s" % (f, e))
    return planes


def op_totals(path, plane_re=r"/device:", line_name="XLA Ops",
              strip_suffix=True):
    """{op_name: total_duration_ps} summed over EVERY matching plane's
    op line (all chips of a multi-device trace).  ``strip_suffix``
    folds '%fusion.123' into '%fusion' families."""
    agg = collections.Counter()
    for plane in read_xspace(path):
        if not re.search(plane_re, plane["name"]):
            continue
        md = plane["event_metadata"]
        for line in plane["xlines"]:
            if line["name"] != line_name:
                continue
            for meta_id, dur, _ in line["events"]:
                name = md.get(meta_id, "#%d" % meta_id)
                name = name.split(" = ")[0]
                if strip_suffix:
                    name = re.sub(r"\.\d+$", "", name)
                agg[name] += dur
    return dict(agg)


def print_op_profile(path, top=20, **kwargs):
    """Top-N op families by device time, with shares — the quick look
    that drives kernel work."""
    agg = op_totals(path, **kwargs)
    total = sum(agg.values()) or 1
    print("%-50s %10s %7s" % ("op", "ms", "share"))
    for name, ps in sorted(agg.items(), key=lambda kv: -kv[1])[:top]:
        print("%-50s %10.3f %6.2f%%"
              % (name[:50], ps / 1e9, 100.0 * ps / total))
    return agg


def op_profile(path, plane_re=r"/device:", line_name="XLA Ops"):
    """Per-op roofline rows from the device trace:
    [{name, category, time_ps, count, flops, bytes, source}] — the
    hlo_category / flops / bytes_accessed stats the XLA runtime attaches
    to each op's event metadata (what tensorboard's op_profile view
    shows, without the TF stack).  flops/bytes are per-execution; time_ps
    is summed over all executions in the capture."""
    rows = {}
    for plane in read_xspace(path):
        if not re.search(plane_re, plane["name"]):
            continue
        md = plane["event_metadata"]
        st = plane.get("event_stats", {})
        for line in plane["xlines"]:
            if line["name"] != line_name:
                continue
            for meta_id, dur, _ in line["events"]:
                name = md.get(meta_id, "#%d" % meta_id).split(" = ")[0]
                r = rows.get(name)
                if r is None:
                    s = st.get(meta_id, {})
                    rows[name] = r = {
                        "name": name,
                        "category": s.get("hlo_category", "?"),
                        "time_ps": 0, "count": 0,
                        "flops": s.get("flops", 0) or 0,
                        "bytes": s.get("bytes_accessed", 0) or 0,
                        "source": s.get("source", "")}
                r["time_ps"] += dur
                r["count"] += 1
    return sorted(rows.values(), key=lambda r: -r["time_ps"])


def category_profile(path, device_kind, **kwargs):
    """Aggregate ``op_profile`` rows by hlo_category with achieved
    TFLOP/s and GB/s against the published peaks of the chip the
    capture came from (``device_kind`` as jax reports it; core/peaks.py
    — an unknown kind raises).  The first stop for 'where did my step
    time go'."""
    from paddle_tpu.core.peaks import device_peaks

    peaks = device_peaks(device_kind)
    peak_tflops, peak_gbps = peaks["bf16_tflops"], peaks["hbm_gbps"]
    cats = {}
    for r in op_profile(path, **kwargs):
        c = cats.setdefault(r["category"], {
            "category": r["category"], "time_ps": 0, "flops": 0,
            "bytes": 0, "count": 0})
        c["time_ps"] += r["time_ps"]
        c["flops"] += r["flops"] * r["count"]
        c["bytes"] += r["bytes"] * r["count"]
        c["count"] += r["count"]
    out = sorted(cats.values(), key=lambda c: -c["time_ps"])
    for c in out:
        secs = c["time_ps"] / 1e12 or 1e-12
        c["tflops_per_s"] = c["flops"] / secs / 1e12
        c["gbps"] = c["bytes"] / secs / 1e9
        c["mxu_util"] = c["tflops_per_s"] / peak_tflops
        c["hbm_util"] = c["gbps"] / peak_gbps
    return out


def print_category_profile(path, device_kind, top=12, **kwargs):
    cats = category_profile(path, device_kind, **kwargs)
    total = sum(c["time_ps"] for c in cats) or 1
    print("%-28s %9s %7s %9s %8s %9s %8s" % (
        "category", "ms", "share", "TFLOP/s", "mxu", "GB/s", "hbm"))
    for c in cats[:top]:
        print("%-28s %9.3f %6.2f%% %9.1f %7.1f%% %9.0f %7.1f%%" % (
            c["category"][:28], c["time_ps"] / 1e9,
            100.0 * c["time_ps"] / total, c["tflops_per_s"],
            100.0 * c["mxu_util"], c["gbps"], 100.0 * c["hbm_util"]))
    return cats


def kernel_profile(path, name_re=r".", plane_re=r"/device:",
                   line_name="XLA Ops", _all_rows=None):
    """Per-KERNEL rows (not categories) for ops matching ``name_re`` —
    the attribution ``category_profile`` cannot give for custom-calls:
    XLA's flop counter is blank inside them (Pallas kernels), so their
    achieved TFLOP/s must come from caller-supplied analytic FLOPs.
    Returns [{name, time_ps, count, ms_per_exec}] sorted by total time;
    pair with analytic per-exec FLOPs to get MXU utilization."""
    all_rows = _all_rows if _all_rows is not None else op_profile(
        path, plane_re=plane_re, line_name=line_name)
    rows = [r for r in all_rows if re.search(name_re, r["name"])]
    for r in rows:
        r["ms_per_exec"] = r["time_ps"] / 1e9 / max(r["count"], 1)
    return rows


def print_kernel_profile(path, device_kind, name_re=r".", top=15,
                         flops_per_exec=None, **kwargs):
    """Print per-kernel rows; ``flops_per_exec`` maps a regex to the
    analytic FLOPs of ONE execution (e.g. flash-attention tile math) to
    report achieved TFLOP/s / MXU fraction (against ``device_kind``'s
    bf16 peak, core/peaks.py) for custom-calls."""
    from paddle_tpu.core.peaks import device_peaks

    peak_tflops = device_peaks(device_kind)["bf16_tflops"]
    all_rows = op_profile(path, **kwargs)   # parse the capture ONCE
    rows = kernel_profile(path, name_re=name_re, _all_rows=all_rows,
                          **kwargs)
    total = sum(r["time_ps"] for r in all_rows) or 1
    print("%-46s %9s %6s %7s %9s %7s" % (
        "kernel", "ms", "count", "share", "TFLOP/s", "mxu"))
    for r in rows[:top]:
        tf = mxu = None
        if flops_per_exec:
            for pat, fl in flops_per_exec.items():
                if re.search(pat, r["name"]):
                    secs = r["time_ps"] / 1e12 or 1e-12
                    tf = fl * r["count"] / secs / 1e12
                    mxu = tf / peak_tflops
                    break
        print("%-46s %9.2f %6d %6.2f%% %9s %7s" % (
            r["name"][:46], r["time_ps"] / 1e9, r["count"],
            100.0 * r["time_ps"] / total,
            "%.1f" % tf if tf is not None else "-",
            "%.1f%%" % (100 * mxu) if mxu is not None else "-"))
    return rows
