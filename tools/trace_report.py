#!/usr/bin/env python
"""Merge telemetry trace dumps and print a per-phase step-time
breakdown (ISSUE 6 tentpole c — the timeline.py analog for the new
telemetry layer).

Inputs are the per-process dump files the tracer writes
(``trace_<label>_<pid>.json`` under FLAGS_telemetry_dump_dir, or any
``Tracer.dump`` output; a previously merged chrome trace also loads).
A jax profiler capture (``--xplane DIR``) already holds the program's
spans beside the runtime's events and the device ops
(observability/trace.py bridges them): ``--gaps`` reads it alone, moves
the device plane onto the host planes' clock (``export.clock_skew``)
and puts the device's idle gaps down to the spans.

Usage:
    python tools/trace_report.py DUMP.json [DUMP2.json ...]
    python tools/trace_report.py DUMPS... --merge merged_trace.json
    python tools/trace_report.py --xplane /tmp/xprof_capture --gaps
    python tools/trace_report.py DUMPS... --prefix step. --top 20
    python tools/trace_report.py DUMPS... --numerics   # grad-norm
        rollup per process; numerics_*.json trip artifacts passed as
        inputs are summarized (first bad op, round cid, recent losses)
    python tools/trace_report.py DUMPS... --all        # every rollup

--merge writes one chrome://tracing JSON: each process is a chrome
pid named by its label, and spans of the same sync round share a
``cid`` arg ((round, sender, seq) wire identity) — select one in the
viewer to correlate a trainer's send/barrier/get with the pserver's
scatter/apply for that round.

Per-subsystem rollups are table-registry driven (ROLLUPS below): each
entry names its flag, the export.py rows/format pair and its section
title, so a new subsystem adds ONE registry row instead of another
copy-paste dispatch branch (ISSUE 13 satellite; rollups had been
copy-pasted per flag since PR 7).  ``--all`` implies ``--kernels``
plus every registry rollup.
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# one row per rollup: (flag/attr name, export rows fn, export format
# fn, text-mode section title, --help text).  Everything downstream —
# argparse registration, --all, the JSON wrap and the text sections —
# iterates this table.  ``gaps`` alone reads the --xplane capture, not
# the dumps.
ROLLUPS = (
    ("numerics", "numerics_rows", "format_numerics_table",
     "numerics rollup (grad-norm trend / nonfinite sightings per "
     "process):",
     "print the numerics-observatory rollup (grad-norm trend, param "
     "absmax, nonfinite counts per process — ISSUE 8); "
     "numerics_*.json trip artifacts may also be passed as inputs "
     "and are summarized"),
    ("wire", "wire_rows", "format_wire_table",
     "wire rollup (grad compression / fastwire traffic / staleness "
     "per process):",
     "print the pserver wire/compression rollup (grad bytes raw vs "
     "on-wire, codec encode time, fastwire traffic, staleness gap "
     "per process — ISSUE 10)"),
    ("serve", "serve_rows", "format_serve_table",
     "serve rollup (requests/tokens / decode occupancy / TTFT+ITL / "
     "paged KV pressure / prefix-cache + speculative columns per "
     "process):",
     "print the serving-tier rollup (requests/tokens, decode-batch "
     "occupancy, TTFT and inter-token latency, paged KV cache "
     "pressure: blocks used/total, allocation failures, preemptions "
     "— ISSUE 11; plus the ISSUE 19 columns: prefix-hit-rate, "
     "blocks shared, speculative accept-rate, draft overhead)"),
    ("scale", "scale_rows", "format_scale_table",
     "scale rollup (resource ledgers per process: pending grads / "
     "caches+evictions / barrier quorum / apply backlog):",
     "print the scale-observatory rollup (resource ledgers per "
     "process: pending-grad footprint, reply/replay cache bytes + "
     "evictions, barrier set, apply backlog, oldest-pending age, "
     "quorum scan work — ISSUE 12); flight dumps work as inputs too "
     "(their metrics snapshot carries the ledger gauges)"),
    ("slo", "slo_rows", "format_slo_table",
     "slo rollup (burn rates / budget remaining / alerts per "
     "process):",
     "print the Watchtower SLO rollup (per-spec fast/slow burn "
     "rates, error budget remaining, alert counters per process — "
     "ISSUE 13); flight dumps written by a firing alert carry the "
     "offending series too"),
    ("moe", "moe_rows", "format_moe_table",
     "moe rollup (router steps/tokens / per-expert load / dropped "
     "fraction / entropy per process):",
     "print the MoE routing rollup (capacity-factor stats from "
     "parallel/moe.py: per-expert load distribution, dropped-token "
     "fraction, router entropy per process — ISSUE 15 rider)"),
    ("weaver", "weaver_rows", "format_weaver_table",
     "weaver rollup (schedules explored/pruned / failing schedules / "
     "minimized repro length per process):",
     "print the schedule-exploration rollup (weaver explorer "
     "coverage: schedules executed, sleep-set-pruned branches, "
     "failing schedules found, minimized decision-trace length per "
     "process — ISSUE 18); tools/weaver.py leaves a dump when "
     "FLAGS_telemetry_dump_dir is set"),
    ("gaps", "gap_rows", "format_gap_table",
     "idle gaps (device idle time put down to the program span the "
     "scheduler/executor thread was in):",
     "from the --xplane capture: every gap between device ops put "
     "down to the deepest program span (serve.* / step.* / "
     "executor.*) covering most of it — span, gaps, idle seconds, "
     "share of the window, idle seconds under the span itself; "
     "'unspanned' last"),
)


def _print_trips(paths):
    """Summarize numerics_*.json trip artifacts: who tripped, where,
    which round/step, and the first bad op when bisect named one."""
    print("numerics trip artifacts:")
    for p in sorted(paths):
        try:
            with open(p) as f:
                rec = json.load(f)
        except Exception as e:
            print("  %s: unreadable (%s)" % (p, e))
            continue
        parts = [rec.get("reason", "?")]
        if rec.get("cid"):
            parts.append("cid=%s" % rec["cid"])
        if rec.get("sender"):
            parts.append("sender=%s" % rec["sender"])
        fbo = rec.get("first_bad_op")
        if fbo:
            parts.append("first_bad_op=%s (block %s op %s, out %s)" % (
                fbo.get("type"), fbo.get("block"), fbo.get("op_idx"),
                fbo.get("output")))
        if rec.get("trip_vars"):
            parts.append("vars=%s" % rec["trip_vars"][:4])
        losses = rec.get("losses") or []
        if losses:
            parts.append("recent_losses=%s" % [
                round(v, 4) for v in losses[-4:]])
        print("  %s: %s" % (os.path.basename(p), "  ".join(parts)))


def main(argv=None):
    from paddle_tpu.observability import export

    ap = argparse.ArgumentParser(
        description="merge telemetry dumps; print per-phase breakdown")
    ap.add_argument("dumps", nargs="*",
                    help="per-process trace dump JSON files")
    ap.add_argument("--merge", default=None, metavar="OUT.json",
                    help="write the merged chrome://tracing JSON here")
    ap.add_argument("--xplane", default=None, metavar="DIR",
                    help="jax profiler capture (dir or .xplane.pb) "
                         "that --gaps reads")
    ap.add_argument("--prefix", default="",
                    help="only report span names with this prefix "
                         "(e.g. 'step.' for the executor phases)")
    ap.add_argument("--top", type=int, default=0,
                    help="limit the table to the top-N phases by total")
    ap.add_argument("--json", action="store_true",
                    help="emit the breakdown rows as JSON instead")
    ap.add_argument("--kernels", action="store_true",
                    help="with --json: wrap output as {phases, kernels} "
                         "including the per-kernel rollup (text mode "
                         "always prints the rollup when kernels exist)")
    for flag, _rows, _fmt, _title, help_text in ROLLUPS:
        ap.add_argument("--" + flag, action="store_true",
                        help=help_text)
    ap.add_argument("--all", action="store_true", dest="all_rollups",
                    help="implies --kernels plus every per-subsystem "
                         "rollup (%s)" % " ".join(
                             "--" + f for f, *_ in ROLLUPS))
    args = ap.parse_args(argv)
    if args.all_rollups:
        args.kernels = True
        for flag, *_ in ROLLUPS:
            setattr(args, flag, flag != "gaps" or bool(args.xplane))
    if args.gaps and not args.xplane:
        ap.error("--gaps reads a profiler capture: give --xplane DIR")
    if not args.dumps and not args.gaps:
        ap.error("give trace dumps, or --xplane DIR --gaps")
    profile = export.load_profile(args.xplane) if args.gaps else None

    # numerics trip artifacts ride the same dump dir as trace dumps;
    # partition them out by their fixed filename shape
    # (numerics_<pid>_<n>.json, see numerics.dump_numerics) so the
    # merge only sees real trace dumps — a multi-MB trace is never
    # json-parsed twice just to read a 'kind' key
    trips = []
    dump_paths = []
    for p in args.dumps:
        if os.path.basename(p).startswith("numerics_"):
            trips.append(p)
        else:
            dump_paths.append(p)
    if not dump_paths and trips:
        # trip-artifacts-only invocation: summarize and exit
        _print_trips(trips)
        return 0

    trace, dumps = export.merge_files(dump_paths, out_path=args.merge)
    rows = export.phase_rows(dumps)
    if args.prefix:
        rows = [r for r in rows if r["name"].startswith(args.prefix)]
    # per-kernel rollup (ISSUE 7): Pallas launch-site spans grouped by
    # kernel name — fusion wins readable straight from a telemetry
    # dump.  Skipped in plain
    # --json mode (pre-existing contract emits bare phase rows), which
    # also spares the full extra span walk on large rings
    krows = export.kernel_rows(dumps, trace) \
        if (args.kernels or not args.json) else []
    # every registered rollup asked for: flag -> its export rows
    rollup_rows = {flag: getattr(export, rows_fn)(
                       profile if flag == "gaps" else dumps)
                   for flag, rows_fn, _fmt, _title, _h in ROLLUPS
                   if getattr(args, flag)}
    if args.json:
        if rollup_rows or args.kernels:
            # one wrapped object, keys present for the rollups asked
            # for; bare phase rows stay the no-flag contract
            print(json.dumps(dict(
                {"phases": rows, "kernels": krows}, **rollup_rows),
                indent=2))
        else:
            print(json.dumps(rows, indent=2))
    else:
        total_spans = sum(len(d.get("spans", [])) for d in dumps)
        if dumps:
            print("%d process dump(s), %d spans, %d trace events%s" % (
                len(dumps), total_spans, len(trace["traceEvents"]),
                (" -> %s" % args.merge) if args.merge else ""))
        open_spans = [s for d in dumps
                      for s in d.get("open_spans", [])]
        if open_spans:
            print("OPEN (never finished — where each thread was "
                  "blocked at dump time):")
            for s in open_spans:
                print("  %-32s elapsed %.1f ms  %s" % (
                    s["name"], s.get("elapsed_us", 0) / 1e3,
                    s.get("cid", "")))
        if dumps:
            print(export.format_phase_table(rows, top=args.top))
        if krows:
            print("\nper-kernel rollup (pallas launch sites + device "
                  "ops of a chrome trace that holds them):")
            print(export.format_kernel_table(krows))
        for flag, _rows_fn, fmt_fn, title, _h in ROLLUPS:
            if not getattr(args, flag):
                continue
            print("\n" + title)
            print(getattr(export, fmt_fn)(rollup_rows[flag]))
    if trips:
        _print_trips(trips)
    if not rows:
        # a written --merge artifact — or any requested rollup that
        # produced rows (flight dumps carry metrics but no completed
        # spans) — is a success even when the span table is empty;
        # fail only when the run produced no output at all
        if dumps:
            print("no completed spans matched", file=sys.stderr)
        return 0 if (args.merge or krows
                     or any(rollup_rows.values())) else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
