"""Trace export: merge per-process telemetry dumps into ONE
chrome://tracing JSON, reduce a trace to a per-phase breakdown table,
and put a profiler capture's device idle gaps down to the program's
spans.

The per-process dump (trace.Tracer.dump) stamps spans in absolute
wall-clock microseconds, so merging is pure concatenation: each process
becomes a chrome pid with its label as the process name, and spans of
the same sync round share a ``cid`` arg (trace.round_cid) — select one
in the viewer to see the trainer's send/barrier/get next to the
pserver's scatter/apply for that round.

Device traces: under a jax profiler session the tracer's spans are
events of the capture itself (trace.py's bridge), so the merged
timeline of host spans and device ops IS the xplane — open it in
Perfetto/TensorBoard, or reduce it with ``gap_rows``, which first moves
the device plane onto the host planes' clock (``clock_skew``).
"""
from __future__ import annotations

import bisect
import glob
import json
import os

from . import metrics

__all__ = ["load_dump", "chrome_trace", "merge_files", "phase_rows",
           "format_phase_table", "kernel_rows", "format_kernel_table",
           "numerics_rows", "format_numerics_table", "serve_rows",
           "format_serve_table", "scale_rows", "format_scale_table",
           "slo_rows", "format_slo_table", "weaver_rows",
           "format_weaver_table", "load_profile", "clock_skew",
           "gap_rows", "format_gap_table"]


def load_dump(path):
    with open(path) as f:
        data = json.load(f)
    if "traceEvents" in data and "spans" not in data:
        # already a chrome trace (e.g. a previous merge): adapt
        spans = []
        for ev in data["traceEvents"]:
            if ev.get("ph") != "X":
                continue
            # open-span markers carry elapsed-at-dump-time as their
            # duration; re-ingesting them as completed spans would let
            # a hung run's open barriers dominate the phase table.
            # Device events (merges written before the tracer bridged
            # to the profiler held them) are likewise excluded: the
            # original dumps never contained them, so the re-loaded
            # phase table must not be device-op-dominated either.
            if ev.get("cat") in ("open", "device") \
                    or (ev.get("args") or {}).get("open"):
                continue
            s = {"name": ev.get("name", "?"), "ts_us": ev.get("ts", 0),
                 "dur_us": ev.get("dur", 0), "tid": ev.get("tid", 0)}
            cid = (ev.get("args") or {}).get("cid")
            if cid:
                s["cid"] = cid
            spans.append(s)
        return {"label": os.path.basename(path), "pid": 0,
                "spans": spans, "open_spans": [], "metrics": {}}
    return data


def chrome_trace(dumps):
    """[per-process dump dicts] -> chrome trace dict."""
    events = []
    used_pids = set()
    for i, d in enumerate(dumps):
        # fallback pids sit above kernel.pid_max (4194304) so they
        # can't collide with another dump's real OS pid; an explicit
        # pid 0 (the profiler's single-process export) is honored
        pid = d["pid"] if d.get("pid") is not None else (9_000_000 + i)
        # multi-host merges can present the SAME os pid from different
        # machines — remap the later dump so each keeps its own chrome
        # track (and its own process_name label)
        while pid in used_pids:
            pid = 9_000_000 + i if pid < 9_000_000 else pid + 1
        used_pids.add(pid)
        label = d.get("label") or ("proc%d" % i)
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": label}})
        for s in d.get("spans", []):
            ev = {"name": s["name"], "ph": "X", "pid": pid,
                  "tid": s.get("tid", 0), "ts": s.get("ts_us", 0),
                  "dur": s.get("dur_us", 0), "cat": "host"}
            args = dict(s.get("args") or {})
            if s.get("cid"):
                args["cid"] = s["cid"]
            if args:
                ev["args"] = args
            events.append(ev)
        for s in d.get("open_spans", []):
            ev = {"name": s["name"] + " (open)", "ph": "X", "pid": pid,
                  "tid": s.get("tid", 0), "ts": s.get("ts_us", 0),
                  "dur": s.get("elapsed_us", 0), "cat": "open"}
            args = dict(s.get("args") or {})
            if s.get("cid"):
                args["cid"] = s["cid"]
            args["open"] = True
            ev["args"] = args
            events.append(ev)
    events.sort(key=lambda e: (e.get("ts", 0), e.get("pid", 0)))
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def merge_files(paths, out_path=None):
    """Merge per-process dump files into one chrome trace; write it to
    ``out_path`` when given.  Returns (trace_dict, dumps)."""
    dumps = [load_dump(p) for p in paths]
    trace = chrome_trace(dumps)
    if out_path:
        d = os.path.dirname(out_path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(trace, f)
    return trace, dumps


def phase_rows(dumps):
    """Aggregate span durations by name over per-process dumps:
    [{name, count, total_ms, mean_ms, p50_ms, p99_ms, share}] sorted by
    total time — the per-phase step-time breakdown."""
    groups = {}
    for d in dumps:
        for s in d.get("spans", []):
            dur = s.get("dur_us")
            if dur is None:
                continue
            groups.setdefault(s["name"], []).append(dur / 1e3)
    total = sum(sum(v) for v in groups.values()) or 1e-12
    rows = []
    for name, vals in groups.items():
        vals.sort()
        n = len(vals)
        rows.append({
            "name": name, "count": n,
            "total_ms": round(sum(vals), 3),
            "mean_ms": round(sum(vals) / n, 3),
            "p50_ms": round(metrics.nearest_rank(vals, 50), 3),
            "p99_ms": round(metrics.nearest_rank(vals, 99), 3),
            "share": round(sum(vals) / total, 4),
        })
    rows.sort(key=lambda r: -r["total_ms"])
    return rows


def _kernel_group(name):
    """Normalize a device-op / launch-site name to its kernel family:
    'pallas.flash_attention' -> 'flash_attention',
    '%fusion.123' / 'fusion.4' -> 'fusion',
    'jit__matmul_kernel.12' -> 'jit__matmul_kernel' — so one row per
    kernel, not one per compiled instance."""
    import re

    if name.startswith("pallas."):
        name = name[len("pallas."):]
    name = name.lstrip("%")
    # strip compiled-instance suffixes ('.123') only — a bare trailing
    # digit is part of the op name ('exp2', 'atan2')
    name = re.sub(r"(\.\d+)+$", "", name)
    return name or "?"


def kernel_rows(dumps, trace=None):
    """Per-kernel rollup (ISSUE 7 satellite): Pallas launch-site spans
    (the ``pallas.*`` spans the kernels emit under FLAGS_telemetry)
    grouped by kernel name, merged with the device-side events
    (cat 'device') of a chrome trace that holds them, so a fusion win
    is readable straight from a telemetry dump.  Returns
    [{kernel, side, count, total_ms, mean_ms, share}] sorted by total
    time; host and device entries stay separate rows ('side')."""
    groups = {}
    for d in dumps:
        for s in d.get("spans", []):
            if not s.get("name", "").startswith("pallas."):
                continue
            dur = s.get("dur_us")
            if dur is None:     # open span: no duration to roll up
                continue
            key = (_kernel_group(s["name"]), "host")
            groups.setdefault(key, []).append(dur / 1e3)
    for ev in (trace or {}).get("traceEvents", []):
        if ev.get("cat") != "device" or ev.get("ph") != "X":
            continue
        key = (_kernel_group(ev.get("name", "?")), "device")
        groups.setdefault(key, []).append((ev.get("dur") or 0) / 1e3)
    total = {side: sum(sum(v) for (k, s), v in groups.items()
                       if s == side) or 1e-12
             for side in ("host", "device")}
    rows = []
    for (kernel, side), vals in groups.items():
        rows.append({
            "kernel": kernel, "side": side, "count": len(vals),
            "total_ms": round(sum(vals), 3),
            "mean_ms": round(sum(vals) / len(vals), 3),
            "share": round(sum(vals) / total[side], 4),
        })
    rows.sort(key=lambda r: (r["side"], -r["total_ms"]))
    return rows


def format_kernel_table(rows):
    out = ["%-40s %-7s %7s %10s %9s %7s" % (
        "kernel", "side", "count", "total_ms", "mean_ms", "share")]
    for r in rows:
        out.append("%-40s %-7s %7d %10.3f %9.3f %6.1f%%" % (
            r["kernel"][:40], r["side"], r["count"], r["total_ms"],
            r["mean_ms"], 100.0 * r["share"]))
    return "\n".join(out)


def numerics_rows(dumps):
    """Numerics-observatory rollup (ISSUE 8 satellite): per process
    dump, the training-health metrics the always-on registry carried —
    the gradient-norm distribution (trend over the run's recent
    window), parameter abs-max, nonfinite sightings and guard trips.
    Works on any trace dump (the metrics snapshot rides every one);
    processes that never observed a health read-back report zeros."""
    rows = []
    for d in dumps:
        m = d.get("metrics", {})
        gh = m.get("grad_global_norm", {})
        rows.append({
            "label": d.get("label", "?"),
            "checks": m.get("numerics_checks_total", {}).get("value", 0),
            "grad_norm_mean": round(gh.get("mean", 0.0), 6),
            "grad_norm_p50": round(gh.get("p50", 0.0), 6),
            "grad_norm_p90": round(gh.get("p90", 0.0), 6),
            "grad_norm_p99": round(gh.get("p99", 0.0), 6),
            "param_absmax": round(
                m.get("param_absmax", {}).get("value", 0.0), 6),
            "nonfinite": m.get("numerics_nonfinite_total",
                               {}).get("value", 0),
            "trips": m.get("numerics_trips_total", {}).get("value", 0),
            "pserver_nonfinite_grads": m.get(
                "pserver_nonfinite_grads_total", {}).get("value", 0),
        })
    rows.sort(key=lambda r: r["label"])
    return rows


def format_numerics_table(rows):
    out = ["%-24s %7s %13s %12s %12s %12s %10s %6s" % (
        "process", "checks", "grad_norm_p50", "p90", "p99",
        "param_absmax", "nonfinite", "trips")]
    for r in rows:
        out.append("%-24s %7d %13.4g %12.4g %12.4g %12.4g %10d %6d" % (
            r["label"][:24], r["checks"], r["grad_norm_p50"],
            r["grad_norm_p90"], r["grad_norm_p99"], r["param_absmax"],
            r["nonfinite"], r["trips"]))
    return "\n".join(out)


def wire_rows(dumps):
    """Pserver wire/compression rollup (ISSUE 10 satellite): per
    process dump, the outbound grad bytes before/after the negotiated
    codec (equal when compression is off), the codec's encode-time
    distribution, fastwire socket traffic, and the bounded-staleness
    barrier spread.  Works on any trace dump — the always-on metrics
    snapshot rides every one."""
    rows = []
    for d in dumps:
        m = d.get("metrics", {})

        def val(name, default=0):
            return (m.get(name) or {}).get("value", default)

        raw = val("wire_bytes_raw_total")
        comp = val("wire_bytes_compressed_total")
        ch = m.get("compress_ms", {})
        rows.append({
            "label": d.get("label", "?"),
            "grad_bytes_raw": raw,
            "grad_bytes_compressed": comp,
            "compression_ratio": round(raw / comp, 2) if comp else 1.0,
            "compress_ms_p50": round(ch.get("p50", 0.0), 3),
            "compress_ms_p99": round(ch.get("p99", 0.0), 3),
            "compress_count": ch.get("count", 0),
            "fastwire_tx": val("fastwire_bytes_sent_total"),
            "fastwire_rx": val("fastwire_bytes_recv_total"),
            "staleness_gap": val("pserver_staleness_gap"),
            "replays": val("rpc_round_replays_total"),
            "dedup_drops": val("pserver_dedup_drops_total"),
        })
    rows.sort(key=lambda r: r["label"])
    return rows


def format_wire_table(rows):
    out = ["%-24s %12s %12s %6s %9s %9s %12s %12s %6s" % (
        "process", "grad_raw_B", "grad_wire_B", "ratio", "czip_p50",
        "czip_p99", "fastwire_tx", "fastwire_rx", "stale")]
    for r in rows:
        out.append("%-24s %12d %12d %6.2f %9.3f %9.3f %12d %12d %6d"
                   % (r["label"][:24], r["grad_bytes_raw"],
                      r["grad_bytes_compressed"],
                      r["compression_ratio"], r["compress_ms_p50"],
                      r["compress_ms_p99"], r["fastwire_tx"],
                      r["fastwire_rx"], r["staleness_gap"]))
    return "\n".join(out)


def serve_rows(dumps):
    """Serving-tier rollup (ISSUE 11 satellite): per process dump, the
    request/token plane — predict batches and occupancy, and the
    generative decode loop's tokens/TTFT/inter-token distributions with
    the paged KV cache pressure (blocks used/total, allocation
    failures, preemptions).  Works on any trace dump — the always-on
    metrics snapshot rides every one."""
    rows = []
    for d in dumps:
        m = d.get("metrics", {})

        def val(name, default=0):
            return (m.get(name) or {}).get("value", default)

        def hist(name, field, default=0.0):
            return (m.get(name) or {}).get(field, default)

        slots = val("serve_decode_slots_total")
        pfx_tok = val("serve_prefix_tokens_total")
        pfx_cached = val("serve_prefix_tokens_cached_total")
        proposed = val("serve_spec_proposed_total")
        draft_us = val("serve_spec_draft_us_total")
        verify_us = val("serve_spec_verify_us_total")
        rows.append({
            "label": d.get("label", "?"),
            "requests": val("serve_requests_total"),
            "batches": val("serve_batches_total"),
            "gen_requests": val("serve_gen_requests_total"),
            "tokens": val("serve_tokens_total"),
            "prefills": val("serve_prefills_total"),
            "prefill_rides": val("serve_prefill_rides_total"),
            "decode_steps": val("serve_decode_steps_total"),
            "decode_occupancy_pct": round(
                100.0 * val("serve_decode_rows_total") / slots, 1)
            if slots else 0.0,
            "ttft_p50_ms": round(hist("serve_ttft_ms", "p50"), 3),
            "ttft_p99_ms": round(hist("serve_ttft_ms", "p99"), 3),
            "itl_p50_ms": round(hist("serve_itl_ms", "p50"), 3),
            "itl_p99_ms": round(hist("serve_itl_ms", "p99"), 3),
            "kv_blocks_used": val("serve_kv_blocks_used"),
            "kv_blocks_total": val("serve_kv_blocks_total"),
            "kv_alloc_failures": val("serve_kv_alloc_failures_total"),
            "preemptions": val("serve_kv_preemptions_total"),
            # prefix cache + speculative decode (ISSUE 19)
            "prefix_hit_rate_pct": round(100.0 * pfx_cached / pfx_tok,
                                         1) if pfx_tok else 0.0,
            "blocks_shared": val("serve_kv_blocks_shared"),
            "cow_copies": val("serve_kv_cow_copies_total"),
            "spec_accept_rate": round(
                val("serve_spec_accepted_total") / proposed, 3)
            if proposed else 0.0,
            "draft_overhead_pct": round(
                100.0 * draft_us / (draft_us + verify_us), 1)
            if draft_us + verify_us else 0.0,
        })
    rows.sort(key=lambda r: r["label"])
    return rows


def format_serve_table(rows):
    out = ["%-20s %7s %8s %8s %6s %9s %9s %8s %8s %9s %7s %8s "
           "%7s %6s %6s %7s" % (
               "process", "reqs", "tokens", "steps", "occ%", "ttft_p50",
               "ttft_p99", "itl_p50", "itl_p99", "kv_used", "allocF",
               "preempt", "pfxHit%", "shared", "accept", "draft%")]
    for r in rows:
        out.append("%-20s %7d %8d %8d %6.1f %9.3f %9.3f %8.3f %8.3f "
                   "%5d/%-3d %7d %8d %7.1f %6d %6.3f %7.1f" % (
                       r["label"][:20],
                       r["requests"] + r["gen_requests"], r["tokens"],
                       r["decode_steps"], r["decode_occupancy_pct"],
                       r["ttft_p50_ms"], r["ttft_p99_ms"],
                       r["itl_p50_ms"], r["itl_p99_ms"],
                       r["kv_blocks_used"], r["kv_blocks_total"],
                       r["kv_alloc_failures"], r["preemptions"],
                       r.get("prefix_hit_rate_pct", 0.0),
                       r.get("blocks_shared", 0),
                       r.get("spec_accept_rate", 0.0),
                       r.get("draft_overhead_pct", 0.0)))
    return "\n".join(out)


def scale_rows(dumps):
    """Scale-observatory rollup (ISSUE 12): per process dump, the
    resource-ledger gauges the collector mirrors into the always-on
    registry — pending-grad footprint, reply/replay cache bytes and
    their metered evictions, the live barrier set, apply backlog and
    oldest-pending age, hier fan-in buffers, fastwire socket
    population, and the quorum-bookkeeping work counter.  Works on any
    trace OR flight dump (the metrics snapshot rides both); flight
    dumps additionally carry the full ledger time series under their
    'ledger' key."""
    rows = []
    for d in dumps:
        m = d.get("metrics", {})

        def val(name, default=0):
            return (m.get(name) or {}).get("value", default)

        rows.append({
            "label": d.get("label", "?"),
            "pending_bytes": val("ledger_pserver_pending_grad_bytes"),
            "pending_entries": val(
                "ledger_pserver_pending_grad_entries"),
            "reply_cache_bytes": val(
                "ledger_pserver_reply_cache_bytes"),
            "reply_evictions": val(
                "pserver_reply_cache_evictions_total"),
            "replay_cache_bytes": val("ledger_rpc_replay_cache_bytes"),
            "replay_evictions": val("rpc_replay_cache_evictions_total"),
            "barrier_set": val("ledger_pserver_barrier_set"),
            "apply_backlog_rounds": val(
                "ledger_pserver_apply_backlog_rounds"),
            "oldest_pending_age_s": val(
                "ledger_pserver_oldest_pending_age_s"),
            "hier_fanin_bytes": val("ledger_hier_fanin_bytes"),
            "fastwire_conns": val("ledger_fastwire_server_conns"),
            "quorum_scan_ops": val("pserver_quorum_scan_ops_total"),
        })
    rows.sort(key=lambda r: r["label"])
    return rows


def format_scale_table(rows):
    out = ["%-22s %12s %8s %12s %7s %12s %7s %8s %8s %8s %10s" % (
        "process", "pending_B", "entries", "reply_B", "replyEv",
        "replay_B", "rplyEv", "barrier", "backlog", "oldest_s",
        "scan_ops")]
    for r in rows:
        out.append(
            "%-22s %12d %8d %12d %7d %12d %7d %8d %8d %8.2f %10d" % (
                r["label"][:22], r["pending_bytes"],
                r["pending_entries"], r["reply_cache_bytes"],
                r["reply_evictions"], r["replay_cache_bytes"],
                r["replay_evictions"], r["barrier_set"],
                r["apply_backlog_rounds"], r["oldest_pending_age_s"],
                r["quorum_scan_ops"]))
    return "\n".join(out)


def slo_rows(dumps):
    """Watchtower SLO rollup (ISSUE 13): per process dump, the
    per-spec burn-rate gauges the evaluator mirrors into the always-on
    registry (``slo_burn_fast_<name>`` / ``slo_burn_slow_<name>`` /
    ``slo_budget_remaining_<name>``) plus the alert counters.  Works
    on any trace OR flight dump — the metrics snapshot rides both;
    flight dumps written by a firing alert additionally carry the
    offending series under their top-level 'slo' key."""
    rows = []
    for d in dumps:
        m = d.get("metrics", {})

        def val(name, default=0):
            return (m.get(name) or {}).get("value", default)

        prefix = "slo_burn_fast_"
        names = sorted(k[len(prefix):] for k in m
                       if k.startswith(prefix))
        alerts = val("slo_alerts_total")
        active = val("slo_alerts_active")
        if not names:
            if alerts or active:
                rows.append({"label": d.get("label", "?"), "slo": "",
                             "burn_fast": 0.0, "burn_slow": 0.0,
                             "budget_remaining": 1.0,
                             "alerts_total": alerts,
                             "alerts_active": active})
            continue
        for n in names:
            rows.append({
                "label": d.get("label", "?"), "slo": n,
                "burn_fast": round(val("slo_burn_fast_" + n, 0.0), 4),
                "burn_slow": round(val("slo_burn_slow_" + n, 0.0), 4),
                "budget_remaining": round(
                    val("slo_budget_remaining_" + n, 1.0), 4),
                "alerts_total": alerts,
                "alerts_active": active,
            })
    rows.sort(key=lambda r: (r["label"], r["slo"]))
    return rows


def format_slo_table(rows):
    out = ["%-22s %-28s %10s %10s %10s %7s %7s" % (
        "process", "slo", "burn_fast", "burn_slow", "budget_rem",
        "alerts", "active")]
    for r in rows:
        out.append("%-22s %-28s %10.2f %10.2f %10.2f %7d %7d" % (
            r["label"][:22], r["slo"][:28], r["burn_fast"],
            r["burn_slow"], r["budget_remaining"], r["alerts_total"],
            r["alerts_active"]))
    return "\n".join(out)


def moe_rows(dumps):
    """MoE routing rollup (ISSUE 15 rider): per process dump, the
    capacity-factor stats the moe_ffn routing shard feeds the
    always-on registry — routed steps/tokens, per-expert load
    distribution (balance), dropped-token fraction and router entropy.
    Works on any trace or flight dump (the metrics snapshot rides
    both)."""
    rows = []
    for d in dumps:
        m = d.get("metrics", {})

        def val(name, default=0):
            return (m.get(name) or {}).get("value", default)

        def hist(name, field, default=0.0):
            return (m.get(name) or {}).get(field, default)

        steps = val("moe_router_steps_total")
        tokens = val("moe_tokens_total")
        if not steps and not tokens:
            continue
        rows.append({
            "label": d.get("label", "?"),
            "steps": steps,
            "tokens": tokens,
            "dropped_tokens": val("moe_dropped_tokens_total"),
            "dropped_frac": round(val("moe_dropped_token_frac", 0.0),
                                  4),
            "router_entropy": round(val("moe_router_entropy", 0.0), 4),
            "expert_load_p50": hist("moe_expert_load_tokens", "p50"),
            "expert_load_p99": hist("moe_expert_load_tokens", "p99"),
            "expert_load_mean": round(
                hist("moe_expert_load_tokens", "mean"), 2),
        })
    rows.sort(key=lambda r: r["label"])
    return rows


def format_moe_table(rows):
    out = ["%-22s %7s %9s %9s %9s %9s %9s %9s %9s" % (
        "process", "steps", "tokens", "dropped", "drop_frac",
        "entropy", "load_p50", "load_p99", "load_mean")]
    for r in rows:
        out.append("%-22s %7d %9d %9d %9.4f %9.4f %9.4g %9.4g %9.4g"
                   % (r["label"][:22], r["steps"], r["tokens"],
                      r["dropped_tokens"], r["dropped_frac"],
                      r["router_entropy"], r["expert_load_p50"],
                      r["expert_load_p99"], r["expert_load_mean"]))
    return "\n".join(out)


def weaver_rows(dumps):
    """Weaver schedule-exploration rollup (ISSUE 18 satellite): per
    process dump, how much of the interleaving space the explorer
    covered — schedules executed, sibling branches the sleep-set
    pruning skipped, failing schedules found, and the decision length
    of the last minimized repro.  tools/weaver.py leaves a dump when
    FLAGS_telemetry_dump_dir is set, so CI runs roll up here."""
    rows = []
    for d in dumps:
        m = d.get("metrics", {})

        def val(name, default=0):
            return (m.get(name) or {}).get("value", default)

        explored = val("weaver_schedules_explored_total")
        pruned = val("weaver_schedules_pruned_total")
        if not explored and not pruned:
            continue
        rows.append({
            "label": d.get("label", "?"),
            "explored": explored,
            "pruned": pruned,
            "pruned_pct": round(
                100.0 * pruned / (explored + pruned), 1)
            if (explored + pruned) else 0.0,
            "failures": val("weaver_failures_total"),
            "minimized_len": val("weaver_minimized_trace_len"),
        })
    rows.sort(key=lambda r: r["label"])
    return rows


def format_weaver_table(rows):
    out = ["%-24s %9s %9s %8s %9s %8s" % (
        "process", "explored", "pruned", "pruned%", "failures",
        "min_len")]
    for r in rows:
        out.append("%-24s %9d %9d %8.1f %9d %8d" % (
            r["label"][:24], r["explored"], r["pruned"],
            r["pruned_pct"], r["failures"], r["minimized_len"]))
    return "\n".join(out)


def format_phase_table(rows, top=0):
    out = ["%-32s %7s %10s %9s %9s %9s %7s" % (
        "phase", "count", "total_ms", "mean_ms", "p50_ms", "p99_ms",
        "share")]
    for r in (rows[:top] if top else rows):
        out.append("%-32s %7d %10.3f %9.3f %9.3f %9.3f %6.1f%%" % (
            r["name"][:32], r["count"], r["total_ms"], r["mean_ms"],
            r["p50_ms"], r["p99_ms"], 100.0 * r["share"]))
    return "\n".join(out)


# ---------------------------------------------------------------------------
# Device idle gaps put down to the program's spans (one profiler capture)
# ---------------------------------------------------------------------------

PROGRAM_SPANS = ("serve.", "step.", "executor.")
# the TPU runtime's own host events that tie the two clocks of a
# capture together: it hands a program to the device / learns that one
# has ended
LAUNCHED = "DoEnqueueProgram"
SEEN_DONE = "tpu::System::Execute=>Done"


def load_profile(path):
    """``jax.profiler.ProfileData`` of a capture: an ``.xplane.pb``
    file, or the newest one under a ``jax.profiler.start_trace`` dir."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError("no .xplane.pb under %s" % path)
        path = found[-1]
    return ProfileData.from_file(path)


def _innermost(spans):
    """Properly nested ``(start, end, name)`` spans of one thread ->
    disjoint ``(start, end, name)`` in time order, every instant owned
    by the deepest span over it."""
    out, stack = [], []     # stack of [end, name]; t: owned up to here
    t = 0

    def close(upto):
        nonlocal t
        while stack and stack[-1][0] <= upto:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for a, b, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        close(a)
        if stack and a > t:
            out.append((t, a, stack[-1][1]))
        t = max(t, a)
        stack.append([b, name])
    close(float("inf"))
    return out


def _nearest_diffs(marks, times):
    """For each of ``times`` the signed distance (mark - time) to the
    nearest of the sorted ``marks``, kept where it lies within a
    millisecond of the median one: a run paired with another run's
    mark (a pipeline that launches far ahead, the capture's edges)
    falls out, and with fewer than half left the marks say nothing."""
    if not marks or not times:
        return []
    diffs = []
    for t in times:
        i = bisect.bisect_left(marks, t)
        diffs.append(min((marks[j] - t for j in (i - 1, i)
                          if 0 <= j < len(marks)), key=abs))
    mid = sorted(diffs)[len(diffs) // 2]
    near = [d for d in diffs if abs(d - mid) < 1e6]
    return near if 2 * len(near) >= len(diffs) else []


def clock_skew(profile):
    """``(lo, hi)`` ns by which a capture's device planes run AHEAD of
    its host planes (either bound None where nothing shows it).  The
    profiler converts the device's clock to the host's with an error of
    a millisecond or two (v5e: 1.1 and 1.5-1.8 ms in two captures,
    steady inside each), which is the size of the gaps to be explained.
    Causality bounds it: a run of an executable (the device's ``XLA
    Modules`` line) starts after the runtime enqueued it (``LAUNCHED``
    ends; skew >= end - start) and the runtime sees it done
    (``SEEN_DONE``) after it ended (skew <= seen - end)."""
    runs, launched, seen = [], [], []
    for plane in profile.planes:
        for line in plane.lines:
            if plane.name.startswith("/device:"):
                if line.name == "XLA Modules":
                    runs.extend((e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events)
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name == LAUNCHED:
                        launched.append(e.start_ns + e.duration_ns)
                    elif e.name == SEEN_DONE:
                        seen.append(e.start_ns)
    lo = _nearest_diffs(sorted(launched), [a for a, _ in runs])
    hi = _nearest_diffs(sorted(seen), [b for _, b in runs])
    lo, hi = (max(lo) if lo else None), (min(hi) if hi else None)
    if lo is not None and hi is not None and lo > hi:
        # a loop that launches far ahead of the device (training keeps
        # 24 steps in flight) pairs a run with another run's launch,
        # steadily; the end of a run is seen promptly in every regime
        lo = None
    return lo, hi


def gap_rows(profile, prefixes=PROGRAM_SPANS):
    """From one capture (``jax.profiler.ProfileData``): the union of
    each device's op intervals, and every idle gap between them put
    down to the deepest program span — a host event whose name starts
    with one of ``prefixes``, on the scheduler's or executor's thread —
    that covers more of it than any other (or than no span at all:
    ``unspanned``).  The device's times are first moved onto the host
    planes' clock by ``clock_skew`` (the middle of its bounds, or the
    one it has; the ``(window)`` row carries ``skew_ns`` and the shift
    applied).  The window is the extent of the device's ops.
    Returns ``[{span, gaps, idle_s, share, under_s}]`` — ``share`` of
    the window; ``under_s`` the idle seconds that fall inside the span
    itself, whichever span its gap went to — most idle first and
    ``unspanned`` last, after a ``(window)`` row holding the window's
    seconds, all gaps, all idle seconds and the idle share."""
    prefixes = tuple(prefixes)
    skew = clock_skew(profile)
    known = [x for x in skew if x is not None]
    shift = sum(known) // len(known) if known else 0
    owned = []          # disjoint (start, end, name) over all threads
    devices = []        # per device plane: sorted (start, end) of ops
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events
                         if e.name.startswith(prefixes)]
                owned.extend(_innermost(spans))
        elif plane.name.startswith("/device:"):
            ops = sorted((e.start_ns + shift,
                          e.start_ns + e.duration_ns + shift)
                         for line in plane.lines if line.name == "XLA Ops"
                         for e in line.events)
            if ops:
                devices.append(ops)
    owned.sort()
    starts = [o[0] for o in owned]
    tally = {}          # name -> [gaps, idle ns of them, ns under it]
    window = idle = n_gaps = 0
    for ops in devices:
        window += max(b for _, b in ops) - ops[0][0]
        busy_to = ops[0][0]
        for a, b in ops:
            if a > busy_to:
                gap = a - busy_to
                cover = {"unspanned": gap}
                i = max(0, bisect.bisect_right(starts, busy_to) - 1)
                while i < len(owned) and owned[i][0] < a:
                    ov = min(a, owned[i][1]) - max(busy_to, owned[i][0])
                    if ov > 0:
                        cover[owned[i][2]] = cover.get(owned[i][2], 0) + ov
                        cover["unspanned"] -= ov
                    i += 1
                for name, ov in cover.items():
                    tally.setdefault(name, [0, 0, 0])[2] += ov
                row = tally[max(cover, key=cover.get)]
                row[0] += 1
                row[1] += gap
                n_gaps += 1
                idle += gap
            busy_to = max(busy_to, b)
    window = window or 1
    rows = [{"span": name, "gaps": n, "idle_s": ns / 1e9,
             "share": ns / window, "under_s": under / 1e9}
            for name, (n, ns, under) in tally.items() if n or under]
    rows.sort(key=lambda r: (r["span"] == "unspanned", -r["idle_s"]))
    return [{"span": "(window)", "gaps": n_gaps, "idle_s": idle / 1e9,
             "share": idle / window, "under_s": idle / 1e9,
             "window_s": window / 1e9, "skew_ns": skew,
             "shift_ns": shift}] + rows


def format_gap_table(rows):
    out = []
    if rows and rows[0]["span"] == "(window)":
        out.append("window %.3f s, device idle %.3f s (%.2f%%) in %d "
                   "gaps" % (rows[0]["window_s"], rows[0]["idle_s"],
                             100.0 * rows[0]["share"], rows[0]["gaps"]))
        lo, hi = rows[0]["skew_ns"]
        out.append("device clock ahead of the host planes' by %s to %s "
                   "ms: device times moved %.3f ms" % (
                       "?" if lo is None else "%.3f" % (lo / 1e6),
                       "?" if hi is None else "%.3f" % (hi / 1e6),
                       rows[0]["shift_ns"] / 1e6))
        rows = rows[1:]
    out.append("%-28s %9s %10s %16s %10s" % (
        "span", "gaps", "idle_s", "share of window", "under_s"))
    for r in rows:
        out.append("%-28s %9d %10.4f %15.2f%% %10.4f" % (
            r["span"][:28], r["gaps"], r["idle_s"], 100.0 * r["share"],
            r["under_s"]))
    return "\n".join(out)
