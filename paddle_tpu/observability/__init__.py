"""Unified telemetry layer (ISSUE 6): step-scoped tracing, always-on
metrics, chrome-trace export with distributed round correlation, and a
hang flight recorder.

  trace      span API + process tracer (live under FLAGS_telemetry or
             a jax profiler session, whose capture then holds the
             spans; the dead hot path is one check a step)
  metrics    counters/gauges/histograms, always on; Prometheus text +
             JSON snapshot exports
  export     merge per-process dumps into one chrome://tracing JSON;
             per-phase breakdown rows; a profiler capture's device
             idle gaps put down to the program's spans (gap_rows)
  flight     dump the ring + open spans + metrics + resource ledgers
             on watchdog timeout, wall-budget expiry, injected
             faults, SIGTERM/SIGALRM
  ledger     (ISSUE 12) per-subsystem resource ledgers: pserver
             pending grads / reply cache / barrier quorum / apply
             backlog, client replay cache, hier fan-in buffers,
             fastwire sockets — incremental byte/entry counters
             sampled by a low-rate collector into ledger_* gauges +
             a bounded time-series ring; FLAGS_ledger_watch turns a
             crossed threshold into a flight dump (collapse
             forensics for tools/scale_bench.py)
  numerics   (ISSUE 8) on-device tensor-health guards: fused per-step
             health reduction over watched tensors, four-mode
             escalation (FLAGS_check_numerics =
             off|metrics|guard|bisect), numerics_*.json forensics
             incl. first-bad-op bisection; imported lazily by its
             consumers (executor, rpc, trainer)
  tsdb       (ISSUE 13) Watchtower time-series store: a background
             sampler appends every counter/gauge/histogram-percentile
             (and the refreshed ledger) to size-bounded append-only
             binary segments under FLAGS_tsdb_dir, with range-scan /
             downsample / rate() queries and byte-bounded retention —
             the durable history slo.py, tools/watchtower.py and
             tools/perf_sentinel.py read
  slo        (ISSUE 13) declarative SLOs (FLAGS_slo_spec: JSON/TOML
             file or inline objectives) evaluated continuously
             against the tsdb with multi-window burn-rate alerting:
             a firing (slo, window) bumps slo_alerts_total, writes
             ONE flight dump embedding the offending series, and is
             visible in BarrierStatus introspection; both imported
             lazily by their consumers

Instrumented sites: core/executor_impl (step/feed/dispatch/sync spans,
compile-cache + step counters), distributed/rpc (send/gather/barrier/
apply spans carrying the (round, sender, seq) wire identity as a
correlation id, dedup/replay counters), distributed/fastwire (wire
byte counters), kernels (Pallas launch-site spans), fluid/trainer and
fluid/profiler (RecordEvent is now a telemetry span).

See README "Observability" and tools/trace_report.py.
"""
from . import metrics  # noqa: F401
from . import trace  # noqa: F401
from .trace import TRACER, round_cid  # noqa: F401

__all__ = ["trace", "metrics", "TRACER", "round_cid"]
