"""Unified telemetry layer (ISSUE 6): span nesting + ring eviction,
disabled-path no-op (zero allocations), Prometheus/JSON metric exports,
executor step spans, cross-process (round, sender, seq) correlation on
a 2-trainer x 2-pserver localhost run, flight-recorder dumps on
injected WatchdogTimeout, the profiler rebase, and the < 2% hot-path
overhead gate."""
import glob
import json
import multiprocessing as mp
import os
import socket
import sys
import time

import numpy as np
import pytest

from paddle_tpu.core.flags import FLAGS
from paddle_tpu.observability import export, metrics, trace
from paddle_tpu.observability.trace import TRACER

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------- spans

def test_span_nesting_and_ring_eviction():
    tr = trace.Tracer(ring_size=4, enabled=True)
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    done = tr.completed()
    assert [s["name"] for s in done] == ["inner", "outer"]
    by = {s["name"]: s for s in done}
    assert by["outer"]["depth"] == 0
    assert by["inner"]["depth"] == 1
    assert by["inner"]["ts_us"] >= by["outer"]["ts_us"]
    # ring eviction: only the newest ring_size spans survive
    for i in range(10):
        tr.end(tr.begin("s%d" % i))
    names = [s["name"] for s in tr.completed()]
    assert names == ["s6", "s7", "s8", "s9"]
    # limit= slices BEFORE dict conversion (the flight recorder's
    # signal-handler bound) and keeps the newest
    assert [s["name"] for s in tr.completed(limit=2)] == ["s8", "s9"]
    assert [s["name"] for s in tr.completed(limit=99)] == names


def test_open_spans_visible_and_unbalanced_end():
    tr = trace.Tracer(ring_size=16, enabled=True)
    outer = tr.begin("blocked.here", cid="round:7")
    tr.begin("child")   # never ended — an exception unwound past it
    open_ = tr.open_spans()
    assert {s["name"] for s in open_} == {"blocked.here", "child"}
    assert any(s.get("cid") == "round:7" for s in open_)
    tr.end(outer)       # pops back through the orphaned child
    assert tr.open_spans() == []
    assert tr.completed()[-1]["name"] == "blocked.here"


def test_disabled_path_is_noop_and_allocation_free():
    assert not TRACER.on
    # warm: the probe's counter object and code paths exist already
    trace.disabled_step_probe(2000)
    before = sys.getallocatedblocks()
    trace.disabled_step_probe(20000)
    after = sys.getallocatedblocks()
    # counted-steps microbench: the disabled path must not allocate
    # (small tolerance for interpreter-internal churn)
    assert abs(after - before) < 32, (before, after)
    assert TRACER.completed() is not None  # and recorded no spans for it


def test_runtime_flag_flip_reaches_tracer():
    """`FLAGS.telemetry = True` set programmatically (not just env at
    import) must actually enable tracing — and the ring resizes when
    FLAGS_telemetry_ring_size is assigned."""
    assert not TRACER.on
    old_ring = int(FLAGS.telemetry_ring_size)
    try:
        FLAGS.telemetry = True
        assert TRACER.on
        TRACER.end(TRACER.begin("flag.flip"))
        assert any(s["name"] == "flag.flip" for s in TRACER.completed())
        FLAGS.telemetry_ring_size = 8
        assert TRACER._ring.maxlen == 8
    finally:
        FLAGS.telemetry = False
        FLAGS.telemetry_ring_size = old_ring
    assert not TRACER.on
    assert TRACER._ring.maxlen == old_ring


def test_flight_dump_from_signal_mid_observe(tmp_path):
    """A signal landing on the thread that is INSIDE Histogram.observe
    (lock held) must still produce a dump, not deadlock — the metric
    locks are reentrant for exactly this."""
    import signal

    h = metrics.histogram("t_unit_sig_ms")
    from paddle_tpu.observability import flight

    got = {}

    def handler(signum, frame):
        got["path"] = flight.dump("signal:test",
                                  directory=str(tmp_path))

    prev = signal.signal(signal.SIGALRM, handler)
    try:
        with h._lock:           # simulate: interrupted mid-observe
            signal.raise_signal(signal.SIGALRM)
        assert got["path"] and os.path.exists(got["path"])
    finally:
        signal.signal(signal.SIGALRM, prev)


def test_span_decorator_and_correlation_id():
    calls = []

    @trace.traced("deco.site", lambda x: {"x": x})
    def fn(x):
        calls.append(x)
        return x * 2

    assert fn(3) == 6          # disabled: pure passthrough
    TRACER.clear()
    TRACER.enable()
    try:
        assert fn(4) == 8
    finally:
        TRACER.disable()
    spans = TRACER.completed()
    assert any(s["name"] == "deco.site" and s["args"] == {"x": 4}
               for s in spans)
    assert trace.round_cid(12) == "round:12"


# -------------------------------------------------------------- metrics

def test_metrics_prometheus_and_json_export():
    c = metrics.counter("t_unit_requests_total", "unit-test counter")
    c.zero()
    c.inc()
    c.inc(2)
    g = metrics.gauge("t_unit_depth", "unit-test gauge")
    g.set(1.5)
    h = metrics.histogram("t_unit_lat_ms", "unit-test histogram",
                          bounds=(1.0, 10.0, 100.0))
    h.zero()
    for v in (0.5, 2.0, 2.0, 50.0, 200.0):
        h.observe(v)

    text = metrics.prometheus_text()
    assert "# TYPE t_unit_requests_total counter" in text
    assert "t_unit_requests_total 3" in text
    assert "# TYPE t_unit_depth gauge" in text
    assert "t_unit_depth 1.5" in text
    assert "# TYPE t_unit_lat_ms histogram" in text
    # cumulative buckets: le=1 -> 1, le=10 -> 3, le=100 -> 4, +Inf -> 5
    assert 't_unit_lat_ms_bucket{le="1"} 1' in text
    assert 't_unit_lat_ms_bucket{le="10"} 3' in text
    assert 't_unit_lat_ms_bucket{le="100"} 4' in text
    assert 't_unit_lat_ms_bucket{le="+Inf"} 5' in text
    assert "t_unit_lat_ms_count 5" in text

    # full precision for large counters: '%g'-style 6-significant-digit
    # rounding would freeze a byte counter between scrapes
    big = metrics.counter("t_unit_bytes_total")
    big.zero()
    big.inc(123456789)
    assert "t_unit_bytes_total 123456789" in metrics.prometheus_text()

    snap = metrics.snapshot()
    assert snap["t_unit_requests_total"]["value"] == 3
    assert snap["t_unit_lat_ms"]["count"] == 5
    assert snap["t_unit_lat_ms"]["p50"] == 2.0
    assert snap["t_unit_lat_ms"]["p99"] == 200.0
    assert h.percentile(50) == 2.0
    # same name re-registration returns the same object; kind clash dies
    assert metrics.counter("t_unit_requests_total") is c
    with pytest.raises(TypeError):
        metrics.gauge("t_unit_requests_total")


# ---------------------------------------------------- executor coverage

def test_executor_step_spans_and_counters():
    import paddle_tpu.fluid as fluid

    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            loss = fluid.layers.mean(fluid.layers.fc(x, size=4))
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {"x": np.ones((2, 8), np.float32)}
        steps0 = metrics.counter("executor_steps_total").value
        h = metrics.histogram("step_wall_ms")
        hn0 = h.count
        TRACER.clear()
        TRACER.enable()
        try:
            exe.run(main, feed=feed, fetch_list=[loss])
            prep = exe.prepare(main, feed_specs=feed,
                               fetch_list=[loss])
            for _ in range(2):
                prep.run_prepared(feed)
            prep.sync_scope()
        finally:
            TRACER.disable()
    names = {s["name"] for s in TRACER.completed()}
    assert {"executor.run", "executor.dispatch", "step.prepared",
            "step.feed", "step.dispatch",
            "step.sync_scope"} <= names
    assert metrics.counter("executor_steps_total").value >= steps0 + 3
    assert h.count >= hn0 + 3  # run + 2 prepared steps observed


def test_sub_block_runs_are_not_steps(monkeypatch):
    # a pserver's listen_and_serv applies each shard's optimize block
    # via ExecutorCore.run(block_id=N) — those must not land in the
    # step counter / step_wall_ms histogram (they'd report shard-apply
    # time as the process's step stats)
    from paddle_tpu.core.executor_impl import ExecutorCore
    import paddle_tpu.fluid as fluid

    monkeypatch.setattr(ExecutorCore, "_run_impl",
                        lambda self, *a, **kw: [])
    core = fluid.Executor(fluid.CPUPlace())._core
    desc = fluid.Program().desc
    steps = metrics.counter("executor_steps_total")
    h = metrics.histogram("step_wall_ms")
    for enabled in (False, True):
        (TRACER.enable if enabled else TRACER.disable)()
        try:
            s0, h0 = steps.value, h.count
            core.run(desc, None, block_id=3)
            assert (steps.value, h.count) == (s0, h0)
            core.run(desc, None, block_id=0)
            assert steps.value == s0 + 1
            assert h.count == (h0 + 1 if enabled else h0)
        finally:
            TRACER.disable()


# ------------------------------------------------------- export + tools

def _make_dump(tmp_path, label, spans, pid):
    path = tmp_path / ("trace_%s_%d.json" % (label, pid))
    path.write_text(json.dumps({
        "label": label, "pid": pid, "spans": spans, "open_spans": [],
        "metrics": {}}))
    return str(path)


def test_export_merge_and_phase_report(tmp_path, capsys):
    t0 = 1000.0
    d1 = _make_dump(tmp_path, "trainer0", [
        {"name": "rpc.send_vars", "ts_us": t0, "dur_us": 500.0,
         "tid": 1, "cid": "round:0"},
        {"name": "step.dispatch", "ts_us": t0 + 600, "dur_us": 100.0,
         "tid": 1},
    ], pid=11)
    d2 = _make_dump(tmp_path, "pserver", [
        {"name": "pserver.apply_round", "ts_us": t0 + 200,
         "dur_us": 300.0, "tid": 2, "cid": "round:0"},
    ], pid=22)
    out = str(tmp_path / "merged.json")
    trace_dict, dumps = export.merge_files([d1, d2], out_path=out)
    assert os.path.exists(out)
    evs = [e for e in trace_dict["traceEvents"] if e.get("ph") == "X"]
    with_cid = [e for e in evs
                if (e.get("args") or {}).get("cid") == "round:0"]
    assert {e["pid"] for e in with_cid} == {11, 22}
    # process names carried through
    names = {e["args"]["name"] for e in trace_dict["traceEvents"]
             if e.get("ph") == "M"}
    assert {"trainer0", "pserver"} <= names
    rows = export.phase_rows(dumps)
    assert rows[0]["name"] == "rpc.send_vars"  # largest total first
    assert rows[0]["total_ms"] == 0.5

    # the CLI prints the per-phase table and writes a merge
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    rc = trace_report.main([d1, d2, "--merge",
                            str(tmp_path / "m2.json")])
    assert rc == 0
    printed = capsys.readouterr().out
    assert "rpc.send_vars" in printed and "pserver.apply_round" in printed
    assert "total_ms" in printed
    assert os.path.exists(tmp_path / "m2.json")


def test_kernel_rollup_groups_launch_sites_and_device_ops(tmp_path,
                                                          capsys):
    """ISSUE 7 satellite: the per-kernel rollup groups pallas.*
    launch-site spans by kernel name and xplane device events by their
    normalized op family, and the trace_report CLI prints it."""
    d1 = _make_dump(tmp_path, "trainer0", [
        {"name": "pallas.matmul_fused", "ts_us": 0.0, "dur_us": 1000.0,
         "tid": 1},
        {"name": "pallas.matmul_fused", "ts_us": 5.0, "dur_us": 3000.0,
         "tid": 1},
        {"name": "pallas.flash_attention", "ts_us": 9.0,
         "dur_us": 500.0, "tid": 1},
        {"name": "step.dispatch", "ts_us": 20.0, "dur_us": 400.0,
         "tid": 1},
    ], pid=31)
    dumps = [export.load_dump(d1)]
    trace = {"traceEvents": [
        {"name": "%fusion.123", "cat": "device", "ph": "X", "ts": 0,
         "dur": 2000},
        {"name": "%fusion.7", "cat": "device", "ph": "X", "ts": 1,
         "dur": 1000},
        {"name": "jit__matmul_kernel.3", "cat": "device", "ph": "X",
         "ts": 2, "dur": 500},
    ]}
    rows = export.kernel_rows(dumps, trace)
    by = {(r["kernel"], r["side"]): r for r in rows}
    assert by[("matmul_fused", "host")]["count"] == 2
    assert by[("matmul_fused", "host")]["total_ms"] == 4.0
    assert by[("flash_attention", "host")]["count"] == 1
    assert by[("fusion", "device")]["count"] == 2
    assert by[("fusion", "device")]["total_ms"] == 3.0
    assert by[("jit__matmul_kernel", "device")]["count"] == 1
    # non-pallas host spans stay out of the kernel rollup
    assert ("step.dispatch", "host") not in by
    # CLI prints the rollup table whenever kernel rows exist
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    rc = trace_report.main([d1])
    assert rc == 0
    printed = capsys.readouterr().out
    assert "per-kernel rollup" in printed
    assert "matmul_fused" in printed


# ------------------------------------------- cross-process correlation

def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_cross_process_round_correlation(tmp_path):
    """2 trainers x 2 pservers on localhost with FLAGS_telemetry on:
    every process dumps its trace, and the merged timeline correlates
    trainer send/barrier/get spans with the pserver scatter/apply spans
    of the same round via the shared cid (acceptance criterion)."""
    import dist_train_helpers as H

    os.environ["JAX_PLATFORMS"] = "cpu"
    env = {"FLAGS_telemetry": "1",
           "FLAGS_telemetry_dump_dir": str(tmp_path)}
    ctx = mp.get_context("spawn")
    eps = ["127.0.0.1:%d" % _free_port() for _ in range(2)]
    pservers = ",".join(eps)
    steps = 3

    ps_procs = [ctx.Process(target=H.run_pserver,
                            args=(ep, pservers, 2, "softmax", True, env))
                for ep in eps]
    for p in ps_procs:
        p.start()
    q = ctx.Queue()
    tr_procs = [ctx.Process(target=H.run_trainer,
                            args=(tid, pservers, 2, steps, q, "softmax",
                                  True, env))
                for tid in range(2)]
    for p in tr_procs:
        p.start()
    for _ in range(2):
        q.get(timeout=240)
    for p in tr_procs + ps_procs:
        p.join(timeout=60)
        if p.is_alive():
            p.terminate()
            pytest.fail("worker did not exit")

    dump_paths = sorted(glob.glob(str(tmp_path / "trace_*.json")))
    assert len(dump_paths) == 4, dump_paths
    dumps = [export.load_dump(p) for p in dump_paths]
    trainer_dumps = [d for d in dumps if d["label"].startswith("trainer")]
    pserver_dumps = [d for d in dumps if d["label"].startswith("pserver")]
    assert len(trainer_dumps) == 2 and len(pserver_dumps) == 2

    def cids(dump, prefix):
        return {s["cid"] for s in dump["spans"]
                if s.get("cid") and s["name"].startswith(prefix)}

    # acceptance: trainer send/get spans and pserver apply spans of the
    # same round share a correlation id, across every process pair
    for td in trainer_dumps:
        send_cids = cids(td, "rpc.send_vars")
        get_cids = cids(td, "rpc.get_vars")
        assert trace.round_cid(0) in send_cids
        assert send_cids & get_cids, (send_cids, get_cids)
        for pd in pserver_dumps:
            apply_cids = cids(pd, "pserver.apply_round")
            scatter_cids = cids(pd, "pserver.scatter")
            assert send_cids & apply_cids, (td["label"], pd["label"])
            assert send_cids & scatter_cids
    # pserver rounds metric rode the dump
    for pd in pserver_dumps:
        applied = pd["metrics"]["pserver_rounds_applied_total"]["value"]
        assert applied >= steps
    # and the merge produces ONE chrome trace whose correlated events
    # span trainer and pserver pids
    merged, _ = export.merge_files(dump_paths,
                                   out_path=str(tmp_path / "merged.json"))
    cid0 = trace.round_cid(0)
    pids = {e["pid"] for e in merged["traceEvents"]
            if (e.get("args") or {}).get("cid") == cid0}
    assert len(pids) >= 3  # 2 trainers + at least one pserver


# ------------------------------------------------------ flight recorder

def test_flight_recorder_on_injected_watchdog(tmp_path):
    from paddle_tpu.distributed.resilience import (WatchdogTimeout,
                                                   watchdog_error)

    old = FLAGS.telemetry_dump_dir
    FLAGS.telemetry_dump_dir = str(tmp_path)
    try:
        TRACER.enable()
        blocked_span = TRACER.begin("op.recv", cid="round:5")
        err = watchdog_error(
            "recv", ["127.0.0.1:6174"],
            lambda ep: {"applied_round": 4, "barriers": 1, "alive": 2,
                        "known": ["trainer0", "trainer1"],
                        "waiting_for": ["trainer1"]})
        TRACER.end(blocked_span)
    finally:
        TRACER.disable()
        FLAGS.telemetry_dump_dir = old
    assert isinstance(err, WatchdogTimeout)
    # the dump path is attached to the raised error message
    assert "flight recorder:" in str(err)
    assert err.flight_path and os.path.exists(err.flight_path)
    rec = json.loads(open(err.flight_path).read())
    assert rec["reason"] == "watchdog:recv"
    # names the blocked op and the missing peer
    assert rec["blocked"]["op"] == "recv"
    assert "trainer1" in json.dumps(rec["blocked"]["details"])
    # and the open span the process was blocked in
    assert any(s["name"] == "op.recv" and s.get("cid") == "round:5"
               for s in rec["open_spans"])
    assert "executor_steps_total" in rec["metrics"]


def test_flight_recorder_on_injected_fault(tmp_path):
    from paddle_tpu.distributed import resilience

    old = FLAGS.telemetry_dump_dir
    FLAGS.telemetry_dump_dir = str(tmp_path)
    try:
        inj = resilience.install_faults("t_point:drop:1.0:1")
        with pytest.raises(resilience.InjectedFault):
            resilience.fault_point("t_point")
        assert inj.stats["t_point"] == 1
    finally:
        FLAGS.telemetry_dump_dir = old
        resilience.install_faults("")
    dumps = glob.glob(str(tmp_path / "flight_*.json"))
    assert dumps, "injected fault left no flight artifact"
    rec = json.loads(open(dumps[0]).read())
    assert rec["reason"] == "fault:t_point"


# ------------------------------------------------------ profiler rebase

def test_profiler_api_backed_by_telemetry(tmp_path, capsys):
    from paddle_tpu.fluid import profiler

    path = str(tmp_path / "prof")
    was_on = TRACER.on
    with profiler.profiler(state="CPU", sorted_key="total",
                           profile_path=path):
        with profiler.RecordEvent("my_event"):
            time.sleep(0.002)
        with profiler.RecordEvent("my_event"):
            pass
    assert TRACER.on == was_on  # session restored the tracer state
    out = capsys.readouterr().out
    assert "my_event" in out and "Calls" in out
    data = json.loads(open(path).read())
    evs = [e for e in data["traceEvents"] if e["name"] == "my_event"]
    assert len(evs) == 2
    assert evs[0]["dur"] > 0


def test_profiler_events_are_bounded():
    """The old module-level events list grew without bound; events now
    live in the tracer ring (FLAGS_telemetry_ring_size)."""
    from paddle_tpu.fluid import profiler

    ring = int(FLAGS.telemetry_ring_size)
    profiler.start_profiler("CPU")
    try:
        for i in range(ring + 100):
            with profiler.RecordEvent("bounded"):
                pass
        assert len(TRACER.completed()) <= ring
    finally:
        profiler.stop_profiler(profile_path=None)


# --------------------------------------------------------- overhead gate

sys.path.insert(0, os.path.join(REPO, "tools"))
try:
    import telemetry_overhead
finally:
    sys.path.pop(0)


@pytest.fixture(scope="module")
def overhead_gates():
    """tools/telemetry_overhead.py's gates, measured once, in-process."""
    os.environ.setdefault("TELEMETRY_OVERHEAD_STEPS", "150")
    assert not TRACER.on
    return telemetry_overhead.measure()["gates"]


@pytest.mark.parametrize("gate", telemetry_overhead.GATES)
def test_instrumented_disabled_hot_path_under_two_percent(overhead_gates,
                                                          gate):
    """CI satellite: one case for each gate of the tool, so a failure
    names the gate (every limit is the tool's own: 2%)."""
    assert set(overhead_gates) == set(telemetry_overhead.GATES)
    g = overhead_gates[gate]
    assert g["ok"], "%s: %.5f of the step, limit %.3f" % (
        gate, g["frac"], g["limit"])
