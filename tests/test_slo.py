"""Watchtower SLO engine (ISSUE 13): spec parsing, burn-rate math,
alert lifecycle, per-tenant serving tagging, the fault drill the
fault_matrix 'slo' preset runs, the <2% sampler/evaluator overhead
gate, and the e2e acceptance run (serving + 2x2 pserver workload with
the tsdb sampler + SLO evaluator armed in every process)."""
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
from paddle_tpu.observability import flight
from paddle_tpu.observability import metrics as obs_metrics
from paddle_tpu.observability import slo, tsdb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


def _tool(name):
    sys.path.insert(0, TOOLS)
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(autouse=True)
def _clean():
    slo.reset()
    yield
    slo.reset()
    tsdb.stop_sampler()


# ------------------------------------------------------------- parsing

def test_parse_objective_and_inline_specs():
    assert slo.parse_objective("serve_request_ms.p99 <= 10") == (
        "serve_request_ms.p99", "<=", 10.0)
    specs = slo.load_specs(
        "serve_request_ms.p99<=10,"
        "pserver_rounds_applied_total.rate>=1.5,"
        "numerics_nonfinite_total==0")
    assert [s.metric for s in specs] == [
        "serve_request_ms.p99", "pserver_rounds_applied_total.rate",
        "numerics_nonfinite_total"]
    assert specs[0].op == "<=" and specs[0].threshold == 10.0
    assert specs[1].op == ">=" and specs[1].threshold == 1.5
    # defaults ride along
    assert specs[0].budget == slo.DEFAULT_BUDGET
    assert specs[0].fast_s == slo.DEFAULT_FAST_S
    with pytest.raises(ValueError):
        slo.parse_objective("metric ~ 5")
    with pytest.raises(ValueError):
        slo.load_specs("a<=1,a<=2")       # duplicate names
    assert slo.load_specs("") == []


def test_load_specs_json_and_toml(tmp_path):
    spec = {"slo": [
        {"name": "p99", "objective": "serve_request_ms.p99 <= 10",
         "budget": 0.02, "fast_s": 60, "slow_s": 600,
         "burn_fast": 10.0, "burn_slow": 1.5},
        "numerics_nonfinite_total == 0",
    ]}
    jpath = str(tmp_path / "slo.json")
    with open(jpath, "w") as f:
        json.dump(spec, f)
    specs = slo.load_specs(jpath)
    assert specs[0].name == "p99" and specs[0].budget == 0.02
    assert specs[0].fast_s == 60 and specs[0].burn_fast == 10.0
    assert specs[1].metric == "numerics_nonfinite_total"

    tpath = str(tmp_path / "slo.toml")
    with open(tpath, "w") as f:
        f.write('[[slo]]\nname = "p99"\n'
                'objective = "serve_request_ms.p99 <= 10"\n'
                'budget = 0.02\n'
                '[[slo]]\n'
                'objective = "numerics_nonfinite_total == 0"\n')
    specs2 = slo.load_specs(tpath)
    assert specs2[0].name == "p99" and specs2[0].budget == 0.02
    assert specs2[1].op == "=="
    with pytest.raises(ValueError):
        slo.SLO("m", "<=", 1, budget=0.0)   # bad budget
    with pytest.raises(ValueError):
        slo.SLO("m", "~", 1)                # bad op
    # a typo'd spec-file path must raise, never silently re-parse as
    # inline objectives (that would disable monitoring undiagnosed)
    with pytest.raises(FileNotFoundError):
        slo.load_specs(str(tmp_path / "nope.json"))
    with pytest.raises(FileNotFoundError):
        slo.load_specs(str(tmp_path / "nope.toml"))


# ------------------------------------------------------ burn-rate math

def _mk_store(tmp_path, values, name="m", now=None, step=1.0):
    store = tsdb.TSDB(str(tmp_path / "ts"))
    now = now or time.time()
    for i, v in enumerate(values):
        store.append(name, v, t=now - (len(values) - i) * step)
    return store, now


def test_burn_rate_math(tmp_path):
    """burn = bad_frac / budget, per window, firing at its
    threshold."""
    # 20 samples, 10 violate m<=5 -> bad_frac 0.5; budget 0.05 ->
    # burn 10
    store, now = _mk_store(tmp_path, [1.0] * 10 + [9.0] * 10)
    spec = slo.SLO("m", "<=", 5, budget=0.05, fast_s=60, slow_s=600,
                   burn_fast=8.0, burn_slow=2.0)
    ev = slo.Evaluator(store, [spec], dump_alerts=False)
    row = ev.evaluate(now=now)[0]
    fast = row["windows"]["fast"]
    assert fast["samples"] == 20 and fast["bad"] == 10
    assert fast["bad_frac"] == pytest.approx(0.5)
    assert fast["burn"] == pytest.approx(10.0)
    assert fast["firing"]                   # 10 >= burn_fast 8
    slow = row["windows"]["slow"]
    assert slow["burn"] == pytest.approx(10.0) and slow["firing"]
    assert row["budget_remaining"] == 0.0   # 0.5/0.05 clamps at 0
    # healthy series: zero burn, full budget
    store2, now2 = _mk_store(tmp_path / "h", [1.0] * 20)
    row2 = slo.Evaluator(store2, [spec],
                         dump_alerts=False).evaluate(now=now2)[0]
    assert row2["windows"]["fast"]["burn"] == 0.0
    assert not row2["windows"]["fast"]["firing"]
    assert row2["budget_remaining"] == 1.0


def test_burn_needs_min_samples_and_empty_window(tmp_path):
    store, now = _mk_store(tmp_path, [9.0, 9.0])   # violating, but 2
    spec = slo.SLO("m", "<=", 5, budget=0.01, min_samples=3)
    ev = slo.Evaluator(store, [spec], dump_alerts=False)
    fast = ev.evaluate(now=now)[0]["windows"]["fast"]
    assert fast["burn"] > 0 and not fast["firing"]
    # a window with NO samples is unknown, not firing
    empty = ev.evaluate(now=now + 10000)[0]["windows"]["fast"]
    assert empty["samples"] == 0 and not empty["firing"]


def test_rate_objective_windows(tmp_path):
    """A .rate objective evaluates consecutive-sample rates: a
    throughput floor fires when the counter stalls."""
    store = tsdb.TSDB(str(tmp_path / "ts"))
    now = time.time()
    # counter advances 5/s for 20 s, then STALLS for 20 s
    for i in range(20):
        store.append("rounds_total", 5.0 * i, t=now - 40 + i)
    for i in range(20):
        store.append("rounds_total", 95.0, t=now - 20 + i)
    spec = slo.SLO("rounds_total.rate", ">=", 1.0, budget=0.3,
                   fast_s=15, slow_s=45, burn_fast=2.0,
                   burn_slow=2.0)
    ev = slo.Evaluator(store, [spec], dump_alerts=False)
    row = ev.evaluate(now=now)[0]
    # fast window only sees the stall -> 100% bad -> burn 1/0.3
    assert row["windows"]["fast"]["burn"] == pytest.approx(1 / 0.3,
                                                           rel=1e-3)
    assert row["windows"]["fast"]["firing"]
    # slow window is ~half healthy (20 of 39 rate points bad ->
    # burn ~1.71), under its 2.0 threshold
    assert row["windows"]["slow"]["burn"] == pytest.approx(
        20 / 39 / 0.3, rel=1e-3)
    assert not row["windows"]["slow"]["firing"]


# ------------------------------------------------------ alert lifecycle

def test_alert_fires_once_per_slo_window_with_series(tmp_path):
    """A firing (slo, window) bumps slo_alerts_total, mirrors gauges,
    and writes EXACTLY ONE flight dump embedding the offending
    series — repeated evaluations do not re-dump."""
    obs_metrics.zero_all()
    store, now = _mk_store(tmp_path, [9.0] * 10)
    spec = slo.SLO("m", "<=", 5, name="drill", budget=0.05,
                   fast_s=60, slow_s=600)
    prev = FLAGS.telemetry_dump_dir
    FLAGS.telemetry_dump_dir = str(tmp_path / "dumps")
    try:
        ev = slo.Evaluator(store, [spec])
        for _ in range(4):                  # repeated passes
            ev.evaluate(now=now)
        assert obs_metrics.counter("slo_alerts_total").value == 2
        assert obs_metrics.gauge("slo_alerts_active").value == 2
        assert obs_metrics.gauge("slo_burn_fast_drill").value \
            == pytest.approx(20.0)
        assert obs_metrics.gauge(
            "slo_budget_remaining_drill").value == 0.0
        dumps = sorted(glob.glob(
            str(tmp_path / "dumps" / "flight_*.json")))
        reasons = {}
        for p in dumps:
            with open(p) as f:
                rec = json.load(f)
            reasons.setdefault(rec["reason"], []).append(rec)
        # exactly one dump per (slo, window)
        assert sorted(reasons) == ["slo:drill:fast", "slo:drill:slow"]
        assert all(len(v) == 1 for v in reasons.values())
        alert = reasons["slo:drill:fast"][0]["slo"]["alert"]
        assert alert["slo"] == "drill" and alert["window"] == "fast"
        assert alert["objective"] == "m <= 5"
        assert len(alert["series"]) == 10   # the offending series
        assert all(v == 9.0 for _, v in alert["series"])
        # a FIRST-evaluation alert's dump still carries the current
        # pass's status table (alerts fire after status commit)
        status = reasons["slo:drill:fast"][0]["slo"]["status"]
        assert status and status[0]["name"] == "drill"
        assert status[0]["windows"]["fast"]["burn"] \
            == pytest.approx(20.0)
        # alert state is visible via the module introspection surface
        ev2 = slo._EVAL   # not installed; use the evaluator directly
        assert {(a["slo"], a["window"])
                for a in ev.active_alerts()} \
            == {("drill", "fast"), ("drill", "slow")}
    finally:
        FLAGS.telemetry_dump_dir = prev


def test_alert_clears_when_burn_recovers(tmp_path):
    store, now = _mk_store(tmp_path, [9.0] * 10)
    spec = slo.SLO("m", "<=", 5, name="rec", budget=0.05, fast_s=30,
                   slow_s=30000)
    ev = slo.Evaluator(store, [spec], dump_alerts=False)
    ev.evaluate(now=now)
    assert ("rec", "fast") in {(a["slo"], a["window"])
                               for a in ev.active_alerts()}
    # healthy samples push the bad window out of the fast horizon
    for i in range(60):
        store.append("m", 1.0, t=now + i)
    ev.evaluate(now=now + 60)
    assert ("rec", "fast") not in {(a["slo"], a["window"])
                                   for a in ev.active_alerts()}


def test_barrier_status_carries_slo_alerts(tmp_path):
    """BarrierStatus-style introspection: the pserver's status reply
    names currently-firing alerts."""
    from paddle_tpu.core.scope import Scope
    from paddle_tpu.distributed.rpc import VariableServer

    srv = VariableServer(Scope(), {}, lambda b: None, fanin=1)
    st = json.loads(srv._barrier_status(b"").decode())
    assert st["slo_alerts"] == []
    store, now = _mk_store(tmp_path, [9.0] * 10)
    ev = slo.install(store=store,
                     specs=[slo.SLO("m", "<=", 5, name="ps",
                                    budget=0.05)],
                     dump_alerts=False)
    ev.evaluate(now=now)
    st = json.loads(srv._barrier_status(b"").decode())
    assert "ps:fast" in st["slo_alerts"]


# ---------------------------------------------- per-tenant serving tags

def _save_tiny_model(d):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.core.scope import Scope

    main_p, startup = fluid.Program(), fluid.Program()
    scope = Scope()
    with fluid.scope_guard(scope):
        with fluid.program_guard(main_p, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data(name="x", shape=[16],
                                      dtype="float32")
                h = fluid.layers.fc(x, size=32, act="tanh")
                out = fluid.layers.fc(h, size=4, act="softmax")
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(
            d, ["x"], [out], exe, main_program=main_p,
            aot_feed_specs={"x": ((1, 16), "float32")})
    return np.ones((1, 16), np.float32)


def test_per_tenant_request_metrics(tmp_path):
    """server.submit tags every request into the tenant's own
    latency histogram; failures/drops land in its error counter —
    the series a per-tenant SLO evaluates."""
    from paddle_tpu import serving

    obs_metrics.zero_all()
    d = str(tmp_path / "model")
    x = _save_tiny_model(d)
    with serving.InferenceServer(max_batch=2, max_wait_us=0) as srv:
        srv.load("tenant_a", d, warm=[1])
        for _ in range(5):
            srv.predict("tenant_a", {"x": x})
        h = obs_metrics.histogram("serve_request_ms_tenant_a")
        assert h.count == 5
        assert obs_metrics.counter(
            "serve_request_errors_total_tenant_a").value == 0
        # a failing request (wrong feed width caught in-batch) counts
        # as that tenant's error, not a latency sample
        with pytest.raises(Exception):
            srv.predict("tenant_a",
                        {"x": np.ones((1, 7), np.float32)})
        assert obs_metrics.counter(
            "serve_request_errors_total_tenant_a").value >= 1
        assert h.count == 5


# ----------------------------------------------------- the fault drill

def test_slo_fault_drill(tmp_path):
    """The fault_matrix 'slo' preset body: a short serve+train loop
    with the tsdb sampler feeding a store and the SLO evaluator armed,
    while an injected serve_dispatch DELAY fault burns the
    request-latency budget.  Asserts the burn-rate alert fires within
    the fast window, exactly one flight dump lands per (slo, window)
    naming the violated SLO with the offending series embedded, and
    the healthy train-side SLO never fires."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    from paddle_tpu.distributed import resilience

    obs_metrics.zero_all()
    dump_dir = FLAGS.telemetry_dump_dir or str(tmp_path / "dumps")
    prev_dump = FLAGS.telemetry_dump_dir
    FLAGS.telemetry_dump_dir = dump_dir
    store = tsdb.TSDB(str(tmp_path / "ts"))
    prev_inj = resilience.get_injector()
    if not any(r.point == "serve_dispatch" for r in prev_inj.rules):
        # standalone run: the preset exports FLAGS_fault_spec itself
        resilience.install_faults("serve_dispatch:delay:0.02")
    try:
        # -- train half: a few prepared steps feed the executor
        # step-wall histogram the healthy SLO watches
        main_p, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_p, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data(name="x", shape=[8],
                                      dtype="float32")
                loss = fluid.layers.mean(fluid.layers.fc(x, size=4))
                fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            feed = {"x": np.ones((4, 8), np.float32)}
            prep = exe.prepare(main_p, feed_specs=feed,
                               fetch_list=[loss])
            for _ in range(5):
                prep.run_prepared(feed)
            prep.sync_scope()

        # -- serve half under the injected latency fault
        d = str(tmp_path / "model")
        xfeed = _save_tiny_model(d)
        specs = [
            slo.SLO("serve_request_ms_m.p99", "<=", 2.0,
                    name="serve_p99", budget=0.05, fast_s=30,
                    slow_s=300, min_samples=3),
            slo.SLO("executor_step_wall_ms.p99", "<=", 1e9,
                    name="train_step", budget=0.05, fast_s=30,
                    slow_s=300, min_samples=3),
        ]
        ev = slo.install(store=store, specs=specs)
        t_fault = time.time()
        alert_at = None
        with serving.InferenceServer(max_batch=2,
                                     max_wait_us=0) as srv:
            srv.load("m", d, warm=[1])
            for i in range(30):
                srv.predict("m", {"x": xfeed})
                tsdb.sample_registry(store)
                ev.evaluate()
                if alert_at is None and ev.active_alerts():
                    alert_at = time.time()
        assert alert_at is not None, "burn-rate alert never fired"
        # (1) within the fast window of the fault's onset
        assert alert_at - t_fault < specs[0].fast_s
        firing = {(a["slo"], a["window"])
                  for a in ev.active_alerts()}
        assert ("serve_p99", "fast") in firing
        # extra evaluation passes must not re-dump
        for _ in range(3):
            ev.evaluate()
        # (2) exactly one flight dump per (slo, window), naming the
        # violated SLO and embedding the offending series
        by_reason = {}
        for p in glob.glob(os.path.join(dump_dir, "flight_*.json")):
            with open(p) as f:
                rec = json.load(f)
            if str(rec.get("reason", "")).startswith("slo:"):
                by_reason.setdefault(rec["reason"], []).append(rec)
        assert set(by_reason) == {"slo:serve_p99:fast",
                                  "slo:serve_p99:slow"}
        assert all(len(v) == 1 for v in by_reason.values())
        alert = by_reason["slo:serve_p99:fast"][0]["slo"]["alert"]
        assert alert["slo"] == "serve_p99"
        assert alert["objective"].startswith(
            "serve_request_ms_m.p99")
        assert alert["series"], "offending series not embedded"
        assert all(v > 2.0 for _, v in alert["series"][-3:])
        # the healthy train-side SLO never fired
        assert ("train_step", "fast") not in firing
        assert obs_metrics.gauge(
            "slo_burn_fast_train_step").value == 0.0
    finally:
        resilience._injector = prev_inj
        FLAGS.telemetry_dump_dir = prev_dump
        store.close()


# ------------------------------------------------------- overhead gate

def test_sampler_and_evaluator_overhead_gate():
    """Acceptance (3): one full registry sample and one full SLO
    evaluation pass each cost < 2% of their sampling interval, and
    the measured fractions land in the registry as
    telemetry_gate_* gauges (satellite: gate history reaches the
    tsdb instead of living in tool stdout)."""
    T = _tool("telemetry_overhead")
    tsdb_us, tsdb_ms = T._measure_tsdb_us(repeats=2, iters=100)
    tsdb_frac = tsdb_us / (tsdb_ms * 1e3)
    slo_us, slo_ms = T._measure_slo_us(repeats=2, iters=60)
    slo_frac = slo_us / (slo_ms * 1e3)
    assert tsdb_frac < 0.02, tsdb_frac
    assert slo_frac < 0.02, slo_frac
    names = T.record_gate_gauges(
        {"tsdb_overhead_frac": tsdb_frac,
         "slo_overhead_frac": slo_frac})
    assert set(names) == {"telemetry_gate_tsdb_overhead_frac",
                          "telemetry_gate_slo_overhead_frac"}
    snap = obs_metrics.snapshot()
    assert snap["telemetry_gate_tsdb_overhead_frac"]["value"] \
        == pytest.approx(tsdb_frac)


# ------------------------------------------------- e2e acceptance run

def test_e2e_pserver_workload_retains_history(tmp_path):
    """Acceptance core: a real 2x2 pserver workload with
    FLAGS_tsdb_dir set in every process — each trainer/pserver
    retains its own metric history, the SLO file evaluates in-process
    (burn gauges ride the telemetry dumps), and the parent evaluates
    the same SLO file read-only against the pserver's store: sane
    floors hold, an impossible floor fires."""
    import dist_train_helpers as H

    os.environ["JAX_PLATFORMS"] = "cpu"
    tsdb_root = str(tmp_path / "tsdb")
    dump_dir = str(tmp_path / "dumps")
    slo_path = str(tmp_path / "slo.json")
    with open(slo_path, "w") as f:
        json.dump({"slo": [
            {"name": "nonfinite",
             "objective": "numerics_nonfinite_total == 0",
             "fast_s": 5, "slow_s": 60},
            {"name": "barrier_p99",
             "objective": "pserver_barrier_ms.p99 <= 60000",
             "fast_s": 5, "slow_s": 60},
            {"name": "stale",
             "objective": "pserver_staleness_gap <= 4",
             "fast_s": 5, "slow_s": 60},
        ]}, f)
    env = {"FLAGS_telemetry": "1",
           "FLAGS_telemetry_dump_dir": dump_dir,
           "FLAGS_tsdb_dir": tsdb_root,
           "FLAGS_tsdb_sample_ms": "25",
           "FLAGS_slo_spec": slo_path,
           "FLAGS_slo_eval_ms": "50"}
    ctx = mp.get_context("spawn")
    eps = ["127.0.0.1:%d" % _free_port() for _ in range(2)]
    pservers = ",".join(eps)
    steps = 3
    ps_procs = [ctx.Process(target=H.run_pserver,
                            args=(ep, pservers, 2, "softmax", True,
                                  env))
                for ep in eps]
    for p in ps_procs:
        p.start()
    q = ctx.Queue()
    tr_procs = [ctx.Process(target=H.run_trainer,
                            args=(tid, pservers, 2, steps, q,
                                  "softmax", True, env))
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

    # every process left its own store, and they are disjoint dirs
    stores = tsdb.open_stores(tsdb_root)
    assert len(stores) == 4, sorted(stores)
    ps_stores = {k: s for k, s in stores.items()
                 if (s.latest("pserver_rounds_applied_total")
                     or (0, 0))[1] >= steps}
    tr_stores = {k: s for k, s in stores.items()
                 if (s.latest("rpc_bytes_sent_total")
                     or (0, 0))[1] > 0 and k not in ps_stores}
    assert len(ps_stores) == 2, sorted(stores)
    assert len(tr_stores) == 2, sorted(stores)
    for s in ps_stores.values():
        # durable history, not just a final value: multiple samples
        # and the barrier-latency histogram decomposition
        t, v = s.scan("pserver_rounds_applied_total")
        assert len(t) >= 3 and v[-1] >= steps
        assert s.latest("pserver_barrier_ms.count")[1] > 0
        assert s.latest("pserver_barrier_ms.p99") is not None
    # the in-child evaluator ran: burn gauges rode the trace dumps
    trace_dumps = glob.glob(os.path.join(dump_dir, "trace_*.json"))
    assert len(trace_dumps) == 4
    saw_gauges = 0
    for p in trace_dumps:
        with open(p) as f:
            m = json.load(f).get("metrics", {})
        if "slo_burn_fast_nonfinite" in m:
            saw_gauges += 1
            assert m["slo_burn_fast_nonfinite"]["value"] == 0.0
    assert saw_gauges >= 1, "no child evaluator ever evaluated"
    # no alert fired on the healthy run
    assert not [p for p in glob.glob(
        os.path.join(dump_dir, "flight_*.json"))
        if json.load(open(p)).get("reason", "").startswith("slo:")]

    # parent-side: evaluate the SAME file read-only against a pserver
    # store — sane objectives hold; an impossible floor fires
    store = list(ps_stores.values())[0]
    specs = slo.load_specs(slo_path)
    ev = slo.Evaluator(store, specs, dump_alerts=False)
    t_last, _ = store.latest("pserver_rounds_applied_total")
    rows = {r["name"]: r for r in ev.evaluate(now=t_last)}
    assert not rows["nonfinite"]["windows"]["fast"]["firing"]
    assert not rows["barrier_p99"]["windows"]["fast"]["firing"]
    # every sample violates (the counter is never negative), so the
    # burn is 1/budget regardless of when each sample landed
    impossible = slo.SLO("pserver_rounds_applied_total", "<=", -1,
                         name="impossible", budget=0.01, fast_s=120,
                         slow_s=600)
    ev2 = slo.Evaluator(store, [impossible], dump_alerts=False)
    row = ev2.evaluate(now=t_last)[0]
    assert row["windows"]["fast"]["firing"]
    assert row["windows"]["fast"]["burn"] == pytest.approx(100.0)

    # and the full-pile sentinel still passes on the genuine artifacts
    # while flagging a degraded one (acceptance 4 — details in
    # test_watchtower.py)
    ps_tool = _tool("perf_sentinel")
    traj = ps_tool.build_trajectory(REPO, tsdb_root=tsdb_root)
    assert traj["metrics"]["serve_floor_qps"]["floor"] > 0
    assert traj["tsdb"], "tsdb evidence missing from trajectory"
