"""Watchtower (ISSUE 13): tsdb store semantics, the registry sampler,
the perf-regression sentinel's tier-1 quick modes (synthetic planted
regression -> rc 3, clean -> rc 0), the watchtower report, and the
trace_report --all registry dispatch."""
import json
import os
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


@pytest.fixture(autouse=True)
def _clean_slo():
    slo.reset()
    yield
    slo.reset()
    tsdb.stop_sampler()


# ----------------------------------------------------------- tsdb store

def test_tsdb_append_scan_roundtrip(tmp_path):
    s = tsdb.TSDB(str(tmp_path / "ts"))
    t0 = time.time()
    for i in range(20):
        s.append_row({"g": float(i), "c_total": 2 * i}, t=t0 + i)
    t, v = s.scan("g")
    assert len(t) == 20 and v[0] == 0.0 and v[-1] == 19.0
    # range scan
    t, v = s.scan("g", t0 + 5, t0 + 9)
    assert list(v) == [5.0, 6.0, 7.0, 8.0, 9.0]
    # unknown series -> empty, not an error
    t, v = s.scan("nope")
    assert len(t) == 0
    assert s.latest("g") == (pytest.approx(t0 + 19), 19.0)
    assert s.rate("c_total") == pytest.approx(2.0)
    s.close()


def test_tsdb_rotation_retention_and_reopen(tmp_path):
    # 2 records/row * 20 bytes: a 200-byte segment seals every 5 rows
    s = tsdb.TSDB(str(tmp_path / "ts"), segment_bytes=200,
                  retention_bytes=1000)
    t0 = time.time()
    for i in range(100):
        s.append_row({"a": i, "b": -i}, t=t0 + i)
    segs = [f for f in os.listdir(str(tmp_path / "ts"))
            if f.startswith("seg_")]
    assert len(segs) > 1, "no rotation happened"
    assert s.total_bytes() <= 1000 + 200   # retention (+active slack)
    # oldest samples dropped, newest survive
    t, v = s.scan("a")
    assert v[-1] == 99.0 and v[0] > 0.0
    s.close()
    # a fresh read-only open (another process's view) sees the same
    r = tsdb.TSDB(str(tmp_path / "ts"), create=False)
    t2, v2 = r.scan("a")
    assert list(v2) == list(v)
    assert r.names() == ["a", "b"]
    # read-only stores refuse writes
    with pytest.raises(IOError):
        r.append("a", 1.0)


def test_tsdb_sealed_segment_cache(tmp_path):
    """Sealed segments parse once and serve repeated window queries
    from the cache (the SLO evaluator re-scans every tick); retention
    eviction drops the cached array with the file."""
    # 5 sealed segments — under the cache bound (queries that span
    # more sealed segments than the cache re-parse the overflow)
    s = tsdb.TSDB(str(tmp_path / "ts"), segment_bytes=400,
                  retention_bytes=100000)
    t0 = time.time()
    for i in range(55):
        s.append_row({"a": i, "b": -i}, t=t0 + i)
    assert not s._seg_cache            # nothing read yet
    t1_, v1 = s.scan("a")
    assert s._seg_cache                # sealed segments now cached
    cached = {f: id(arr) for f, (_sz, arr) in s._seg_cache.items()}
    t2_, v2 = s.scan("a")
    assert list(v2) == list(v1)
    for f, (_sz, arr) in s._seg_cache.items():
        assert id(arr) == cached[f], "sealed segment re-parsed"
    # retention keeps the cache in step with the files on disk
    s.retention_bytes = 2000
    for i in range(200):
        s.append_row({"a": 55 + i, "b": 0}, t=t0 + 55 + i)
    assert all(os.path.exists(os.path.join(s.dir, f))
               for f in s._seg_cache)
    s.close()


def test_sentinel_skips_non_numeric_bench_lines():
    """A malformed tail line ({'value': 'n/a'}) is dropped, not
    propagated as an empty metric that crashes the trajectory."""
    ps = _tool("perf_sentinel")
    found = ps._extract_bench_lines(
        '{"metric": "good", "value": 5.0, "unit": "images/sec"}\n'
        '{"metric": "bad", "value": "n/a"}\n'
        '{"metric": "worse", "value": [1, 2]}\n')
    assert set(found) == {"good"}
    traj = ps.build_trajectory(runs=[("x.json", found, False)])
    assert traj["metrics"]["good"]["floor"] == 5.0


def test_tsdb_torn_tail_truncates(tmp_path):
    """A crash mid-frame loses ONE sample, never a parse."""
    s = tsdb.TSDB(str(tmp_path / "ts"))
    t0 = time.time()
    for i in range(5):
        s.append("a", float(i), t=t0 + i)
    s.close()
    seg = os.path.join(str(tmp_path / "ts"), "seg_000001.bin")
    with open(seg, "ab") as f:
        f.write(b"\x01\x02\x03")   # torn partial record
    r = tsdb.TSDB(str(tmp_path / "ts"), create=False)
    t, v = r.scan("a")
    assert list(v) == [0.0, 1.0, 2.0, 3.0, 4.0]


def test_tsdb_rate_handles_counter_reset(tmp_path):
    s = tsdb.TSDB(str(tmp_path / "ts"))
    t0 = time.time()
    for i, val in enumerate([0, 10, 20, 0, 10]):   # reset at i=3
        s.append("c_total", val, t=t0 + i)
    # positive deltas only: 10+10+10 over 4s
    assert s.rate("c_total") == pytest.approx(30 / 4.0)
    # .rate series view clamps the reset interval to 0
    t, v = tsdb.series_values(s, "c_total.rate")
    assert list(v) == [10.0, 10.0, 0.0, 10.0]
    s.close()


def test_tsdb_downsample(tmp_path):
    s = tsdb.TSDB(str(tmp_path / "ts"))
    t0 = time.time()
    for i in range(40):
        s.append("a", float(i), t=t0 + i)
    ds = s.downsample("a", buckets=4)
    assert len(ds) == 4
    assert sum(d["count"] for d in ds) == 40
    assert ds[0]["min"] == 0.0 and ds[-1]["max"] == 39.0
    assert ds[0]["mean"] < ds[-1]["mean"]
    s.close()


def test_registry_sampler_decomposes_histograms(tmp_path):
    obs_metrics.counter("wt_count_total").inc(7)
    obs_metrics.gauge("wt_gauge").set(3.5)
    h = obs_metrics.histogram("wt_hist_ms")
    for x in (1.0, 2.0, 3.0, 100.0):
        h.observe(x)
    s = tsdb.TSDB(str(tmp_path / "ts"))
    n = tsdb.sample_registry(s)
    assert n > 0
    assert s.latest("wt_count_total")[1] == 7
    assert s.latest("wt_gauge")[1] == 3.5
    assert s.latest("wt_hist_ms.count")[1] == 4
    assert s.latest("wt_hist_ms.p99")[1] == 100.0
    assert s.latest("wt_hist_ms.sum")[1] == pytest.approx(106.0)
    s.close()


def test_default_store_and_background_sampler(tmp_path):
    """FLAGS_tsdb_dir + ensure_sampler: a per-(label, pid) store
    appears and fills without any explicit sampling calls."""
    prev_dir, prev_ms = FLAGS.tsdb_dir, FLAGS.tsdb_sample_ms
    FLAGS.tsdb_dir = str(tmp_path / "root")
    FLAGS.tsdb_sample_ms = 20
    try:
        assert tsdb.ensure_sampler() is not None
        obs_metrics.counter("wt_bg_total").inc(5)
        deadline = time.time() + 5.0
        got = None
        while time.time() < deadline:
            stores = tsdb.open_stores(str(tmp_path / "root"))
            for label, st in stores.items():
                if st.latest("wt_bg_total"):
                    got = (label, st.latest("wt_bg_total")[1])
                    break
            if got:
                break
            time.sleep(0.05)
        assert got is not None, "sampler never wrote the store"
        assert got[1] >= 5
        assert str(os.getpid()) in got[0]
    finally:
        tsdb.stop_sampler()
        FLAGS.tsdb_dir, FLAGS.tsdb_sample_ms = prev_dir, prev_ms


# -------------------------------------------------------- perf sentinel

def _fake_runs():
    """A synthetic trajectory: two historical runs of one qps metric
    (higher better) and one latency metric (lower better)."""
    return [
        ("RUN_r01.json",
         {"qps": {"value": 900.0, "higher_is_better": True,
                  "unit": "qps"},
          "p99_ms": {"value": 12.0, "higher_is_better": False,
                     "unit": "ms"}}, False),
        ("RUN_r02.json",
         {"qps": {"value": 1000.0, "higher_is_better": True,
                  "unit": "qps"},
          "p99_ms": {"value": 10.0, "higher_is_better": False,
                     "unit": "ms"}}, False),
    ]


def test_sentinel_synthetic_regression_rc3_and_clean_rc0():
    ps = _tool("perf_sentinel")
    traj = ps.build_trajectory(runs=_fake_runs())
    assert traj["metrics"]["qps"]["floor"] == 1000.0
    assert traj["metrics"]["p99_ms"]["floor"] == 10.0

    # clean run: within 15% of both floors
    clean = {"qps": {"value": 980.0, "higher_is_better": True},
             "p99_ms": {"value": 10.5, "higher_is_better": False}}
    regs, checked, skipped = ps.check_metrics(traj, clean)
    assert not regs and len(checked) == 2 and not skipped

    # planted regression: qps halves, p99 triples
    bad = {"qps": {"value": 500.0, "higher_is_better": True},
           "p99_ms": {"value": 30.0, "higher_is_better": False}}
    regs, _, _ = ps.check_metrics(traj, bad)
    assert {r["metric"] for r in regs} == {"qps", "p99_ms"}
    assert regs[0]["regress_frac"] > 0.15


def test_sentinel_cli_quick_modes(tmp_path):
    """The tier-1 smoke the ISSUE names: a degraded copy of the real
    SERVE_BENCH.json exits rc 3 through the CLI; the genuine artifact
    exits rc 0."""
    ps = _tool("perf_sentinel")
    src = os.path.join(REPO, "SERVE_BENCH.json")
    if not os.path.exists(src):
        pytest.skip("no SERVE_BENCH.json in this checkout")
    with open(src) as f:
        obj = json.load(f)
    degraded = dict(obj)
    degraded["floor"] = dict(obj["floor"],
                             qps=obj["floor"]["qps"] * 0.5)
    bad_path = str(tmp_path / "degraded.json")
    with open(bad_path, "w") as f:
        json.dump(degraded, f)
    assert ps.main(["--no-write", "--check", bad_path]) == 3
    assert ps.main(["--no-write", "--check", src]) == 0


def test_sentinel_quick_runs_gate_against_quick_floors_only():
    """A seconds-scale CI smoke must not be judged against a full
    run's floor (and vice versa)."""
    ps = _tool("perf_sentinel")
    runs = _fake_runs() + [
        ("RUN_quick.json",
         {"qps": {"value": 100.0, "higher_is_better": True}}, True)]
    traj = ps.build_trajectory(runs=runs)
    assert traj["metrics"]["qps"]["floor"] == 1000.0      # full only
    assert traj["metrics"]["qps"]["quick_floor"] == 100.0
    # a quick run at 95 qps: fine vs the quick floor, catastrophic vs
    # the full floor — it must compare against quick only
    regs, checked, _ = ps.check_metrics(
        traj, {"qps": {"value": 95.0, "higher_is_better": True}},
        quick=True)
    assert not regs and checked[0]["quick"]
    # and a quick run WITH a real quick regression still fails
    regs, _, _ = ps.check_metrics(
        traj, {"qps": {"value": 40.0, "higher_is_better": True}},
        quick=True)
    assert regs


def test_sentinel_builds_from_repo_artifacts(tmp_path):
    """The real in-repo *_BENCH.json pile becomes one trajectory with
    the expected headline metrics; a driver-wrapped BENCH_r*.json
    (none is committed any more) parses out of its 'tail'."""
    ps = _tool("perf_sentinel")
    traj = ps.build_trajectory(REPO)
    names = set(traj["metrics"])
    assert "serve_floor_qps" in names
    assert "pserver_dense_rounds_per_sec" in names
    assert "scale_peak_rows_per_sec" in names
    # training rounds parse out of a driver-wrapped tail
    wrapped = {"cmd": "python bench.py", "rc": 0, "tail": "\n".join([
        "some log line",
        json.dumps({"metric": "resnet50_flowers_train_bs256_bf16",
                    "value": 100.0, "unit": "images/sec",
                    "partial": True}),
        json.dumps({"metric": "resnet50_flowers_train_bs256_bf16",
                    "value": 101.0, "unit": "images/sec",
                    "secondary": {"metric": "transformer_lm_train",
                                  "value": 5.0,
                                  "unit": "tokens/sec"}})])}
    got, quick = ps.extract_metrics(wrapped)
    assert not quick
    assert got["resnet50_flowers_train_bs256_bf16"]["value"] == 101.0
    assert got["transformer_lm_train"]["value"] == 5.0
    for ent in traj["metrics"].values():
        assert ent["runs"] and ent["latest"] is not None
    # the CLI writes the canonical record atomically
    out = str(tmp_path / "PERF_TRAJECTORY.json")
    assert ps.main(["--repo", REPO, "--out", out]) == 0
    with open(out) as f:
        written = json.load(f)
    assert written["kind"] == "perf_trajectory"


def test_sentinel_ingests_tsdb(tmp_path):
    ps = _tool("perf_sentinel")
    store = tsdb.TSDB(str(tmp_path / "ts" / "proc_1"))
    t0 = time.time()
    for i in range(5):
        store.append("m_total", i * 2.0, t=t0 + i)
    store.close()
    traj = ps.build_trajectory(
        REPO, tsdb_root=str(tmp_path / "ts"),
        runs=_fake_runs())
    assert traj["tsdb"]["proc_1"]["m_total"]["last"] == 8.0
    assert traj["tsdb"]["proc_1"]["m_total"]["n"] == 5


# ------------------------------------------------------- watchtower CLI

def _canned_state(tmp_path):
    """A canned operational state: one store with a violating series,
    an slo:* flight dump, and a tiny trajectory file."""
    store = tsdb.TSDB(str(tmp_path / "ts" / "serve_1"))
    now = time.time()
    for i in range(30):
        store.append_row({"serve_request_ms_m.p99": 50.0 + i,
                          "serve_requests_total": 10 * i}, t=now - 30 + i)
    store.close()
    ev = slo.Evaluator(
        tsdb.TSDB(str(tmp_path / "ts" / "serve_1"), create=False),
        slo.load_specs("serve_request_ms_m.p99<=10"))
    FLAGS.telemetry_dump_dir, prev = str(tmp_path / "dumps"), \
        FLAGS.telemetry_dump_dir
    try:
        ev.evaluate(now=now)
    finally:
        FLAGS.telemetry_dump_dir = prev
    traj = {"kind": "perf_trajectory", "version": 1, "metrics": {
        "qps": {"higher_is_better": True, "unit": "qps",
                "runs": [{"source": "a", "value": 1000.0,
                          "quick": False},
                         {"source": "b", "value": 500.0,
                          "quick": False}],
                "floor": 1000.0, "latest": 500.0}}}
    tpath = str(tmp_path / "PERF_TRAJECTORY.json")
    with open(tpath, "w") as f:
        json.dump(traj, f)
    return tpath


def test_watchtower_report_from_canned_dump_dir(tmp_path, capsys):
    wt = _tool("watchtower")
    tpath = _canned_state(tmp_path)
    rc = wt.main(["--tsdb", str(tmp_path / "ts"),
                  "--dump-dir", str(tmp_path / "dumps"),
                  "--slo", "serve_request_ms_m.p99<=10",
                  "--trajectory", tpath])
    assert rc == 0
    out = capsys.readouterr().out
    assert "SLO status" in out
    assert "serve_request_ms_m_p99" in out
    assert "fast" in out                      # firing marker
    assert "alerts (" in out and "slo:" in out
    assert "hot series" in out
    # sparkline block characters actually rendered
    assert any(c in out for c in wt.SPARK)
    assert "bench trajectory" in out and "REGRESSED" in out


def test_watchtower_json_report(tmp_path, capsys):
    wt = _tool("watchtower")
    tpath = _canned_state(tmp_path)
    rc = wt.main(["--tsdb", str(tmp_path / "ts"),
                  "--dump-dir", str(tmp_path / "dumps"),
                  "--slo", "serve_request_ms_m.p99<=10",
                  "--trajectory", tpath, "--json"])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["kind"] == "watchtower_report"
    row = rep["slo"][0]
    assert row["firing"]                      # violating series fires
    assert row["budget_remaining"] == 0.0
    assert rep["alerts"] and rep["alerts"][0]["slo"] \
        == "serve_request_ms_m_p99"
    assert rep["alerts"][0]["series_samples"] > 0
    assert rep["bench"][0]["regressed"]


def test_watchtower_slo_anchors_at_store_time(tmp_path, capsys):
    """Post-hoc reads anchor windows at the store's newest sample:
    a collapse from hours ago still shows its burn instead of an
    empty (and therefore 'healthy') wall-clock window."""
    wt = _tool("watchtower")
    store = tsdb.TSDB(str(tmp_path / "ts" / "old_1"))
    old = time.time() - 7200          # two hours ago
    for i in range(20):
        store.append("m", 9.0, t=old + i)
    store.close()
    rc = wt.main(["--tsdb", str(tmp_path / "ts"), "--slo", "m<=5",
                  "--json"])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    row = rep["slo"][0]
    assert row["as_of"] == pytest.approx(old + 19)
    assert "fast" in row["firing"]
    assert row["budget_remaining"] == 0.0


def test_sparkline_shapes():
    wt = _tool("watchtower")
    assert wt.sparkline([]) == ""
    assert wt.sparkline([1.0, 1.0, 1.0]) == wt.SPARK[0] * 3
    s = wt.sparkline(list(range(64)), width=8)
    assert len(s) == 8
    assert s[0] == wt.SPARK[0] and s[-1] == wt.SPARK[-1]


# ----------------------------------------------- trace_report registry

def test_trace_report_all_implies_every_rollup(tmp_path, capsys):
    """--all = --kernels + every registered rollup, through the ONE
    table-registry loop (the per-flag copy-paste dispatch is gone)."""
    tr = _tool("trace_report")
    # registry covers exactly the known rollups
    assert [r[0] for r in tr.ROLLUPS] == [
        "numerics", "wire", "serve", "scale", "slo", "moe", "weaver",
        "gaps"]     # gaps reads a profiler capture: --all asks for it
    #                 only where --xplane gives one
    from paddle_tpu.observability.trace import Tracer
    obs_metrics.counter("slo_alerts_total").inc()
    t = Tracer(enabled=True)
    t.set_label("proc0")
    t.end(t.begin("step.prepared"))
    dump = str(tmp_path / "trace_p.json")
    t.dump(dump)
    rc = tr.main([dump, "--all"])
    assert rc == 0
    out = capsys.readouterr().out
    for title_frag in ("numerics rollup", "wire rollup",
                       "serve rollup", "scale rollup", "slo rollup",
                       "moe rollup"):
        assert title_frag in out, title_frag
    # JSON mode wraps every requested rollup key
    rc = tr.main([dump, "--all", "--json"])
    assert rc == 0
    obj = json.loads(capsys.readouterr().out)
    assert set(obj) == {"phases", "kernels", "numerics", "wire",
                        "serve", "scale", "slo", "moe", "weaver"}


def test_trace_report_slo_rollup_reads_gauges(tmp_path, capsys):
    """The --slo rollup reads the evaluator's mirrored gauges out of
    any dump's metrics snapshot."""
    tr = _tool("trace_report")
    obs_metrics.gauge("slo_burn_fast_myslo").set(21.5)
    obs_metrics.gauge("slo_burn_slow_myslo").set(3.25)
    obs_metrics.gauge("slo_budget_remaining_myslo").set(0.4)
    obs_metrics.counter("slo_alerts_total").inc(2)
    from paddle_tpu.observability.trace import Tracer
    t = Tracer(enabled=True)
    t.set_label("trainer0")
    t.end(t.begin("step.prepared"))
    dump = str(tmp_path / "trace_t.json")
    t.dump(dump)
    rc = tr.main([dump, "--slo"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "slo rollup" in out
    assert "myslo" in out and "21.50" in out
