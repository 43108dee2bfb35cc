"""The program's own spans on the profiler's clock (ISSUE 25): DecodeLoop
phases and run_prepared live under FLAGS_telemetry OR a jax profiler
session, the serve_loop_* / queue-wait counters counted where the work
happens, the dead path building nothing, and export.gap_rows putting a
capture's idle gaps down to the spans."""
import os
import sys
import time

import jax
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.observability import export, metrics, trace
from paddle_tpu.observability.trace import TRACER
from paddle_tpu.serving import InferenceServer, tiny_lm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG_KW = dict(vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
              block_size=8, max_blocks=8)
LOOP_SPANS = ("serve.admit", "serve.grow", "serve.decode.stage",
              "serve.decode", "serve.decode.wait", "serve.emit")


def _counters():
    return {k: v["value"] for k, v in metrics.snapshot().items()
            if v.get("type") == "counter" and k.startswith("serve_")}


def _delta(c0):
    """The counters' change since ``c0``, once the loop has gone idle
    and flushed its phase clock (they no longer move)."""
    last = None
    for _ in range(200):
        c1 = _counters()
        d = {k: c1[k] - c0.get(k, 0) for k in c1}
        if d == last:
            return d
        last = d
        time.sleep(0.05)
    raise AssertionError("the serve counters never settled")


@pytest.fixture
def server():
    cfg, params = tiny_lm(7, max_batch=4, **CFG_KW)
    with InferenceServer() as srv:
        eng = srv.load_generative("g", cfg, params, kv_blocks=32)
        srv.generate("g", [1, 2, 3], max_new_tokens=4).result(180)
        eng.drain_compiles()
        yield srv


@pytest.fixture
def prepared():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, size=4))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        feed = {"x": np.ones((4, 16), np.float32)}
        prep = exe.prepare(main, feed_specs=feed, fetch_list=[loss])
        prep.run_prepared(feed)
        yield prep, feed
        prep.sync_scope()


# ------------------------------------------------------------- counters

def test_loop_phases_add_up_to_the_wall():
    # a model wide enough that an iteration is a millisecond or more:
    # what lies between the phases is some microseconds of glue
    cfg, params = tiny_lm(7, vocab=256, d_model=256, n_heads=4, n_layers=4,
                          d_ff=1024, block_size=8, max_blocks=8,
                          max_batch=4)
    with InferenceServer() as srv:
        eng = srv.load_generative("g", cfg, params, kv_blocks=32)
        srv.generate("g", [1, 2, 3], max_new_tokens=4).result(180)
        eng.drain_compiles()
        c0 = _counters()
        futs = [srv.generate("g", [1, 2, 3, 4 + i], max_new_tokens=12)
                for i in range(6)]
        for f in futs:
            assert len(f.result(180)["tokens"]) == 12
        d = _delta(c0)
    wall = d["serve_loop_wall_us_total"]
    parts = (d["serve_loop_host_us_total"]
             + d["serve_loop_prefill_us_total"]
             + d["serve_loop_wait_us_total"])
    assert wall > 0 and d["serve_loop_prefill_us_total"] > 0
    assert d["serve_loop_wait_us_total"] > 0
    assert abs(wall - parts) <= 0.02 * wall, (wall, parts, d)
    # the identity holds with steps dispatched ahead of the one before:
    # that one's read is the wait, after the next one's dispatch
    assert 0 < d["serve_decode_ahead_total"] <= d["serve_decode_steps_total"]
    # nobody was preempted: one admission a request
    assert d["serve_admissions_total"] == 6
    assert d["serve_queue_wait_us_total"] >= 0


def test_queue_wait_counts_a_request_held_behind_a_full_batch():
    cfg, params = tiny_lm(7, max_batch=1, **CFG_KW)
    with InferenceServer() as srv:
        eng = srv.load_generative("g", cfg, params, kv_blocks=32)
        srv.generate("g", [1, 2, 3], max_new_tokens=4).result(180)
        eng.drain_compiles()
        c0 = _counters()
        srv.generate("g", [1, 2, 3], max_new_tokens=4).result(180)
        alone = _delta(c0)
        c0 = _counters()
        first = srv.generate("g", [1, 2, 3], max_new_tokens=40)
        second = srv.generate("g", [3, 2, 1], max_new_tokens=4)
        held_ms = second.result(180)["ttft_ms"]
        first.result(180)
        both = _delta(c0)
    assert alone["serve_admissions_total"] == 1
    assert both["serve_admissions_total"] == 2
    # the second request sat in the queue for the first one's whole
    # decode: its wait is most of its own time to first token
    waited_ms = both["serve_queue_wait_us_total"] / 1e3
    assert waited_ms > alone["serve_queue_wait_us_total"] / 1e3
    assert 0.5 * held_ms < waited_ms <= 1.05 * held_ms + 1.0, (
        waited_ms, held_ms)


# ---------------------------------------------------------------- spans

@pytest.fixture
def ring(server):
    """The ring's spans of two requests served with FLAGS_telemetry's
    tracer on, and the request futures' results."""
    assert not TRACER.on
    TRACER.clear()
    TRACER.enable()
    try:
        futs = [server.generate("g", [1, 2, 3, 4 + i], max_new_tokens=5)
                for i in range(2)]
        for f in futs:
            f.result(180)
        # the loop closes its last serve.iterate after the futures
        # resolve, then goes idle
        deadline = time.time() + 10
        while time.time() < deadline and not any(
                s["name"] == "serve.idle" for s in TRACER.open_spans()):
            time.sleep(0.01)
    finally:
        TRACER.disable()
    spans = TRACER.completed()
    TRACER.clear()
    return spans


@pytest.mark.parametrize("child", LOOP_SPANS)
def test_loop_spans_nest_under_the_iteration(ring, child):
    parents = [s for s in ring if s["name"] == "serve.iterate"]
    assert parents and len({s["tid"] for s in parents}) == 1
    kids = [s for s in ring if s["name"] == child]
    assert kids, child
    for k in kids:
        assert k["tid"] == parents[0]["tid"] and k["depth"] == 1
        assert any(p["ts_us"] <= k["ts_us"] and k["ts_us"] + k["dur_us"]
                   <= p["ts_us"] + p["dur_us"] + 1.0 for p in parents), k


def test_decode_counts_the_pages_held_against_the_page_slots(ring, server):
    """``serve_decode_pages_total`` / ``serve_decode_page_slots_total``:
    the share of a decode bucket's block table that names a held page,
    which is what a kernel walking the whole table would waste; the
    ``serve.decode`` span says the same a step."""
    decodes = [s for s in ring if s["name"] == "serve.decode"]
    assert decodes
    # ``ahead``: the step went out on the device tokens of the one
    # before, unread; never the first after a prefill
    assert {s["args"]["ahead"] for s in decodes} == {0, 1}
    assert decodes[0]["args"]["ahead"] == 0
    for s in decodes:
        rows, slots = (int(x) for x in s["args"]["bucket"].split("x"))
        # prompts of 4 and 5 new tokens: one block of 8 a row, or two
        assert s["args"]["rows"] <= s["args"]["pages"] <= 2 * rows
        assert s["args"]["pages"] <= rows * slots
    c0 = _counters()
    server.generate("g", [1, 2, 3, 4], max_new_tokens=6).result(180)
    d = _delta(c0)
    # one row of 5..9 tokens: one page a step, then two; a 1-row bucket
    steps = d["serve_decode_steps_total"]
    assert steps == 5
    assert steps <= d["serve_decode_pages_total"] <= 2 * steps
    assert (d["serve_decode_page_slots_total"]
            >= d["serve_decode_pages_total"])


def test_a_requests_prefill_and_finishing_emit_share_its_cid(ring):
    # the first prompt met no running row: a prefill the loop waited for
    (waited,) = [s for s in ring if s["name"] == "serve.prefill"]
    # (one bucket here: a prompt rides in 64 rows or more, and this
    # model's longest sequence is 64)
    assert waited["args"]["tokens"] == 4 and waited["args"]["bucket"] == 64
    child = [s for s in ring if s["name"] == "serve.prefill.dispatch"
             and s.get("cid") == waited["cid"]]
    assert len(child) == 1 and child[0]["depth"] == waited["depth"] + 1
    # the second rode the decode step dispatched next: that step's spans
    # say so, under the request's id
    carried = [s for s in ring if s["name"] in ("serve.decode.stage",
                                                "serve.decode")
               and "riding" in s["args"]]
    assert [s["name"] for s in carried] == ["serve.decode.stage",
                                            "serve.decode"]
    assert {s["args"]["riding"] for s in carried} == {64}
    assert carried[1]["args"]["rows"] == 1 and len(
        {s["cid"] for s in carried}) == 1
    finished = [i for s in ring if s["name"] == "serve.emit"
                for i in s["args"]["finished_ids"]]
    assert waited["cid"] in finished and carried[0]["cid"] in finished
    assert waited["cid"] != carried[0]["cid"]
    done = [s for s in ring if s["name"] == "serve.emit"
            and s["args"]["finished"]]
    assert done and all(s["cid"] == s["args"]["finished_ids"][0]
                        for s in done)


def test_a_profiler_session_lights_the_spans_without_the_flag(
        server, prepared, tmp_path):
    prep, feed = prepared
    assert not TRACER.on and not TRACER.live()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert TRACER.live()
        server.generate("g", [1, 2, 3, 4], max_new_tokens=4).result(180)
        prep.run_prepared(feed)
    finally:
        jax.profiler.stop_trace()
    assert not TRACER.live()
    events = {}
    for plane in export.load_profile(str(tmp_path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, dict(e.stats))
    for name in ("serve.iterate", "serve.prefill", "step.prepared",
                 "step.feed", "step.dispatch") + LOOP_SPANS:
        assert name in events, (name, sorted(events))
    assert events["serve.prefill"]["tokens"] == 4
    assert "cid" in events["serve.prefill"]


def test_the_dead_path_builds_no_span(server, prepared, monkeypatch):
    prep, feed = prepared
    assert not TRACER.live()
    built = []

    class Counted(trace.Span):
        __slots__ = ()

        def __init__(self, *a):
            built.append(a[0])
            super().__init__(*a)

    monkeypatch.setattr(trace, "Span", Counted)
    server.generate("g", [1, 2, 3, 4], max_new_tokens=6).result(180)
    for _ in range(3):
        prep.run_prepared(feed)
    assert built == []


def test_the_liveness_check_allocates_nothing():
    """The once-a-step check of the dead path (the allocation check
    test_disabled_path_is_noop_and_allocation_free makes of a site)."""
    assert not TRACER.live()
    live = TRACER.live
    for _ in range(2000):
        live()
    before = sys.getallocatedblocks()
    for _ in range(20000):
        live()
    assert abs(sys.getallocatedblocks() - before) < 32


# ------------------------------------------------------------- gap_rows

HAND_MADE = """
planes {
  name: "/device:TPU:0"
  lines {
    name: "XLA Ops"
    events { metadata_id: 1 offset_ps: 100000 duration_ps: 200000 }
    events { metadata_id: 1 offset_ps: 700000 duration_ps: 300000 }
  }
  lines {
    name: "XLA Modules"
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 200000 }
    events { metadata_id: 2 offset_ps: 700000 duration_ps: 300000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%%fusion.1 = f32[8]{0} fusion()" } }
  event_metadata { key: 2 value { id: 2 name: "jit_serve_decode(1)" } }
}
planes {
  name: "/host:CPU"
  lines {
    name: "serve-decode-g"
    events { metadata_id: 1 offset_ps: 50000 duration_ps: 900000 }
    events { metadata_id: 2 offset_ps: 250000 duration_ps: %(emit_ps)d }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1000000 }
  }
  lines {
    name: "runtime"
    events { metadata_id: 4 offset_ps: 130000 duration_ps: 10000 }
    events { metadata_id: 5 offset_ps: 370000 duration_ps: 1000 }
    events { metadata_id: 4 offset_ps: 730000 duration_ps: 10000 }
    events { metadata_id: 5 offset_ps: 1070000 duration_ps: 1000 }
  }
  event_metadata { key: 1 value { id: 1 name: "serve.iterate" } }
  event_metadata { key: 2 value { id: 2 name: "serve.emit" } }
  event_metadata { key: 3 value { id: 3 name: "bench.client_wait" } }
  event_metadata { key: 4 value { id: 4 name: "%(launched)s" } }
  event_metadata { key: 5 value { id: 5 name: "%(seen_done)s" } }
}
"""
RUNTIME = {"launched": export.LAUNCHED, "seen_done": export.SEEN_DONE}


def _hand_made(emit_ns, **names):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(
        HAND_MADE % dict(RUNTIME, emit_ps=emit_ns * 1000, **names))


@pytest.mark.parametrize("emit_ns,span,under", [
    (400, "serve.emit", 295), (100, "serve.iterate", 0)])
def test_gap_rows_puts_the_gap_under_the_deepest_covering_span(
        emit_ns, span, under):
    """Two device runs [100,300) and [700,1000) ns on the device's
    clock.  The runtime enqueued them by 140 and 740 and saw them done
    at 370 and 1070 on the host's: the device clock is 40 to 70 ns
    ahead, so its times move by 55 and the one gap is [355,755).
    serve.iterate [50,950) covers it all; serve.emit [250, 250+emit) is
    deeper: 400 ns long it covers 295 of the gap and takes it, 100 ns
    long it ends before the gap and the iteration keeps it.
    bench.client_wait is not the program's and never counts."""
    profile = _hand_made(emit_ns)
    assert export.clock_skew(profile) == (40, 70)
    rows = export.gap_rows(profile)
    assert rows[0]["span"] == "(window)"
    assert rows[0]["gaps"] == 1 and rows[0]["shift_ns"] == 55
    assert rows[0]["idle_s"] == pytest.approx(400e-9)
    assert rows[0]["window_s"] == pytest.approx(900e-9)
    by = {r["span"]: r for r in rows[1:]}
    assert by[span]["gaps"] == 1
    assert by[span]["idle_s"] == pytest.approx(400e-9)
    assert by[span]["share"] == pytest.approx(400 / 900)
    assert by["serve.iterate"]["under_s"] == pytest.approx(
        (400 - under) * 1e-9)
    assert ("serve.emit" in by) == bool(under)
    assert "bench.client_wait" not in by and "unspanned" not in by
    assert "moved 0.000 ms" in export.format_gap_table(rows)


@pytest.mark.parametrize("names,skew", [
    ({"launched": "other"}, (None, 70)),
    ({"seen_done": "other"}, (40, None)),
    ({"launched": "other", "seen_done": "other"}, (None, None)),
])
def test_clock_skew_keeps_the_bounds_the_capture_shows(names, skew):
    profile = _hand_made(400, **names)
    assert export.clock_skew(profile) == skew
    known = [x for x in skew if x is not None]
    assert export.gap_rows(profile)[0]["shift_ns"] == (
        known[0] if known else 0)


def test_gap_rows_leaves_a_gap_no_span_covers_unspanned():
    from jax.profiler import ProfileData

    text = HAND_MADE % dict(RUNTIME, emit_ps=100000)
    text = text.replace('name: "serve.iterate"', 'name: "other.iterate"')
    text = text.replace('name: "serve.emit"', 'name: "other.emit"')
    rows = export.gap_rows(ProfileData.from_text_proto(text))
    assert [r["span"] for r in rows] == ["(window)", "unspanned"]
    assert rows[1]["gaps"] == 1


def test_trace_report_gaps_reads_a_capture_alone(tmp_path, capsys):
    from jax.profiler import ProfileData

    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(
            HAND_MADE % dict(RUNTIME, emit_ps=400000)))
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    assert trace_report.main(["--xplane", str(tmp_path), "--gaps"]) == 0
    printed = capsys.readouterr().out
    assert "serve.emit" in printed and "share of window" in printed
    with pytest.raises(SystemExit):
        trace_report.main(["--gaps"])
