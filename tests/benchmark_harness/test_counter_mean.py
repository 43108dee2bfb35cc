"""The ``counter_mean`` reader, and the three metrics of the serving
scheduler's own counters (PR 25) read from the deltas of a real run of
the program at a tiny size: a counter that the program renames, or
stops counting, leaves its metric with nothing to read and fails here.
A number from this CPU run is never a device metric."""
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib.spec import Spec                        # noqa: E402

METRICS = ("sched_host_share.serve", "prefill_share.serve",
           "queue_wait_ms.serve")


@pytest.fixture(scope="module")
def spec():
    return Spec(ROOT)


@pytest.mark.parametrize("counters,want", [
    ({"n": 6000, "d": 3}, 2.0),
    ({"d": 3}, 0.0),
    ({"n": 6000, "d": 0}, None),
    ({"n": 6000}, None),
    ({}, None),
])
def test_counter_mean_is_scaled_delta_over_delta(spec, counters, want):
    read = spec.reader("counter_mean").read
    got = read(trace=None, facts={"counters": counters}, peaks=None,
               num="n", den="d", scale=0.001)
    assert got == want
    assert read(trace=None, facts={}, peaks=None, num="n", den="d") is None


@pytest.fixture(scope="module")
def window_counters():
    """Deltas of the program's ``serve_*`` counters across a few
    requests, taken as the serving driver takes them."""
    from paddle_tpu.observability import metrics
    from paddle_tpu.serving import InferenceServer, tiny_lm

    def counters():
        return {k: v["value"] for k, v in metrics.snapshot().items()
                if v.get("type") == "counter" and k.startswith("serve_")}

    cfg, params = tiny_lm(3, vocab=64, d_model=32, n_heads=2, n_layers=2,
                          d_ff=64, block_size=8, max_blocks=8, max_batch=2)
    with InferenceServer() as srv:
        srv.load_generative("g", cfg, params, kv_blocks=32)
        c0 = counters()
        futs = [srv.generate("g", [1, 2, 3 + i], max_new_tokens=8)
                for i in range(4)]
        for f in futs:
            f.result(180)
        # the idle loop flushes its phase clock: read once they rest
        c1, last = counters(), None
        while c1 != last:
            time.sleep(0.05)
            c1, last = counters(), c1
    return {k: c1[k] - c0.get(k, 0) for k in c1}


@pytest.mark.parametrize("metric", METRICS)
def test_metric_reads_the_programs_own_counters(spec, window_counters,
                                                metric):
    entry = {m["name"]: m for m in spec.doc["per_layer"]}[metric]
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "serving scheduler"
    mf = spec.metric_file(metric)
    for key in ("num", "den"):
        assert window_counters.get(mf["args"][key], 0) > 0, mf["args"][key]
    value = spec.reader(mf["reader"]).read(
        trace=None, facts={"counters": window_counters}, peaks=None,
        **mf["args"])
    assert value is not None and value > 0
    if entry["unit"] == "%":
        assert value < 100.0
    # a program without the counters (the parent commit) reads nothing
    assert spec.reader(mf["reader"]).read(
        trace=None, facts={"counters": {}}, peaks=None,
        **mf["args"]) is None


def test_the_two_shares_and_the_wait_add_up(spec, window_counters):
    c = window_counters
    parts = sum(c["serve_loop_%s_us_total" % k]
                for k in ("host", "prefill", "wait"))
    assert abs(parts - c["serve_loop_wall_us_total"]) \
        <= 0.02 * c["serve_loop_wall_us_total"]
    assert c["serve_admissions_total"] == 4
