"""The per-layer metrics that read the serving loop's step counters
(``decode_step_ms``, ``ride_step_ms``, ``decode_late_share``,
``dispatch_ms``, ``ride_wait_ms``), rehearsed through the harness on the
CPU at a tiny size in the ``nemotron_h`` cell's temporary root; a
number from these runs is never a device metric."""
import pytest

from test_nemotron_cell import _run, tiny_root  # noqa: F401 (a fixture)

STEP_METRICS = ("decode_step_ms.serve", "ride_step_ms.serve",
                "decode_late_share.serve", "dispatch_ms.serve",
                "ride_wait_ms.serve")
SERVING_CELLS = ["cgpt13b-serve-chat-c16", "nemotron3s-ep4-serve-reason-c64"]


@pytest.mark.parametrize("name", STEP_METRICS)
def test_a_step_metric_is_read_from_program_counters_in_both_serving_cells(
        tiny_root, name):
    from benchmark.lib.spec import Spec

    spec = Spec(tiny_root)
    (entry,) = [m for m in spec.doc["per_layer"] if m["name"] == name]
    assert entry["source"] == "program_counter"
    assert entry["workloads"][:2] == SERVING_CELLS
    assert spec.metric_file(name)["reader"] in ("counter_mean",
                                                "counter_ratio")


def test_traced_rehearsal_reports_the_step_metrics(tiny_root):
    res = _run(tiny_root, trace=1)
    assert res["correct"], res["compared"]
    got = res["metrics"]
    for name in STEP_METRICS:
        assert name in got, (name, sorted(got))
    value = {name: got[name]["value"] for name in STEP_METRICS}
    assert value["decode_step_ms.serve"] > 0
    assert value["ride_step_ms.serve"] > 0
    assert 0 <= value["decode_late_share.serve"] <= 100
    assert value["dispatch_ms.serve"] > 0
    assert value["ride_wait_ms.serve"] >= 0
