"""Tests of the benchmark's harness (``benchmark/``, ``BENCHMARK.json``).

Every file the harness finds by name is a case of its own, so a cell, a
configuration, a metric or a limit that a later PR adds is tested by
being there.  The drivers are rehearsed on the CPU at a tiny size in a
temporary benchmark root that ADDS files beside copies of the real ones
and edits none; a number from these runs is never a device metric (the
command itself refuses to run without a TPU, which is tested too).
"""
import glob
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run                     # noqa: E402
from benchmark.lib import check, counts, reduce, reference, traffic  # noqa: E402
from benchmark.lib.peaks import DEVICE_PEAKS, device_peaks  # noqa: E402
from benchmark.lib.spec import NAME, UNIT, Spec, SpecError  # noqa: E402

V5E = DEVICE_PEAKS["TPU v5 lite"]
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _names(kind):
    return sorted(os.path.splitext(os.path.basename(p))[0]
                  for p in glob.glob(os.path.join(BENCH, kind, "*.json")))


def _doc():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def spec():
    return Spec(ROOT)


# ---------------------------------------------------------------------------
# every file loads and names only things that exist
# ---------------------------------------------------------------------------

def test_benchmark_json_is_the_contracts_shape(spec):
    doc = spec.doc
    assert sorted(doc) == ["command", "configs", "end_to_end", "paths",
                           "per_layer", "run_seconds", "workloads"]
    assert doc["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= doc["run_seconds"] <= 51
    assert "setup_s" in {m["name"] for m in doc["end_to_end"]}
    for m in doc["end_to_end"]:
        assert sorted(set(m) - {"workloads"}) == [
            "better", "bound", "name", "source", "unit"]
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in doc["per_layer"]:
        assert sorted(set(m) - {"workloads"}) == [
            "better", "layer", "moves", "name", "source", "unit"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    four = sum(w["chips"] == 4 for w in doc["workloads"])
    assert four <= max(1, len(doc["workloads"]) // 4)
    for path in doc["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))


@pytest.mark.parametrize("cell", [w["name"] for w in _doc()["workloads"]])
def test_cell_names_files_that_exist(spec, cell):
    c = spec.cell(cell)
    config, mix = spec.config(c), spec.traffic(c)
    assert len(c["why"]) <= 200
    assert set(config["sizes"]) == {"d_model", "n_heads", "n_layers", "d_ff",
                                    "n_positions", "vocab_size"}
    entry = spec.configs[c["config"]]
    assert entry["file"].startswith("benchmark/configs/")
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    for key in config["reduced"]:
        assert config[key] != config["published"][key], key
        assert not key.endswith(("_dim", "_rank")), key
    # a size is as published unless ``reduced`` names it (only depth is
    # cut); what else differs is the program's own departure, named
    for key, value in config["published"].items():
        if isinstance(value, (int, float)):
            assert key in config["reduced"] or config[key] == value, key
        elif config[key] != value:
            assert any(d.startswith(key) for d in config["departures"]), key
    driver = spec.driver(mix["driver"])
    assert callable(driver.Driver)
    limits = spec.limits(c)
    assert limits and all(v >= 0 for v in limits.values())
    reported = {m["name"] for m in spec.end_to_end(c)}
    assert "setup_s" in reported and len(reported) >= 2
    assert spec.per_layer(c), "a cell reports at least one per-layer metric"
    for m in spec.per_layer(c):
        assert m["moves"] in reported, (m["name"], m["moves"])


@pytest.mark.parametrize("metric", [m["name"] for m in _doc()["per_layer"]])
def test_per_layer_metric_has_a_reader(spec, metric):
    mf = spec.metric_file(metric)
    reader = spec.reader(mf["reader"])
    assert callable(reader.read)
    import inspect

    params = inspect.signature(reader.read).parameters
    assert set(mf.get("args", {})) | {"trace", "facts", "peaks"} == set(params)


@pytest.mark.parametrize("kind,name", [(k, n) for k in (
    "configs", "traffic", "metrics", "limits") for n in _names(k)])
def test_data_file_is_listed_and_well_named(spec, kind, name):
    assert NAME.match(name), name
    doc = spec.doc
    listed = {
        "configs": {os.path.splitext(os.path.basename(c["file"]))[0]
                    for c in doc["configs"]},
        "traffic": {w["traffic"] for w in doc["workloads"]},
        "metrics": {m["name"] for m in doc["per_layer"]},
        "limits": {w["name"] for w in doc["workloads"]},
    }[kind]
    # a file that BENCHMARK.json does not list yet belongs to a cell or a
    # metric kept for later: it still has to parse
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        json.load(f)
    if kind in ("configs", "limits"):
        assert name in listed, "%s/%s.json is used by no cell" % (kind, name)


@pytest.mark.parametrize("name,unit,ok", [
    ("ttft_p95_ms", "ms", True), ("mfu.train", "%", True),
    ("a-b_c.d", "tokens/s", True), ("has space", "ms", False),
    ("slash/name", "ms", False), ("ok", "tokens per s", False),
    ("ok", "µs", False), ("x" * 65, "ms", False),
    ("ok", "x" * 17, False)])
def test_names_and_units_are_held_to_the_allowed_characters(name, unit, ok):
    assert bool(NAME.match(name) and UNIT.match(unit)) is ok


def test_every_listed_name_and_unit_is_allowed(spec):
    doc = spec.doc
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in doc[group]:
            assert NAME.match(e["name"]), e["name"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]), m
    for w in doc["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        device_peaks("TPU v9 imaginary")


# ---------------------------------------------------------------------------
# counts.py by hand-worked cases
# ---------------------------------------------------------------------------

CGPT_L6 = dict(d_model=2048, n_heads=16, n_layers=6, d_ff=8192,
               vocab_size=50257, n_positions=2048)
PR21 = dict(d_model=1024, n_heads=8, n_layers=6, d_ff=4096, vocab_size=8192,
            n_positions=2048)


def test_counts_training_steps_by_hand():
    # 1.3B L6: 50.3M matmul parameters a layer, 102.9M in the head
    assert counts.matmul_params(CGPT_L6) == 6 * 50331648 + 2048 * 50257
    per_token = counts.train_flops_per_token(CGPT_L6, 2048)
    assert per_token == 6 * 404916224 + 6 * 6 * 2048 * 2048
    assert abs(per_token * 4096 / 1e12 - 10.57) < 0.01      # 10.6 TFLOP
    # PR 21's d1024 L6 vocab 8192 step at 32,768 tokens: 19.0 TFLOP (the
    # embedding tables, which bench.py adds, are looked up, not multiplied)
    assert abs(counts.train_flops_per_token(PR21, 2048) * 32768 / 1e12
               - 18.97) < 0.01


def test_counts_serving_and_kernels_by_hand():
    s = dict(CGPT_L6, n_layers=24)
    layers = 24 * 50331648
    assert counts.decode_flops(s, 100) == (
        2 * (layers + 2048 * 50257) + 4 * 2048 * 24 * 100)
    assert counts.prefill_flops(s, 3) == (
        2 * layers * 3 + 4 * 2048 * 24 * 6 + 2 * 2048 * 50257)
    # K and V of 100 positions x 2048 x 4 bytes in 24 layers
    assert counts.paged_decode_bytes(s, 100) == 2 * 100 * 2048 * 4 * 24
    flops, nbytes = counts.flash_train_work(CGPT_L6, 2, 2048)
    assert flops == 6 * 7 * 2 * 2048 * 2048 * 2048
    tensor, stats = 2 * 2048 * 2048 * 2, 2 * 16 * 2048 * 4
    assert nbytes == 6 * (12 * tensor + 3 * stats)
    floor, side = counts.least_time(flops, nbytes, V5E)
    assert side == "compute" and abs(floor - flops / 197e12) < 1e-12
    assert counts.least_time(0, 819e9, V5E) == (1.0, "memory")


# ---------------------------------------------------------------------------
# reduce.py on a hand-made trace whose numbers are known
# ---------------------------------------------------------------------------

def test_reduce_on_the_hand_made_trace():
    tr = reduce.Trace(reduce.load(os.path.join(DATA,
                                               "hand_made.xplane.pbtxt")))
    assert tr.window == (0.0, 1000.0)
    assert tr.busy_s() == pytest.approx(550e-9)
    assert tr.idle_share() == pytest.approx(0.45)
    seconds, n = tr.summed("tpu_custom_call")
    assert (seconds, n) == (pytest.approx(200e-9), 1)
    assert tr.summed("no_such_kernel") == (0.0, 0)
    # the run [50,500) holds the kernel and one %a; the run that ends
    # after the window is not counted
    assert tr.calls_per_run("^jit_step", "tpu_custom_call") == {1}
    assert tr.calls_per_run("^jit_step", "fusion|custom") == {2}
    assert tr.calls_per_run("^jit_other", "tpu_custom_call") == set()
    top = tr.top_ops(2)
    assert top[0] == ["%a fusion f32[8,8]", pytest.approx(300e-9)]
    assert top[1] == ["%k.1 custom-call f32[8,8]", pytest.approx(200e-9)]
    gaps = tr.longest_gaps(3)
    assert gaps[0] == ["drain", pytest.approx(200e-9)]
    assert gaps[1] == ["step_call", pytest.approx(150e-9)]


def test_a_reader_that_finds_nothing_returns_nothing(spec):
    tr = reduce.Trace(reduce.load(os.path.join(DATA,
                                               "hand_made.xplane.pbtxt")))
    roof = spec.reader("kernel_roofline")
    facts = {"kernel_work": {"k": {"flops": 197e12 * 100e-9, "bytes": 0}},
             "n_layers": 1}
    args = dict(work="k", events="tpu_custom_call", modules="^jit_step",
                calls_per_layer=1)
    assert roof.read(tr, facts, V5E, **args) == pytest.approx(50.0)
    assert roof.read(tr, facts, V5E, **dict(args, events="absent")) is None
    assert roof.read(tr, {"n_layers": 1}, V5E, **args) is None
    # a step that holds another number of matching calls than the
    # kernel's own (a further Pallas kernel has joined it) is not read
    assert roof.read(tr, dict(facts, n_layers=2), V5E, **args) is None
    assert roof.read(tr, facts, V5E, **dict(args, events="fusion|custom")) \
        is None
    assert roof.read(tr, facts, V5E, **dict(args, modules="^jit_other")) \
        is None
    assert spec.reader("step_mfu").read(tr, {}, V5E) is None
    assert spec.reader("counter_ratio").read(tr, {}, V5E, num="a",
                                             den="b") is None
    assert spec.reader("device_idle").read(tr, {}, V5E) \
        == pytest.approx(45.0)


def test_percentile_is_nearest_rank_over_all_samples(spec):
    assert reduce.percentile(range(1, 101), 95) == 95
    assert reduce.percentile([7.0], 95) == 7.0
    assert reduce.percentile([1, 2], 50) == 1
    assert reduce.percentile([], 95) is None
    read = spec.reader("sample_percentile").read
    facts = {"ttft_ms": [30.0, 10.0, 20.0, 40.0]}
    assert read(None, facts, V5E, samples="ttft_ms", p=75) == 30.0
    assert read(None, facts, V5E, samples="absent", p=75) is None


# ---------------------------------------------------------------------------
# check.py by hand-worked cases
# ---------------------------------------------------------------------------

def test_norm_gap_is_by_the_worst_leaf_against_the_median_leaf():
    ref = {"a": 1.0, "b": [2.0, 4.0], "c": 1e-6}
    got = {"a": 1.1, "b": [2.0, 3.0], "c": 3e-6}
    gap, leaf = check.worst_norm_gap(got, ref)
    # median leaf norm is 1.5; c's gap is 2e-6 / 1.5, b[1]'s 1 / 4
    assert (gap, leaf) == (pytest.approx(0.25), "b[1]")
    assert check.worst_norm_gap(got, ref, leave_out={"b[1]"})[1] == "a"
    assert check.worst_norm_gap({"a": float("nan")}, {"a": 1.0})[0] \
        == float("inf")
    assert check.still_leaves({"a": 1.0, "b": [2.0, 4.0], "c": 1e-6}) == {"c"}
    assert check.loss_gap([2.0, 3.3], [2.0, 3.0]) == pytest.approx(0.1)
    with pytest.raises(ValueError, match="limits name"):
        check.verdicts({"x": 1.0}, {"y": 1.0})


def test_sample_gap_is_element_by_element():
    ref = {"a": np.array([3.0, 4.0]), "b": np.array([[1.0, 0.0],
                                                     [0.0, 2.0]])}
    same = check.sample_gap(ref, ref)
    assert same[:2] == (0.0, 0.0)
    got = {"a": np.array([3.0, 4.0]), "b": np.array([[1.0, 0.5],
                                                     [0.0, 2.0]])}
    # rms sizes: a 3.54, b[0] 0.71, b[1] 1.41 (the median leaf); b[0]
    # differs by rms 0.354, measured against the median leaf's 1.41
    median, worst, leaf = check.sample_gap(got, ref)
    assert (median, leaf) == (0.0, "b[0]")
    assert worst == pytest.approx(0.25)
    # the same norms, other elements: a gap of norms cannot see it
    turned = {"a": np.array([4.0, 3.0]), "b": ref["b"]}
    assert check.sample_gap(turned, ref)[1] == pytest.approx(
        np.sqrt(1.0) / np.sqrt(12.5))


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

def test_every_seed_gets_the_same_sizes_in_another_order(spec):
    mix = spec.traffic(spec.cell("cgpt13b-serve-chat-c16"))
    n = traffic.SET_SIZE
    a = traffic.RequestStream(mix, 1, 50257)
    b = traffic.RequestStream(mix, 2**31 + 5, 50257)
    la = [a.lengths(i) for i in range(n)]
    lb = [b.lengths(i) for i in range(n)]
    assert sorted(la) == sorted(lb) and la != lb
    assert min(p for p, _ in la) >= 32 and max(p for p, _ in la) <= 768
    assert min(o for _, o in la) >= 16 and max(o for _, o in la) <= 192
    ids, _ = a.request(7)
    again, _ = traffic.RequestStream(mix, 1, 50257).request(7)
    assert (ids == again).all() and ids.max() < 50257
    # 16 callers at the longest request fit the pool: no preemption
    assert 16 * -(-(768 + 192) // 16) <= 1024 - 1
    toks, labs = traffic.lm_batch(2**31 + 5, 2, 64, 97)
    assert toks.shape == (2, 64) and labs.shape == (2, 64, 1)
    assert (toks[:, 1:] == labs[:, :-1, 0]).all()
    assert (toks[0] != toks[1]).any()


# ---------------------------------------------------------------------------
# a temporary benchmark root: tiny cells ADDED beside copies of the real
# files, none of which is edited
# ---------------------------------------------------------------------------

TINY = dict(n_embd=64, n_head=2, n_inner=128, n_layer=2, vocab_size=211)
# the smallest size at which a lower precision reads apart from bf16 AMP
SMALL_ELEM_LIMIT = 0.08     # bf16 AMP reads 0.036-0.041 here, the fp8 recipe 0.117-0.134
SMALL = dict(n_embd=256, n_head=2, n_inner=1024, n_layer=2, vocab_size=2048,
             n_positions=256)

EXTRA_DRIVER = '''
"""A driver a later PR might add: one jitted matmul, back to back."""
import time
import jax, jax.numpy as jnp


class Driver:
    def __init__(self, config, mix, seed, devices, peaks, meter, limits, log):
        self.n, self.seed, self.limits = int(mix["n"]), seed, limits

    def setup(self):
        self.x = jax.random.normal(jax.random.PRNGKey(self.seed % 1000),
                                   (self.n, self.n))
        self.f = jax.jit(lambda a: a @ a.T)
        self.f(self.x).block_until_ready()

    def measure(self, seconds, tracer):
        tracer.start()
        t0, k = time.perf_counter(), 0
        with tracer.span("window"):
            while time.perf_counter() - t0 < seconds:
                self.y = self.f(self.x).block_until_ready()
                k += 1
        w = time.perf_counter() - t0
        tracer.stop()
        return {"attempted": k, "failed": 0,
                "end_to_end": {"calls_per_s": k / w},
                "facts": {"window_s": w, "calls": k}}

    def release(self):
        pass

    def check(self, record):
        import numpy as np
        x = np.asarray(self.x, np.float64)
        gap = float(np.abs(np.asarray(self.y) - x @ x.T).max())
        return [{"name": "max_abs_gap", "value": gap,
                 "limit": self.limits["max_abs_gap"],
                 "ok": gap <= self.limits["max_abs_gap"]}]
'''

EXTRA_READER = '''
def read(trace, facts, peaks, key):
    return facts.get(key)
'''


def _dump(path, doc):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench_root"))
    bench = os.path.join(root, "benchmark")
    for d in ("configs", "traffic", "metrics", "limits", "drivers",
              "readers"):
        shutil.copytree(os.path.join(BENCH, d), os.path.join(bench, d))
    before = {p: open(p, "rb").read() for p in glob.glob(
        os.path.join(bench, "*", "*"))}
    doc = _doc()

    def cfg(src, **over):
        with open(os.path.join(BENCH, "configs", src + ".json")) as f:
            c = json.load(f)
        c.update(TINY, **over)
        return c

    train = cfg("cerebras-gpt-1.3b-l6-train", n_positions=32)
    _dump(os.path.join(bench, "configs", "tiny-train.json"), train)
    f32 = json.loads(json.dumps(train))
    f32["program"]["amp"] = "none"
    _dump(os.path.join(bench, "configs", "tiny-train-f32.json"), f32)
    small = cfg("cerebras-gpt-1.3b-l6-train", **SMALL)
    _dump(os.path.join(bench, "configs", "small-train.json"), small)
    _dump(os.path.join(bench, "traffic", "small-b2s256.json"), {
        "driver": "train_lm", "batch": 2, "seq_len": 256, "check_steps": 3,
        "warmup_steps": 1, "steps_in_flight": 2, "trace_seconds": 1})
    serve = cfg("cerebras-gpt-1.3b-serve", n_positions=128)
    serve["program"].update(block_size=8, max_blocks=16, max_batch=4,
                            kv_blocks=80)
    _dump(os.path.join(bench, "configs", "tiny-serve.json"), serve)
    _dump(os.path.join(bench, "traffic", "tiny-b2s32.json"), {
        "driver": "train_lm", "batch": 2, "seq_len": 32, "check_steps": 3,
        "warmup_steps": 1, "steps_in_flight": 2, "trace_seconds": 1})
    _dump(os.path.join(bench, "traffic", "tiny-chat-c4.json"), {
        "driver": "serve_lm_closed", "clients": 4,
        "prompt_len": {"median": 24, "sigma": 0.7, "lo": 4, "hi": 64},
        "output_len": {"median": 12, "sigma": 0.5, "lo": 4, "hi": 24},
        "warmup_seconds": 0.5, "check_requests": 3, "trace_seconds": 1})
    _dump(os.path.join(bench, "traffic", "extra-mix.json"),
          {"driver": "extra_matmul", "n": 64})
    with open(os.path.join(bench, "drivers", "extra_matmul.py"), "w") as f:
        f.write(EXTRA_DRIVER)
    with open(os.path.join(bench, "readers", "fact.py"), "w") as f:
        f.write(EXTRA_READER)
    _dump(os.path.join(bench, "metrics", "calls.extra.json"),
          {"reader": "fact", "args": {"key": "calls"}})
    train_limits = {"loss_gap": 2e-3, "grad_norm_gap": 0.05,
                    "change_norm_gap": 0.08, "grad_elem_gap": 1.0,
                    "grad_elem_worst": 1.0}
    for cell in ("tiny-train", "tiny-train-f32"):
        _dump(os.path.join(bench, "limits", cell + ".json"),
              {"limits": train_limits})
    _dump(os.path.join(bench, "limits", "small-train.json"),
          {"limits": dict(train_limits, grad_elem_gap=SMALL_ELEM_LIMIT)})
    _dump(os.path.join(bench, "limits", "tiny-serve.json"),
          {"limits": {"logit_gap": 0.02, "logit_gap_mean": 1e-5,
                      "failed_requests": 0}})
    _dump(os.path.join(bench, "limits", "extra.json"),
          {"limits": {"max_abs_gap": 1e-2}})

    real_train, real_serve = (w["name"] for w in doc["workloads"])
    for name, file in (("small-train", "small-train"),
                       ("tiny-train", "tiny-train"),
                       ("tiny-train-f32", "tiny-train-f32"),
                       ("tiny-serve", "tiny-serve"),
                       ("extra-config", "tiny-train")):
        doc["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "test",
            "file": "benchmark/configs/%s.json" % file})
    for name, config, mix in (
            ("small-train", "small-train", "small-b2s256"),
            ("tiny-train", "tiny-train", "tiny-b2s32"),
            ("tiny-train-f32", "tiny-train-f32", "tiny-b2s32"),
            ("tiny-serve", "tiny-serve", "tiny-chat-c4"),
            ("extra", "extra-config", "extra-mix")):
        doc["workloads"].append({"name": name, "config": config,
                                 "traffic": mix, "chips": 1, "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if real_train in m.get("workloads", ()):
            m["workloads"] += ["small-train", "tiny-train",
                               "tiny-train-f32"]
        if real_serve in m.get("workloads", ()):
            m["workloads"].append("tiny-serve")
    doc["end_to_end"].append({
        "name": "calls_per_s", "unit": "1/s", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": ["extra"]})
    doc["per_layer"].append({
        "name": "calls.extra", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "test", "moves": "calls_per_s",
        "workloads": ["extra"]})
    _dump(os.path.join(root, "BENCHMARK.json"), doc)
    for p, content in before.items():
        assert open(p, "rb").read() == content, "edited a file: %s" % p
    return root


def _run(root, cell, seed=2**31 + 11, seconds=0.5, trace=0, **kw):
    import jax

    return bench_run.run_cell(
        Spec(root), cell, seed, seconds, trace, jax.devices()[:1],
        trace_dir=os.path.join(root, ".bench_trace"), peaks=V5E, **kw)


def _assert_result_shape(res, trace):
    keys = list(res)
    assert keys[-1] == "compared", keys
    want = RESULT_KEYS + (["breakdown"] if trace else [])
    assert keys[:-1] == want, keys
    dev_keys = {"platform", "kind", "count", "memory_peak_bytes"}
    if trace:
        dev_keys |= {"busy_s", "window_s"}
    assert set(res["device"]) == dev_keys
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)
    for c in res["compared"]:
        assert set(c) == {"name", "value", "limit"}
    json.dumps(res)


def test_a_cell_a_config_a_driver_and_a_metric_are_added_as_files(tiny_root):
    res = _run(tiny_root, "extra", trace=0)
    _assert_result_shape(res, trace=False)
    assert res["correct"] and set(res["metrics"]) == {"calls_per_s",
                                                      "setup_s"}
    res = _run(tiny_root, "extra", trace=1)
    _assert_result_shape(res, trace=True)
    assert set(res["metrics"]) == {"calls.extra"}
    # the real cells are still there, untouched
    assert Spec(tiny_root).cell("cgpt13b-train-b2s2048")["chips"] == 1


@pytest.mark.parametrize("cell", ["tiny-train", "tiny-train-f32"])
def test_train_driver_rehearsal_ties_the_reference_to_the_program(
        tiny_root, cell):
    """The program's loss, first gradient and three-step change through
    Executor.prepare/run_prepared against lib/reference.py with the same
    seeded weights; in float32 they agree to rounding."""
    res = _run(tiny_root, cell)
    _assert_result_shape(res, trace=False)
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    got = {c["name"]: c["value"] for c in res["compared"]}
    assert set(got) == {"loss_gap", "grad_norm_gap", "change_norm_gap",
                        "grad_elem_gap", "grad_elem_worst"}
    if cell == "tiny-train-f32":
        assert got["loss_gap"] < 1e-5 and got["grad_norm_gap"] < 1e-3
        assert got["change_norm_gap"] < 1e-2
        assert got["grad_elem_worst"] < 1e-3


def test_serve_driver_rehearsal_ties_the_reference_to_the_engine(tiny_root):
    """Prefill then paged decode at batch above one: every served token
    is the reference's own first choice (or within rounding of it)."""
    res = _run(tiny_root, "tiny-serve", seconds=1.0)
    _assert_result_shape(res, trace=False)
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                   "setup_s"}
    assert res["attempted"] > 10 and res["failed"] == 0


def test_traced_rehearsal_reports_per_layer_metrics_only(tiny_root):
    res = _run(tiny_root, "tiny-serve", seconds=1.0, trace=1)
    _assert_result_shape(res, trace=True)
    # no device plane on the CPU: the trace readers find nothing and the
    # metrics are left out; counters and the host clock still read
    assert "decode_occupancy.serve" in res["metrics"]
    assert "compiles_in_window.serve" in res["metrics"]
    assert res["metrics"]["ttft_p95_ms.serve"]["value"] > 0
    assert "paged_attn_roofline.serve" not in res["metrics"]
    assert "device_idle.serve" not in res["metrics"]
    assert 0 < res["metrics"]["decode_occupancy.serve"]["value"] <= 100


# ---------------------------------------------------------------------------
# the timed path broken underneath: ``correct`` has to come out false
# ---------------------------------------------------------------------------

def _broken(monkeypatch, driver, method, wrap):
    """Every later load of ``driver`` gets ``method`` wrapped (the
    harness loads a driver's module afresh for each run)."""
    real = Spec.driver

    def patched(self, name):
        mod = real(self, name)
        if name == driver:
            setattr(mod.Driver, method, wrap(getattr(mod.Driver, method)))
        return mod

    monkeypatch.setattr(Spec, "driver", patched)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch, tiny_root):
    def wrap(real):
        state = {}

        def step(self):
            # the first call runs (so there is a loss to return); later
            # ones return it again and leave the state where it was
            if "out" not in state:
                state["out"] = real(self)
            return state["out"]
        return step

    _broken(monkeypatch, "train_lm", "_step", wrap)
    res = _run(tiny_root, "tiny-train-f32")
    assert not res["correct"]
    got = {c["name"]: c["value"] for c in res["compared"]}
    assert got["change_norm_gap"] > 0.5       # one step where three are due


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch, tiny_root):
    def wrap(real):
        def feed(self):
            toks, labs = real(self)
            return toks[:1], labs[:1]      # the mean is over the rest
        return feed

    _broken(monkeypatch, "train_lm", "_feed_arrays", wrap)
    res = _run(tiny_root, "tiny-train-f32")
    assert not res["correct"]
    got = {c["name"]: c["value"] for c in res["compared"]}
    assert got["grad_norm_gap"] > 0.1


def test_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch, tiny_root):
    def wrap(real):
        def generate(self, prompt, n_out):
            res = dict(real(self, prompt, n_out))
            toks = list(res["tokens"])
            toks[len(toks) // 2] = (toks[len(toks) // 2] + 1) % 211
            res["tokens"] = toks
            return res
        return generate

    _broken(monkeypatch, "serve_lm_closed", "_generate", wrap)
    res = _run(tiny_root, "tiny-serve", seconds=0.5)
    assert not res["correct"]
    got = {c["name"]: c["value"] for c in res["compared"]}
    assert got["logit_gap"] > 0.02


def test_a_request_that_fails_is_not_correct(monkeypatch, tiny_root):
    def wrap(real):
        def generate(self, prompt, n_out):
            if len(prompt) % 5 == 0:
                raise RuntimeError("dropped")
            return real(self, prompt, n_out)
        return generate

    _broken(monkeypatch, "serve_lm_closed", "_generate", wrap)
    res = _run(tiny_root, "tiny-serve", seconds=0.5)
    assert not res["correct"] and res["failed"] > 0


# ---------------------------------------------------------------------------
# the lower-precision controls, at a size a test run can hold
# ---------------------------------------------------------------------------

def test_training_control_in_fp8_is_not_correct(tiny_root):
    """At the smallest size where precisions read apart: the program
    (bf16 AMP through the executor) is correct, and the reference put in
    its place and computed by the fp8 recipe (the step below bf16) is
    not — by the first gradient read element by element, the one number
    a lower precision moves by three times or more."""
    res = _run(tiny_root, "small-train", seconds=0.2, controls=("fp8",))
    assert res["correct"], res["compared"]
    assert not res["controls"]["fp8"]["correct"], res["controls"]
    spec = Spec(tiny_root)
    cell = spec.cell("small-train")
    sizes = spec.config(cell)["sizes"]
    limits = spec.limits(cell)
    compare = spec.driver("train_lm").compare
    import jax.numpy as jnp

    for seed in (1, 2, 3):
        toks, labs = traffic.lm_batch(seed, 2, 256, sizes["vocab_size"])
        t = jnp.asarray(toks, jnp.int32)
        lab = jnp.asarray(labs[..., 0], jnp.int32)
        ref = reference.train_readings(seed, sizes, True, t, lab, "relu",
                                       1e-3, 3)
        fp8 = reference.train_readings(seed, sizes, True, t, lab, "relu",
                                       1e-3, 3, precision="fp8")
        verdict = {v["name"]: v for v in check.verdicts(compare(fp8, ref),
                                                        limits)}
        assert not verdict["grad_elem_gap"]["ok"], (seed, verdict)


def test_serving_control_in_bf16_is_not_correct(tiny_root):
    """At each position of the same tokens, the token that whole
    bfloat16 (the step below the configuration's float32) puts first:
    over some hundreds of tokens the mean gap by which it lies below the
    reference's best passes the limit the rehearsed cell is held to, and
    the harness's comparison says not correct; int8 fails the widest
    gap too.  The reference's own first choices read 0."""
    limits = Spec(tiny_root).limits({"name": "tiny-serve"})
    sizes = dict(d_model=256, n_heads=2, n_layers=2, d_ff=1024,
                 vocab_size=2048, n_positions=256)
    for seed in (1, 2, 3):
        params = reference.init_params(seed, sizes, False)
        verdict = {}
        for c in ("highest", "bf16", "int8"):
            gaps = []
            for k in range(3):
                seq = np.random.default_rng([seed, k]).integers(
                    0, 2048, 256).tolist()
                gaps.append(reference.served_gaps(
                    params, seq[:64], seq[64:], 256, 192, 2, "gelu_tanh",
                    control=c))
            gaps = np.concatenate(gaps)
            verdict[c] = {v["name"]: v for v in check.verdicts(
                {"logit_gap": gaps.max(), "logit_gap_mean": gaps.mean(),
                 "failed_requests": 0}, limits)}
        assert all(v["ok"] and v["value"] == 0.0
                   for v in verdict["highest"].values())
        assert not verdict["bf16"]["logit_gap_mean"]["ok"], (seed, verdict)
        assert not verdict["int8"]["logit_gap_mean"]["ok"], (seed, verdict)
        assert not verdict["int8"]["logit_gap"]["ok"], (seed, verdict)


def test_the_serving_controls_come_out_not_correct_through_the_harness(
        tiny_root):
    """``run_cell(controls=...)``, which ``controls.py`` drives: the
    run itself is correct and the control put in its place is not."""
    res = _run(tiny_root, "tiny-serve", seconds=1.0, controls=("int8",))
    assert res["correct"], res["compared"]
    assert list(res)[-1] == "compared"
    assert not res["controls"]["int8"]["correct"], res["controls"]
    assert reference.padded(90, 960) == 256
    assert reference.padded(600, 960) == 768
    assert reference.padded(800, 960) == 960


# ---------------------------------------------------------------------------
# the command itself
# ---------------------------------------------------------------------------

def test_the_command_fails_for_want_of_a_tpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    doc = _doc()
    proc = subprocess.run(
        [sys.executable] + doc["command"][1:] + [
            "--workload", doc["workloads"][0]["name"], "--seed",
            str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""
    assert "TPU" in proc.stderr


def test_an_unknown_workload_is_refused(spec):
    with pytest.raises(SpecError, match="unknown workload"):
        spec.cell("no-such-cell")
