"""The ``nemotron_h`` serving cell's files and driver, rehearsed on the
CPU at a tiny size in a temporary benchmark root that adds files beside
copies of the real ones; a number from these runs is never a device
metric."""
import glob
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run                     # noqa: E402
from benchmark.lib import counts_nemotron_h as counts      # noqa: E402
from benchmark.lib import reduce                           # noqa: E402
from benchmark.lib.peaks import DEVICE_PEAKS               # noqa: E402
from benchmark.lib.spec import Spec                        # noqa: E402

V5E = DEVICE_PEAKS["TPU v5 lite"]
CELL = "nemotron3s-ep4-serve-reason-c64"
CONFIG = "nemotron-3-super-ep4-l11-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
            ssm_state_size=16, chunk_size=8, moe_latent_size=32,
            moe_intermediate_size=48, moe_shared_expert_intermediate_size=80,
            num_experts_per_tok=3, vocab_size=96, n_routed_experts=4,
            num_hidden_layers=5)


@pytest.fixture(scope="module")
def spec():
    return Spec(ROOT)


@pytest.fixture(scope="module")
def config(spec):
    return spec.config(spec.cell(CELL))


# ---------------------------------------------------------------------------
# the configuration's file
# ---------------------------------------------------------------------------

def test_every_published_width_is_in_the_file(config):
    want = dict(hidden_size=4096, num_attention_heads=32,
                num_key_value_heads=2, head_dim=128, mamba_num_heads=128,
                mamba_head_dim=64, n_groups=8, ssm_state_size=128,
                conv_kernel=4, moe_latent_size=1024,
                moe_intermediate_size=2688,
                moe_shared_expert_intermediate_size=5376,
                num_experts_per_tok=22, routed_scaling_factor=5,
                chunk_size=128)
    for key, value in want.items():
        assert config[key] == value == config["published"][key], key
    assert config["published"]["n_routed_experts"] == 512
    assert config["published"]["vocab_size"] == 131072
    assert config["published"]["num_hidden_layers"] == 88
    for key in ("deployment", "assumed", "departures", "precision",
                "program", "stage"):
        assert config[key], key


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_the_file_holds_the_catalog_rows_numbers_but_the_reduced(config):
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    for key, value in row["config"].items():
        assert config["published"][key] == value, key
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert sorted(config["reduced"]) == ["n_routed_experts",
                                         "num_hidden_layers", "vocab_size"]


def test_the_stage_is_a_whole_period_of_the_published_pattern(config):
    stage, pattern = config["stage"], config["hybrid_override_pattern"]
    first, last = stage["layers"]
    assert pattern[first:last + 1] == stage["pattern"] == "MEMEMEMEM*E"
    assert len(pattern) == config["published"]["num_hidden_layers"]
    assert len(stage["pattern"]) == config["num_hidden_layers"]
    # 5 : 5 : 1 as the model's 40 : 40 : 8
    assert [pattern.count(k) for k in "ME*"] == [40, 40, 8]
    assert [stage["pattern"].count(k) for k in "ME*"] == [5, 5, 1]
    held = stage["experts_held"]
    assert held[1] - held[0] + 1 == config["n_routed_experts"] == 128
    assert stage["vocab_held"] == [0, config["vocab_size"] - 1]
    # the guide's floors: 8 experts, an eighth of the vocabulary
    assert config["n_routed_experts"] >= 8
    assert 8 * config["vocab_size"] >= config["published"]["vocab_size"]


def test_the_traffic_is_the_issues(spec):
    mix = spec.traffic(spec.cell(CELL))
    assert mix["clients"] == 64 and mix["check_requests"] == 16
    assert mix["prompt_len"] == {"median": 128, "sigma": 0.6, "lo": 64,
                                 "hi": 512}
    assert mix["output_len"] == {"median": 512, "sigma": 0.4, "lo": 256,
                                 "hi": 1024}
    program = spec.config(spec.cell(CELL))["program"]
    longest = mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
    assert longest <= program["block_size"] * program["max_blocks"]
    # every caller at the longest request fits the pool: no preemption
    assert mix["clients"] * -(-longest // program["block_size"]) \
        <= program["kv_blocks"] - 1
    assert mix["clients"] == program["max_batch"]


# ---------------------------------------------------------------------------
# the counts, by hand
# ---------------------------------------------------------------------------

def test_counts_by_hand(config):
    s = counts.shape(config)
    assert counts.mamba_params(s) == 4096 * 18560 + 8192 * 4096
    assert round(counts.mamba_params(s) / 1e6, 1) == 109.6
    assert counts.attention_params(s) == 4096 * 36 * 128 + 4096 * 4096
    assert counts.expert_params(s) == 2 * 1024 * 2688
    assert counts.expert_bytes(s) == 11010048
    # a token meets 22 of 512 experts, 128 of them here: 5.5 on average
    per_token = counts.moe_params_per_token(s)
    assert per_token == pytest.approx(
        4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
        + 5.5 * 2 * 1024 * 2688)
    # the state of one row, read and written in 5 layers
    assert counts.state_bytes_per_row(s) == 5 * 2 * 4 * 128 * 64 * 128
    one = counts.decode_flops(s, 1)
    assert counts.decode_flops(s, 101) - one == 4 * 32 * 128 * 100
    assert counts.prefill_flops(s, 1) == one
    # the whole model by the same equations: 120.7B parameters, 12.8B
    # of them multiplied with a token (the model's own name)
    whole = dict(s, held=512, pattern=config["hybrid_override_pattern"],
                 vocab=131072)
    total = (40 * (counts.mamba_params(whole) + 10240 * 4)
             + 8 * counts.attention_params(whole)
             + 40 * (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
                     + 512 * counts.expert_params(whole))
             + 2 * 4096 * 131072)
    assert round(total / 1e9, 1) == 120.7
    # (the name counts both vocabulary matrices)
    active = counts.matmul_params_per_token(whole) + 2 * 4096 * 131072
    assert round(active / 1e9, 1) == 12.8


def test_the_bytes_roofline_reader_on_the_hand_made_trace(spec):
    """The run [50,500) of jit_step holds the kernel %k.1 for 200 ns;
    work that needs 100 ns at the peak reads 50%."""
    tr = reduce.Trace(reduce.load(os.path.join(DATA,
                                               "hand_made.xplane.pbtxt")))
    roof = spec.reader("kernel_bytes_roofline")
    facts = {"kernel_work": {"k": {"bytes": 819e9 * 100e-9, "flops": 0}}}
    args = dict(work="k", events="^%k[.0-9]* = ", modules="^jit_step")
    assert roof.read(tr, facts, V5E, **args) == pytest.approx(50.0)
    flops = {"kernel_work": {"k": {"flops": 197e12 * 150e-9, "bytes": 1}}}
    assert roof.read(tr, flops, V5E, **args) == pytest.approx(75.0)
    # nothing to read: no such kernel (a parent without it), no work
    # counted, or the kernel outside the executable named
    assert roof.read(tr, facts, V5E, **dict(args, events="^%absent")) is None
    assert roof.read(tr, {}, V5E, **args) is None
    assert roof.read(tr, {"kernel_work": {"k": {"bytes": 0, "flops": 0}}},
                     V5E, **args) is None
    assert roof.read(tr, facts, V5E, **dict(args, modules="^jit_other")) \
        is None


# ---------------------------------------------------------------------------
# the driver, at a tiny size
# ---------------------------------------------------------------------------

def _dump(path, doc):
    with open(path, "w") as f:
        json.dump(doc, f)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("nemotron_root"))
    bench = os.path.join(root, "benchmark")
    for d in ("configs", "traffic", "metrics", "limits", "drivers",
              "readers"):
        shutil.copytree(os.path.join(BENCH, d), os.path.join(bench, d))
    before = {p: open(p, "rb").read() for p in glob.glob(
        os.path.join(bench, "*", "*")) if os.path.isfile(p)}
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["published"] = dict(cfg["published"], n_routed_experts=8)
    cfg["stage"] = {"layers": [0, 4], "pattern": "MEM*E",
                    "experts_held": [2, 5], "vocab_held": [0, 95]}
    cfg["program"] = {"block_size": 8, "max_blocks": 16, "max_batch": 4,
                      "kv_blocks": 80}
    _dump(os.path.join(bench, "configs", "tiny-hybrid.json"), cfg)
    _dump(os.path.join(bench, "traffic", "tiny-reason-c4.json"), {
        "driver": "serve_nemotron_h_closed", "clients": 4,
        "prompt_len": {"median": 12, "sigma": 0.6, "lo": 4, "hi": 40},
        "output_len": {"median": 24, "sigma": 0.4, "lo": 8, "hi": 48},
        "warmup_seconds": 0.5, "check_requests": 3, "trace_seconds": 1})
    _dump(os.path.join(bench, "limits", "tiny-hybrid.json"),
          {"limits": {"logit_gap": 0.02, "logit_gap_mean": 1e-3,
                      "failed_requests": 0}})
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "tiny-hybrid", "source": "test",
                           "reduced": [], "why": "test",
                           "file": "benchmark/configs/tiny-hybrid.json"})
    doc["workloads"].append({"name": "tiny-hybrid", "config": "tiny-hybrid",
                             "traffic": "tiny-reason-c4", "chips": 1,
                             "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-hybrid")
    _dump(os.path.join(root, "BENCHMARK.json"), doc)
    for p, content in before.items():
        assert open(p, "rb").read() == content, "edited a file: %s" % p
    return root


def _run(root, seed=2**31 + 17, seconds=1.0, trace=0, **kw):
    import jax

    return bench_run.run_cell(
        Spec(root), "tiny-hybrid", seed, seconds, trace, jax.devices()[:1],
        trace_dir=os.path.join(root, ".bench_trace"), peaks=V5E, **kw)


def test_rehearsal_ties_the_reference_to_the_served_model(tiny_root):
    """Prefill into a slot and pages, then decode at batch above one
    through the state update and the grouped experts: every served token
    is the reference's first choice or within rounding of it."""
    res = _run(tiny_root)
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                   "setup_s"}
    assert res["attempted"] > 4 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    json.dumps(res)


def test_traced_rehearsal_reports_the_cells_per_layer_metrics(tiny_root):
    res = _run(tiny_root, trace=1)
    got = res["metrics"]
    # the counters read on the CPU; the device-trace readers find no
    # device plane here and their metrics are left out
    for name in ("moe_experts_hit_share.serve", "decode_occupancy.serve",
                 "decode_ahead_share.serve", "prefill_share.serve",
                 "compiles_in_window.serve", "mfu.serve"):
        assert name in got, (name, sorted(got))
    assert 0 < got["moe_experts_hit_share.serve"]["value"] <= 100
    for name in ("moe_decode_roofline.serve", "ssm_decode_roofline.serve",
                 "device_idle.serve", "paged_attn_roofline.serve"):
        assert name not in got


def _broken(monkeypatch, method, wrap):
    real = Spec.driver

    def patched(self, name):
        mod = real(self, name)
        if name == "serve_nemotron_h_closed":
            setattr(mod.Driver, method, wrap(getattr(mod.Driver, method)))
        return mod

    monkeypatch.setattr(Spec, "driver", patched)


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch,
                                                             tiny_root):
    def wrap(real):
        def generate(self, prompt, n_out):
            res = dict(real(self, prompt, n_out))
            toks = list(res["tokens"])
            toks[len(toks) // 2] = (toks[len(toks) // 2] + 1) % 96
            res["tokens"] = toks
            return res
        return generate

    _broken(monkeypatch, "_generate", wrap)
    res = _run(tiny_root, seconds=0.5)
    assert not res["correct"]
    got = {c["name"]: c["value"] for c in res["compared"]}
    assert got["logit_gap"] > 0.02


def test_a_request_that_fails_is_not_correct(monkeypatch, tiny_root):
    def wrap(real):
        def generate(self, prompt, n_out):
            if len(prompt) % 3 == 0:
                raise RuntimeError("dropped")
            return real(self, prompt, n_out)
        return generate

    _broken(monkeypatch, "_generate", wrap)
    res = _run(tiny_root, seconds=0.5)
    assert not res["correct"] and res["failed"] > 0


@pytest.mark.parametrize("control,fails", [
    ("altered_token", "logit_gap"), ("bf16", None), ("int8_products", None),
    ("int8_experts", None)])
def test_a_control_is_held_to_the_cells_own_limits(tiny_root, control,
                                                   fails):
    """``run_cell(controls=...)``, which ``controls.py`` drives: the run
    is correct; the control's own first choices (or the altered tokens)
    are read in the served tokens' place and held to the same limits.
    The altered token comes out not correct; what a lower precision
    reads at this size (87 tokens, 5 blocks of width 64) is the chip's
    to say at the cell's own, so only its verdict's arithmetic is held."""
    res = _run(tiny_root, seconds=0.5, controls=(control,))
    assert res["correct"], res["compared"]
    held = res["controls"][control]
    limits = {c["name"]: c["limit"] for c in res["compared"]}
    assert set(held["compared"]) == set(limits)
    over = {k for k, v in held["compared"].items() if v > limits[k]}
    assert held["correct"] == (not over)
    if fails:
        assert fails in over and not held["correct"], held
