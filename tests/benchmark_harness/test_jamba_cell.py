"""The ``jamba`` serving cell's files and driver, rehearsed on the CPU
at a tiny size in a temporary benchmark root that adds files beside
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
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run                     # noqa: E402
from benchmark.lib import counts_jamba as counts           # noqa: E402
from benchmark.lib.peaks import DEVICE_PEAKS               # noqa: E402
from benchmark.lib.spec import Spec                        # noqa: E402

V5E = DEVICE_PEAKS["TPU v5 lite"]
CELL = "jamba2-3b-serve-reason-c128"
CONFIG = "ai21-jamba2-3b-serve"
# AI21-Jamba2-3B's config.json as published: every key of the model
# catalog's row
PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
    "sliding_window": None, "tie_word_embeddings": True,
    "use_mamba_kernels": True, "vocab_size": 65536,
}
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=1,
            mamba_d_state=16, mamba_dt_rank=8, intermediate_size=96,
            vocab_size=96, num_hidden_layers=5, attn_layer_offset=2,
            attn_layer_period=4)


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
    want = dict(hidden_size=2560, num_attention_heads=20,
                num_key_value_heads=1, intermediate_size=8192,
                mamba_d_state=16, mamba_dt_rank=160, mamba_expand=2,
                mamba_d_conv=4, num_hidden_layers=28, vocab_size=65536,
                attn_layer_offset=7, attn_layer_period=14, num_experts=1,
                rms_norm_eps=1e-6, tie_word_embeddings=True)
    for key, value in want.items():
        assert config[key] == value == config["published"][key], key
    assert config["reduced"] == []
    for key in ("deployment", "assumed", "precision", "program", "init"):
        assert config[key], key
    assert config["departures"] == []


def test_the_file_holds_the_published_config_whole(config):
    assert config["source"] == (
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json")
    assert config["published"] == PUBLISHED
    for key, value in PUBLISHED.items():
        assert config[key] == value, key


def test_the_layer_pattern_is_attention_at_7_and_21(config):
    s = counts.shape(config)
    assert [i for i, k in enumerate(s["pattern"]) if k == "*"] == [7, 21]
    assert s["pattern"].count("M") == 26 and s["head_dim"] == 128
    assert s["d_inner"] == 5120


def test_the_traffic_is_the_issues(spec):
    mix = spec.traffic(spec.cell(CELL))
    assert mix["driver"] == "serve_jamba_closed"
    assert mix["clients"] == 128 and mix["check_requests"] == 16
    assert mix["warmup_seconds"] == 8 and mix["trace_seconds"] == 5
    assert mix["prompt_len"] == {"median": 256, "sigma": 0.7, "lo": 64,
                                 "hi": 1024}
    assert mix["output_len"] == {"median": 768, "sigma": 0.5, "lo": 256,
                                 "hi": 2048}
    program = spec.config(spec.cell(CELL))["program"]
    longest = mix["prompt_len"]["hi"] + mix["output_len"]["hi"]
    assert longest == 3072 == program["block_size"] * program["max_blocks"]
    # every caller at the longest request fits the pool, with the
    # scratch block: no preemption
    assert mix["clients"] * -(-longest // program["block_size"]) \
        == program["kv_blocks"] - 1
    assert mix["clients"] == program["max_batch"]


# ---------------------------------------------------------------------------
# the counts, by hand
# ---------------------------------------------------------------------------

def test_counts_by_hand(config):
    s = counts.shape(config)
    # in_proj, x_proj, dt_proj, out_proj; conv, dt_bias, A_log, D, norms
    assert counts.mamba_params(s) == (2560 * 10240 + 5120 * 192
                                      + 160 * 5120 + 5120 * 2560)
    assert counts.mamba_params(s) + counts.mamba_vectors(s) == 41241792
    assert counts.layer_params(s, "M") == 104161472
    assert counts.layer_params(s, "*") == 76682240
    assert counts.attention_params(s) == 13762560
    assert counts.mlp_params(s) == 62914560
    total = counts.total_params(s)
    assert total == 26 * 104161472 + 2 * 76682240 + 65536 * 2560 + 2560
    assert round(total / 1e9, 2) == 3.03
    # 2 x 2.86B of layer matmuls a token, and the tied head
    assert round(counts.matmul_params_per_token(s) / 1e9, 2) == 2.86
    one = counts.decode_flops(s, 1)
    assert counts.decode_flops(s, 101) - one == 4 * 20 * 128 * 100 * 2
    assert counts.prefill_flops(s, 1) == one
    # a row's state, read and written, its x, dt, B, C, y and its conv
    # window in and out, 26 layers
    assert counts.state_bytes_per_row(s) == 26 * 4 * (
        2 * 16 * 5120 + 3 * 5120 + 2 * 16 + 2 * 3 * 5120)
    assert counts.scan_bytes_per_token(s) == 26 * 4 * (3 * 5120 + 2 * 16)
    assert counts.scan_flops_per_token(s) == 26 * 7 * 5120 * 16


# ---------------------------------------------------------------------------
# the driver, at a tiny size
# ---------------------------------------------------------------------------

def _dump(path, doc):
    with open(path, "w") as f:
        json.dump(doc, f)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("jamba_root"))
    bench = os.path.join(root, "benchmark")
    for d in ("configs", "traffic", "metrics", "limits", "drivers",
              "readers"):
        shutil.copytree(os.path.join(BENCH, d), os.path.join(bench, d))
    before = {p: open(p, "rb").read() for p in glob.glob(
        os.path.join(bench, "*", "*")) if os.path.isfile(p)}
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg.update(TINY)
    cfg["published"] = dict(cfg["published"], **TINY)
    cfg["program"] = {"block_size": 8, "max_blocks": 16, "max_batch": 4,
                      "kv_blocks": 80}
    _dump(os.path.join(bench, "configs", "tiny-jamba.json"), cfg)
    _dump(os.path.join(bench, "traffic", "tiny-reason-c4.json"), {
        "driver": "serve_jamba_closed", "clients": 4,
        "prompt_len": {"median": 12, "sigma": 0.6, "lo": 4, "hi": 40},
        "output_len": {"median": 24, "sigma": 0.4, "lo": 8, "hi": 48},
        "warmup_seconds": 0.5, "check_requests": 3, "trace_seconds": 1})
    _dump(os.path.join(bench, "limits", "tiny-jamba.json"),
          {"limits": {"logit_gap": 0.02, "logit_gap_mean": 1e-3,
                      "failed_requests": 0}})
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    doc["configs"].append({"name": "tiny-jamba", "source": "test",
                           "reduced": [], "why": "test",
                           "file": "benchmark/configs/tiny-jamba.json"})
    doc["workloads"].append({"name": "tiny-jamba", "config": "tiny-jamba",
                             "traffic": "tiny-reason-c4", "chips": 1,
                             "why": "test"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-jamba")
    _dump(os.path.join(root, "BENCHMARK.json"), doc)
    for p, content in before.items():
        assert open(p, "rb").read() == content, "edited a file: %s" % p
    return root


def _run(root, seed=2**31 + 17, seconds=1.0, trace=0, **kw):
    import jax

    return bench_run.run_cell(
        Spec(root), "tiny-jamba", seed, seconds, trace, jax.devices()[:1],
        trace_dir=os.path.join(root, ".bench_trace"), peaks=V5E, **kw)


CONTROLS = ("altered_token", "bf16", "bf16_state", "int8_products")


@pytest.fixture(scope="module")
def plain_run(tiny_root):
    """One run with every control of the reference put in the program's
    place after it (as ``controls.py`` drives them), and the facts its
    window reduced to."""
    seen = {}
    real = Spec.driver

    def patched(self, name):
        mod = real(self, name)
        reduce_ = mod.Driver._reduce

        def keep(drv, *a):
            out = reduce_(drv, *a)
            seen.update(out["facts"], shape=drv.shape)
            return out
        mp.setattr(mod.Driver, "_reduce", keep)
        return mod

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(Spec, "driver", patched)
        return _run(tiny_root, controls=CONTROLS), seen


def test_rehearsal_ties_the_reference_to_the_served_model(plain_run):
    """Prefill into a slot and pages through the prompt scan, then
    decode at batch above one through the state update and the pages:
    every served token is the reference's first choice or within
    rounding of it."""
    res, _ = plain_run
    assert res["correct"], res["compared"]
    assert set(res["metrics"]) == {"serve_tokens_per_s", "itl_p95_ms",
                                   "setup_s"}
    assert res["attempted"] > 4 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    json.dumps(res)


def test_the_kernels_work_is_what_the_steps_counted(plain_run):
    """The two roofline shares' work: the decode steps' bucket rows
    times a row's state traffic, and the prompt tokens scanned (every
    prompt of the window, riding or waited) times a token's."""
    _, facts = plain_run
    work, c, s = facts["kernel_work"], facts["counters"], facts["shape"]
    assert c["serve_scan_tokens_total"] > 0
    assert work["selective_scan"]["bytes"] == c[
        "serve_scan_tokens_total"] * counts.scan_bytes_per_token(s)
    assert work["mamba1_decode"]["bytes"] == c[
        "serve_decode_slots_total"] * counts.state_bytes_per_row(s) > 0


@pytest.mark.parametrize("control", CONTROLS)
def test_a_control_is_held_to_the_cells_own_limits(plain_run, control):
    """The control's own first choices (or the altered tokens) are read
    in the served tokens' place and held to the same limits.  The
    altered token comes out not correct; what a lower precision reads at
    this size is the chip's to say at the cell's own, so only its
    verdict's arithmetic is held."""
    res, _ = plain_run
    held = res["controls"][control]
    limits = {c["name"]: c["limit"] for c in res["compared"]}
    assert set(held["compared"]) == set(limits)
    over = {k for k, v in held["compared"].items() if v > limits[k]}
    assert held["correct"] == (not over)
    if control == "altered_token":
        assert "logit_gap" in over and not held["correct"], held


def test_traced_rehearsal_reports_the_cells_per_layer_metrics(tiny_root):
    res = _run(tiny_root, trace=1)
    got = res["metrics"]
    # the counters read on the CPU; the device-trace readers find no
    # device plane here and their metrics are left out
    for name in ("decode_occupancy.serve", "decode_ahead_share.serve",
                 "compiles_in_window.serve", "mfu.serve",
                 "decode_step_ms.serve", "ride_wait_ms.serve"):
        assert name in got, (name, sorted(got))
    for name in ("mamba1_decode_roofline.serve",
                 "selective_scan_roofline.serve", "device_idle.serve"):
        assert name not in got
    # nor does the cell read what belongs to the other models
    for name in ("moe_experts_hit_share.serve", "ssm_decode_roofline.serve",
                 "prefill_share.serve", "ttft_p95_ms.serve"):
        assert name not in got


def _broken(monkeypatch, method, wrap):
    real = Spec.driver

    def patched(self, name):
        mod = real(self, name)
        if name == "serve_jamba_closed":
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
