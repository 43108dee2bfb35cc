"""What the chip will be asked to compile, checked on the CPU.

1. Every Pallas entry point is exported for ``platforms=["tpu"]`` at
   the shapes chip_smoke.py runs (and at tiny_lm test shapes): this runs
   the Pallas -> Mosaic lowering — not libtpu's compile — and is the
   check that would have caught a kernel Mosaic cannot express
   (the head-batched mat-vec paged_attention used to be; the strided
   conv stage still is, and must say so by taking its XLA route).
2. chip_smoke.py's phase bodies are rehearsed at toy width with the
   kernels interpreted, so chip time is not spent finding typos.
3. chip_smoke.py has no CPU mode.
"""
import functools
import importlib.util
import json
import os
import re
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import pytest
from jax import export

from paddle_tpu.core.place import placed_on
from paddle_tpu.kernels import dispatch
from paddle_tpu.kernels.conv_fused import conv2d_nhwc
from paddle_tpu.kernels.flash_attention import (
    flash_attention, flash_attention_bwd, flash_attention_chunk,
    flash_attention_fwd_lse, paged_attention)
from paddle_tpu.kernels.fused import fused_softmax_cross_entropy
from paddle_tpu.kernels.matmul_fused import (add_ln, matmul_epilogue,
                                             matmul_int8_dequant)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TPU = types.SimpleNamespace(platform="tpu")
sds = jax.ShapeDtypeStruct
bf16, f32, i32, i8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8


def tpu_module(fn, *specs):
    """StableHLO text of ``fn`` lowered for a TPU-placed computation."""
    def placed(*args):
        with placed_on(TPU):
            return fn(*args)

    return export.export(jax.jit(placed), platforms=["tpu"])(
        *specs).mlir_module()


def mosaic_calls(fn, *specs):
    """Kernel names of the Mosaic custom calls in ``fn`` lowered for a
    TPU-placed computation."""
    return re.findall(r'@tpu_custom_call\(.*?kernel_name = "(\w+)"',
                      tpu_module(fn, *specs))


# the transformer train step's attention: B16 H8 T2048 D128 bf16
QKV = sds((16, 8, 2048, 128), bf16)
LSE = sds((16, 8, 2048), f32)


def test_flash_train_kernels_lower():
    def fwd_bwd(q, k, v):
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
        return vjp(out)

    assert mosaic_calls(fwd_bwd, QKV, QKV, QKV) == [
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]
    assert mosaic_calls(
        lambda q, k, v: flash_attention_fwd_lse(q, k, v, causal=True),
        QKV, QKV, QKV) == ["flash_fwd"]
    assert mosaic_calls(
        lambda q, k, v, o, lse, do: flash_attention_bwd(
            q, k, v, o, lse, do, causal=True),
        QKV, QKV, QKV, QKV, LSE, QKV) == ["flash_bwd_dq", "flash_bwd_dkv"]


def test_flash_chunk_lowers():
    q = sds((2, 8, 2048, 128), bf16)
    m = sds((2, 8, 2048), f32)
    acc = sds((2, 8, 2048, 128), f32)
    assert mosaic_calls(
        lambda q, k, v, m, l, acc: flash_attention_chunk(
            q, k, v, m, l, acc, causal=True),
        q, q, q, m, m, acc) == ["_chunk_kernel"]


@pytest.mark.parametrize("s_len", [16, 512, 2048])
def test_prefill_flash_lowers(s_len):
    # the serving prefill ladder: one sequence, fp32, [1, H, S, D]
    q = sds((1, 8, s_len, 128), f32)
    assert mosaic_calls(
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        q, q, q) == ["flash_fwd"]


@pytest.mark.parametrize("b,h,d,n,bs,nb,lead,layer", [
    (8, 8, 128, 256, 16, 128, (), None),  # one layer's pool, no layer=
    (1, 8, 128, 256, 16, 32, (), None),
    # every layer's pool whole, as the engine hands it over
    (16, 16, 128, 1024, 16, 64, (24,), 11),   # cgpt13b-serve-chat-c16
    (16, 16, 128, 1024, 16, 128, (24,), 23),
    (256, 16, 128, 1024, 16, 128, (24,), 0),  # its suffix prefill
    (8, 8, 128, 256, 16, 128, (2,), 0),       # chip_smoke decode
    (1, 8, 128, 256, 16, 32, (2,), 1),        # solo request, tight bucket
])
def test_paged_attention_lowers(b, h, d, n, bs, nb, lead, layer):
    pages = sds((*lead, n, bs, h, d), f32)
    assert mosaic_calls(
        functools.partial(paged_attention, layer=layer),
        sds((b, h, d), f32), pages, pages,
        sds((b, nb), i32), sds((b,), i32)) == ["_paged_kernel"]


@pytest.mark.parametrize("b,h,d,n,bs,nb,lead,layer", [
    (4, 2, 16, 32, 8, 8, (), None),       # tiny_lm test shapes
    (3, 4, 16, 16, 8, 4, (), None),
    (4, 2, 16, 32, 8, 8, (3,), 1),
    (16, 12, 64, 1024, 16, 64, (12,), 5),  # a GPT-2-small-sized head
])
def test_paged_attention_narrow_head_takes_xla_and_says_so(
        b, h, d, n, bs, nb, lead, layer):
    # a head narrower than the 128 lanes is padded to them in HBM, and
    # libtpu's Mosaic refuses the kernel's page copy out of such a pool
    # ("Slice shape along dimension 4 must be aligned to tiling (128)"):
    # those shapes compile through the XLA gather
    pages = sds((*lead, n, bs, h, d), f32)
    before = dispatch.counts().get("paged_attention.xla", 0)
    assert mosaic_calls(
        functools.partial(paged_attention, layer=layer),
        sds((b, h, d), f32), pages, pages,
        sds((b, nb), i32), sds((b,), i32)) == []
    assert dispatch.counts()["paged_attention.xla"] == before + 1


# one step of every kind that attends through the pages, at its
# StepCache key; the engine below: 3 layers, 24 blocks of 8, 2 heads x 128
PAGED_STEPS = {"_compile_decode": (4, 4),
               "_compile_prefill_cached": (16,),
               "_compile_propose": (4, 4, 2),
               "_compile_verify": (4, 4, 3)}


def paged_step_module(compile_step, monkeypatch, named=None):
    """(config, StableHLO text) of one engine step lowered for a TPU;
    ``named`` gets the name the engine compiles it under."""
    from paddle_tpu.serving import GenerativeEngine, tiny_lm

    cfg, params = tiny_lm(3, vocab=64, d_model=256, n_heads=2, n_layers=3,
                          d_ff=64, block_size=8, max_blocks=4, max_batch=4)
    eng = GenerativeEngine(cfg, params, kv_blocks=24, warm=False,
                           prefix_cache=False, spec_k=0)
    try:
        # the step as _aot is handed it, before jit: traced here as
        # placed on a TPU, which the engine's own device is not
        monkeypatch.setattr(
            eng, "_aot", lambda name, step, *specs: (name, eng._flat(step),
                                                     specs))
        name, step, specs = getattr(eng, compile_step)(
            PAGED_STEPS[compile_step])
        if named is not None:
            named.append(name)
        pspec, cspec = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), (eng._params, eng._cache))
        return cfg, tpu_module(
            step, pspec, cspec,
            *[sds(shape, dtype) for shape, dtype in specs])
    finally:
        eng.close()


@pytest.mark.parametrize("compile_step", sorted(PAGED_STEPS))
def test_engine_steps_hand_the_kernel_the_whole_pool(compile_step,
                                                     monkeypatch):
    """A Mosaic call takes whole buffers, so a ``pool[l]`` in front of
    it is a copy of one layer's whole pool, 48 a decode step (54% of
    the serving cell's device time before PR 26).  Every paged kernel
    of every step takes the 5-D pool itself, and nothing in the module
    has one layer's pool as its shape."""
    cfg, text = paged_step_module(compile_step, monkeypatch)
    kernels = [ln for ln in text.splitlines()
               if "@tpu_custom_call" in ln
               and 'kernel_name = "_paged_kernel"' in ln]
    per_step = 2 if compile_step == "_compile_propose" else 1
    assert len(kernels) == cfg.n_layers * per_step
    whole = "tensor<3x24x8x2x128xf32>"
    for ln in kernels:
        operands = ln[ln.rindex(": (") + 3:ln.rindex(") -> ")]
        assert operands.split(", ")[-2:] == [whole, whole], operands
    # pool[l] is a slice to 1x24x8x2x128 and a reshape to 24x8x2x128
    assert not re.search(r"tensor<(1x)?24x8x2x128xf32>", text)


def stripped_module(text):
    """A lowered module with what is no computation taken out: source
    locations, and each Mosaic body decoded from its bytecode and
    printed without them."""
    import base64
    import hashlib

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    def body(match):
        raw = re.sub(r"\\([0-9A-Fa-f]{2})",
                     lambda m: chr(int(m.group(1), 16)), match.group(1))
        code = base64.b64decode(
            json.loads(raw)["custom_call_config"]["body"])
        ctx = jax_mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            asm = ir.Module.parse(code).operation.get_asm(
                enable_debug_info=False)
        return 'backend_config = "%s"' % hashlib.sha256(
            asm.encode()).hexdigest()

    text = re.sub(r'backend_config = "((?:[^"\\]|\\.)*)"', body, text)
    text = re.sub(
        r" ?loc\((?:[^()]|\((?:[^()]|\([^()]*\))*\))*\)", "", text)
    return "\n".join(ln for ln in text.splitlines()
                     if not ln.startswith("#loc"))


# sha256 of stripped_module() of the plain decode step the serving cell
# runs, taken at PR 31's commit (5d240f3) with this file's engine: the
# engine now holds whatever tree of arrays the model's cache_spec names
# and carries an admitted prompt in a program of its own (PR 33), and
# for LMConfig (the K/V pair) the step that carries nothing must lower
# to the program it was, operand for operand and line for line
PARENT_STEPS = {
    "_compile_decode": ((4, 4), "eb99041b02c0d57a14e0b00b79f640c6"
                                "62c2889b3caaa66f087b2dd0fbf81d79"),
}


@pytest.mark.parametrize("compile_step", sorted(PARENT_STEPS))
def test_lm_config_steps_lower_as_before_the_cache_tree(compile_step,
                                                        monkeypatch):
    import hashlib

    key, want = PARENT_STEPS[compile_step]
    monkeypatch.setitem(PAGED_STEPS, compile_step, key)
    _, text = paged_step_module(compile_step, monkeypatch)
    assert hashlib.sha256(
        stripped_module(text).encode()).hexdigest() == want


def test_riding_step_is_a_serve_decode_with_a_flash_call_a_layer_more(
        monkeypatch):
    """The step that carries a prompt is compiled under the plain step's
    name, so that in a trace its paged kernels are ``%serve_decode.N``
    like the plain step's (the decode tokens it makes are counted as
    ``paged_attn_roofline.serve``'s work) and every run of
    ``jit_serve_decode`` holds ``n_layers`` of them: its Mosaic calls
    are the ``n_layers`` unnamed paged kernels, each on the whole pool,
    and ``n_layers`` of ``flash_fwd``, which carry their own name."""
    named = []
    monkeypatch.setitem(PAGED_STEPS, "_compile_ride", (16,))
    cfg, text = paged_step_module("_compile_ride", monkeypatch, named)
    assert named == ["serve_decode"]
    calls = [ln for ln in text.splitlines() if "@tpu_custom_call" in ln]
    names = [re.search(r'kernel_name = "(\w+)"', ln).group(1) for ln in calls]
    assert sorted(names) == sorted(
        ["_paged_kernel", "flash_fwd"] * cfg.n_layers)
    whole = "tensor<3x24x8x2x128xf32>"
    for ln, name in zip(calls, names):
        if name == "_paged_kernel":
            operands = ln[ln.rindex(": (") + 3:ln.rindex(") -> ")]
            assert operands.split(", ")[-2:] == [whole, whole], operands
    assert not re.search(r"tensor<(1x)?24x8x2x128xf32>", text)


def test_decode_step_holds_one_mosaic_call_a_layer(monkeypatch):
    """``paged_attn_roofline.serve`` is read only where every run of
    ``jit_serve_decode`` holds exactly ``n_layers`` custom calls: a
    second Mosaic call a layer (a kernel split in two, another kernel
    moved into the step) would silence the metric, not fail a run."""
    cfg, text = paged_step_module("_compile_decode", monkeypatch)
    assert text.count("@tpu_custom_call") == cfg.n_layers
    assert dispatch.counts().get("paged_attention.pallas", 0) > 0


@pytest.mark.parametrize("n_tok", [64, 512, 128, 192, 320, 576])
def test_grouped_expert_matmul_lowers_at_the_hybrid_cells_widths(n_tok):
    # nemotron3s-ep4-serve-reason-c64: 128 held of 512 routed experts of
    # 1024 x 2688 in 5 layers, top-22; a decode step's 64 rows, a
    # prompt's 512 and the decode steps that carry a prompt of 64 to 512
    # rows: every tile the cell reaches
    from paddle_tpu.kernels.moe_grouped import grouped_expert_ffn

    assert mosaic_calls(
        lambda x, ids, g, w1, w2: grouped_expert_ffn(x, ids, g, w1, w2,
                                                     layer=3, n_routed=512),
        sds((n_tok, 1024), bf16), sds((n_tok, 22), i32),
        sds((n_tok, 22), f32), sds((5, 128, 1024, 2688), bf16),
        sds((5, 128, 2688, 1024), bf16)) == ["grouped_expert_ffn"]


def test_state_update_lowers_at_the_hybrid_cells_widths():
    # 65 slots of 128 state indices x 128 heads x 64 in 5 layers, 64
    # rows, 8 groups
    from paddle_tpu.kernels.ssm_update import ssm_state_update

    assert mosaic_calls(
        lambda st, sl, x, dt, a, b, c: ssm_state_update(
            st, sl, x, dt, a, b, c, layer=4),
        sds((5, 65, 128, 8192), f32), sds((64,), i32),
        sds((64, 128, 64), f32), sds((64, 128), f32), sds((128,), f32),
        sds((64, 8, 128), f32), sds((64, 8, 128), f32)) == [
            "ssm_state_update"]


@pytest.mark.parametrize("compile_step,key", [("_compile_decode", (4, 4)),
                                              ("_compile_ride", (16,))])
def test_hybrid_steps_hand_their_kernels_the_whole_stacks(compile_step, key,
                                                          monkeypatch):
    """The served hybrid's two steps lowered for a TPU, the plain decode
    step and the one that carries a prompt: one state update a Mamba
    layer (the decode rows'), one grouped expert matmul an expert layer
    (over the rows of both kinds), each on the whole stacked operand
    with the layer a scalar: nothing in the module has one layer's
    experts or one layer's state as its shape (a slice in front of a
    Mosaic call is a copy).  The riding step adds one flash call an
    attention layer, the prompt's."""
    from paddle_tpu.serving import GenerativeEngine
    from paddle_tpu.serving.nemotron_h import tiny_nemotron_h

    cfg, params = tiny_nemotron_h(
        3, pattern="MEM*E", vocab=64, hidden=256, n_heads=2, n_kv_heads=1,
        head_dim=128, mamba_heads=4, mamba_head_dim=64, n_groups=2,
        state=128, latent=128, expert_ff=256, shared_ff=256, n_experts=8,
        experts_held=(4, 7), top_k=3, chunk=8, block_size=8, max_blocks=4,
        max_batch=4)
    eng = GenerativeEngine(cfg, params, kv_blocks=24, warm=False,
                           prefix_cache=False, spec_k=0)
    try:
        monkeypatch.setattr(
            eng, "_aot", lambda name, step, *specs: (eng._flat(step), specs))
        step, specs = getattr(eng, compile_step)(key)
        pspec, cspec = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), (eng._params, eng._cache))
        text = tpu_module(step, pspec, cspec,
                          *[sds(shape, dtype) for shape, dtype in specs])
    finally:
        eng.close()
    names = re.findall(r'@tpu_custom_call\(.*?kernel_name = "(\w+)"', text)
    assert names.count("grouped_expert_ffn") == 2
    assert names.count("ssm_state_update") == 2
    assert names.count("flash_fwd") == (
        0 if compile_step == "_compile_decode" else 1)
    for ln in text.splitlines():
        if "@tpu_custom_call" not in ln:
            continue
        if 'kernel_name = "grouped_expert_ffn"' in ln:
            assert "tensor<2x4x128x256xbf16>" in ln
            assert "tensor<2x4x256x128xbf16>" in ln
        if 'kernel_name = "ssm_state_update"' in ln:
            assert "tensor<2x5x128x256xf32>" in ln
    assert not re.search(r"tensor<(1x)?4x128x256xbf16>", text)
    assert not re.search(r"tensor<(1x)?5x128x256xf32>", text)
    assert dispatch.counts().get("grouped_expert_ffn.pallas", 0) > 0


def test_mamba1_state_update_lowers_at_the_jamba_cells_widths():
    # jamba2-3b-serve-reason-c128: 129 slots of a [16, 5120] state and a
    # conv window of 3 x 5120 folded to [24, 640], 26 Mamba layers, 128
    # rows
    from paddle_tpu.kernels.ssm_update import mamba1_state_update

    assert mosaic_calls(
        lambda st, cv, sl, x, dt, a, b, c, w: mamba1_state_update(
            st, cv, sl, x, dt, a, b, c, w, layer=25),
        sds((26, 129, 16, 5120), f32), sds((26, 129, 24, 640), f32),
        sds((128,), i32), sds((128, 5120), f32), sds((128, 5120), f32),
        sds((16, 5120), f32), sds((128, 16), f32), sds((128, 16), f32),
        sds((128, 24, 640), f32)) == ["mamba1_state_update"]


@pytest.mark.parametrize("s_len", [64, 1024, 3072])
def test_selective_scan_lowers_at_the_jamba_cells_widths(s_len):
    # the cell's prompt buckets: the least, the longest prompt's and the
    # longest sequence's
    from paddle_tpu.kernels.selective_scan import selective_scan

    assert mosaic_calls(
        lambda st, slot, n, x, dt, a, b, c: selective_scan(
            st, slot, n, x, dt, a, b, c, layer=3),
        sds((26, 129, 16, 5120), f32), sds((), i32), sds((), i32),
        sds((s_len, 5120), f32), sds((s_len, 5120), f32),
        sds((16, 5120), f32), sds((s_len, 16), f32),
        sds((s_len, 16), f32)) == ["selective_scan"]


@pytest.mark.parametrize("compile_step,key", [("_compile_decode", (4, 4)),
                                              ("_compile_ride", (16,))])
def test_jamba_steps_hand_their_kernels_the_whole_state(compile_step, key,
                                                        monkeypatch):
    """The served Jamba's two steps lowered for a TPU: a run of Mamba
    layers is a loop over the layers, whose body holds one decode state
    update (and, in the step that carries a prompt, one prompt scan), so
    one call a Mamba layer when it runs (tests/test_serving_jamba.py
    sees every layer's state move); each on the whole stacked state
    (the update on the conv windows too) with the layer a scalar:
    nothing in the module has one layer's state or windows as its
    shape.  The riding step adds one flash call an attention
    layer, the prompt's."""
    import sys

    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmark.lib import reference_jamba
    from paddle_tpu.serving import GenerativeEngine
    from paddle_tpu.serving.jamba import JambaConfig

    cfg = JambaConfig(vocab=64, hidden=256, n_layers=5, attn_offset=2,
                      attn_period=4, n_heads=2, n_kv_heads=1, d_inner=256,
                      state=16, dt_rank=16, conv_kernel=4, ff=256, eps=1e-6,
                      block_size=8, max_blocks=4, max_batch=4)
    params = reference_jamba.stacked_params(3, dict(
        vocab=64, hidden=256, pattern=cfg.pattern, n_heads=2, n_kv_heads=1,
        d_inner=256, state=16, dt_rank=16, conv_kernel=4, ff=256, eps=1e-6,
        time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4))
    eng = GenerativeEngine(cfg, params, kv_blocks=24, warm=False,
                           prefix_cache=False, spec_k=0)
    try:
        monkeypatch.setattr(
            eng, "_aot", lambda name, step, *specs: (eng._flat(step), specs))
        step, specs = getattr(eng, compile_step)(key)
        pspec, cspec = jax.tree_util.tree_map(
            lambda a: sds(a.shape, a.dtype), (eng._params, eng._cache))
        text = tpu_module(step, pspec, cspec,
                          *[sds(shape, dtype) for shape, dtype in specs])
    finally:
        eng.close()
    runs = sum(kind == "M" for kind, _, _ in cfg.runs)
    assert runs == 2 and cfg.pattern == "MM*MM"
    names = re.findall(r'@tpu_custom_call\(.*?kernel_name = "(\w+)"', text)
    # the runs' loop bodies are one function where they trace alike
    assert 1 <= names.count("mamba1_state_update") <= runs
    riding = compile_step == "_compile_ride"
    assert (names.count("selective_scan") > 0) == riding
    assert names.count("selective_scan") <= runs
    assert names.count("flash_fwd") == (1 if riding else 0)
    assert text.count("stablehlo.while") >= runs
    for ln in text.splitlines():
        if "@tpu_custom_call" in ln and ("mamba1_state_update" in ln
                                         or "selective_scan" in ln):
            assert "tensor<4x5x16x256xf32>" in ln
        if "@tpu_custom_call" in ln and "mamba1_state_update" in ln:
            assert "tensor<4x5x24x32xf32>" in ln
    assert not re.search(r"tensor<(1x)?5x16x256xf32>", text)
    assert not re.search(r"tensor<(1x)?5x24x32xf32>", text)
    counts = dispatch.counts()
    assert counts.get("mamba1_state_update.pallas", 0) > 0
    if riding:
        assert counts.get("selective_scan.pallas", 0) > 0


# sha256 of stripped_module() of the Mamba-2 state update at the hybrid
# cell's widths and of the served hybrid's two steps (the shapes of
# test_hybrid_steps_hand_their_kernels_the_whole_stacks), with the state
# laid out [L, slots, N, H P]: a change to code they share with the other
# models (the Mamba-1 kernels' file, the attention helpers of
# serving/nemotron_h.py) leaves them lowered as they are
NEMOTRON_STEPS = {
    "ssm_state_update": "4cf87f75c5ad5654caecf14b821cba9d"
                        "df11f71404cbf7f3debb6e246b0e2d9e",
    "_compile_decode": "258e2aac9e77f91b595ab9bb369630d2"
                       "3013d3f9594a7bfdd175dd8875e75d20",
    "_compile_ride": "b980438b46fcd48dafb7db33e61d5da8"
                     "67d44e3fd1b7f0472ce83ae45c855ad3",
}


@pytest.mark.parametrize("what", sorted(NEMOTRON_STEPS))
def test_the_mamba2_kernel_and_the_hybrids_steps_lower_as_before(
        what, monkeypatch):
    import hashlib

    from paddle_tpu.kernels.ssm_update import ssm_state_update
    from paddle_tpu.serving import GenerativeEngine
    from paddle_tpu.serving.nemotron_h import tiny_nemotron_h

    if what == "ssm_state_update":
        text = tpu_module(
            lambda st, sl, x, dt, a, b, c: ssm_state_update(
                st, sl, x, dt, a, b, c, layer=4),
            sds((5, 65, 128, 8192), f32), sds((64,), i32),
            sds((64, 128, 64), f32), sds((64, 128), f32), sds((128,), f32),
            sds((64, 8, 128), f32), sds((64, 8, 128), f32))
    else:
        cfg, params = tiny_nemotron_h(
            3, pattern="MEM*E", vocab=64, hidden=256, n_heads=2,
            n_kv_heads=1, head_dim=128, mamba_heads=4, mamba_head_dim=64,
            n_groups=2, state=128, latent=128, expert_ff=256,
            shared_ff=256, n_experts=8, experts_held=(4, 7), top_k=3,
            chunk=8, block_size=8, max_blocks=4, max_batch=4)
        eng = GenerativeEngine(cfg, params, kv_blocks=24, warm=False,
                               prefix_cache=False, spec_k=0)
        try:
            monkeypatch.setattr(
                eng, "_aot",
                lambda name, step, *specs: (eng._flat(step), specs))
            step, specs = getattr(eng, what)(
                (4, 4) if what == "_compile_decode" else (16,))
            pspec, cspec = jax.tree_util.tree_map(
                lambda a: sds(a.shape, a.dtype), (eng._params, eng._cache))
            text = tpu_module(step, pspec, cspec,
                              *[sds(shape, dtype) for shape, dtype in specs])
        finally:
            eng.close()
    assert hashlib.sha256(
        stripped_module(text).encode()).hexdigest() == NEMOTRON_STEPS[what]


def test_matmul_epilogue_and_add_ln_lower():
    x, w = sds((32768, 1024), bf16), sds((1024, 4096), bf16)
    bias = sds((4096,), f32)
    assert mosaic_calls(
        lambda x, w, b: matmul_epilogue(x, w, b, act="relu"),
        x, w, bias) == ["_matmul_kernel"]
    res = sds((32768, 4096), bf16)
    assert mosaic_calls(
        lambda x, w, b, r: matmul_epilogue(x, w, b, r, act="gelu",
                                           save_preact=True),
        x, w, bias, res) == ["_matmul_kernel"]
    h = sds((32768, 1024), bf16)
    g = sds((1024,), f32)
    assert mosaic_calls(lambda x, y, s, b: add_ln(x, y, s, b),
                        h, h, g, g) == ["_add_ln_kernel"]


@pytest.mark.parametrize("rows", [8, 64, 512])
@pytest.mark.parametrize("k,n,chunk", [(1024, 3072, 1024),
                                       (1024, 4096, 1024),
                                       (4096, 1024, 2048)])
def test_matmul_int8_lowers(rows, k, n, chunk):
    # the int8 tenant's projections at d1024: decode batch 8, prefill rows
    assert mosaic_calls(
        lambda x, wq, s: matmul_int8_dequant(x, wq, s, chunk),
        sds((rows, k), f32), sds((k, n), i8),
        sds((k // chunk, n), f32)) == ["_matmul_int8_kernel"]


def test_matmul_int8_narrow_batch_takes_xla_and_says_so():
    before = dispatch.counts().get("matmul_int8_dequant.xla", 0)
    assert mosaic_calls(
        lambda x, wq, s: matmul_int8_dequant(x, wq, s, 1024),
        sds((4, 1024), f32), sds((1024, 3072), i8),
        sds((1, 3072), f32)) == []
    assert dispatch.counts()["matmul_int8_dequant.xla"] == before + 1


@pytest.mark.parametrize("dtype", [f32, bf16])
def test_fused_softmax_cross_entropy_lowers(dtype):
    assert mosaic_calls(
        fused_softmax_cross_entropy, sds((32768, 8192), dtype),
        sds((32768,), i32)) == ["_ce_kernel"]


@pytest.mark.parametrize("hw,ci,co,k,pad", [(56, 64, 64, 3, 1),
                                            (56, 64, 256, 1, 0),
                                            (7, 512, 512, 3, 1)])
def test_conv_stride1_lowers(hw, ci, co, k, pad):
    # ResNet-50 stride-1 stages, bs256 bf16, train form (fused BN stats)
    assert mosaic_calls(
        lambda x, w: conv2d_nhwc(x, w, (1, 1), (pad, pad), stats=True),
        sds((256, hw, hw, ci), bf16),
        sds((k, k, ci, co), bf16)) == ["_conv_stage_kernel"]


@pytest.mark.parametrize("hw,ci,co,k,pad", [(224, 3, 64, 7, 3),
                                            (56, 128, 128, 3, 1)])
def test_conv_stride2_takes_xla_and_says_so(hw, ci, co, k, pad):
    # Mosaic rejects the strided tap windows; the stem and the
    # downsample stages must compile through the XLA conv instead
    before = dispatch.counts().get("conv2d_nhwc.xla", 0)
    assert mosaic_calls(
        lambda x, w: conv2d_nhwc(x, w, (2, 2), (pad, pad), stats=True),
        sds((256, hw, hw, ci), bf16), sds((k, k, ci, co), bf16)) == []
    assert dispatch.counts()["conv2d_nhwc.xla"] == before + 1


def test_interpret_is_not_a_chip_path():
    q = jnp.zeros((1, 1, 8, 8), f32)
    with placed_on(TPU), pytest.raises(ValueError, match="interpret"):
        flash_attention(q, q, q, interpret=True)


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def meter(smoke):
    # one per module: jax keeps monitoring listeners for the process
    return smoke.CompileMeter()


@pytest.fixture
def rehearsal(smoke, meter, tmp_path, monkeypatch):
    """(target, meter) for a CPU rehearsal: kernels decide as they
    would for a TPU-placed computation and run in Pallas' TPU
    interpreter."""
    from jax.experimental.pallas import tpu as pltpu

    import paddle_tpu.fluid as fluid

    monkeypatch.setattr(dispatch, "target_platform", lambda: "tpu")
    target = smoke.Target(fluid.CPUPlace(), "cpu", str(tmp_path))
    with pltpu.force_tpu_interpret_mode():
        yield target, meter


TOY_LM = dict(vocab_size=64, seq_len=32, d_model=32, n_head=2, n_layers=1,
              d_ff=64)


def test_rehearse_train_transformer(smoke, rehearsal):
    target, meter = rehearsal
    rec = smoke.phase_train_transformer(target, meter, TOY_LM, 2,
                                        run_steps=2, prepared_steps=1)
    assert set(rec["kernels"]) == {"flash_attention_fwd_lse.pallas",
                                   "flash_attention_bwd.pallas"}
    assert rec["losses"][-1] < rec["losses"][0]
    json.dumps(rec)


def test_a_kernel_forced_to_xla_fails_the_kernel_assertion(smoke):
    """The failure the smoke exists to catch — a kernel that fell to
    its XLA reference on the path — shows up as an unexpected
    '<kernel>.xla' dispatch, which every phase asserts against."""
    q = sds((2, 2, 64, 16), f32)
    mark = smoke.kernel_mark()
    mosaic_calls(lambda q, k, v: flash_attention_fwd_lse(
        q, k, v, force_xla=True), q, q, q)
    with pytest.raises(AssertionError, match="flash_attention_fwd_lse.xla"):
        smoke.assert_kernels(smoke.kernel_delta(mark),
                             {"flash_attention_fwd_lse.pallas": 1})


def test_rehearse_serve_generate(smoke, rehearsal):
    target, meter = rehearsal
    # d_model 128 / max_batch 8: the narrowest LM whose int8 matmuls
    # tile, in one head: the paged kernel wants a head of 128 lanes;
    # 2 blocks of 16 keep every request in the top block bucket
    lm = dict(vocab=64, d_model=128, n_heads=1, n_layers=1, d_ff=128,
              block_size=16, max_blocks=2, max_batch=8)
    rec = smoke.phase_serve_generate(target, meter, lm,
                                     (17, 18, 19, 20, 21), 3, 32)
    assert set(rec["tenants"]) == {"lm_fp32", "lm_int8"}
    assert rec["tenants"]["lm_int8"]["kernels"][
        "matmul_int8_dequant.pallas"] > 0
    json.dumps(rec)


def test_rehearse_train_resnet(smoke, rehearsal):
    target, meter = rehearsal
    rec = smoke.phase_train_resnet50(target, meter, 4, depth=8, steps=2,
                                     data_set="cifar10")
    assert rec["losses"][-1] < rec["losses"][0]
    json.dumps(rec)


def test_rehearse_multichip(smoke, rehearsal):
    target, meter = rehearsal
    rec = smoke.phase_multichip(target, meter, TOY_LM, 2, None,
                                meshes=({"dp": 2, "tp": 2},), steps=1)
    assert rec["dp2xtp2"]["tp_sliced_params"] > 0
    assert rec["dp2xtp2"]["batch"] == 4
    json.dumps(rec)


def test_result_line_holds_exactly_the_contract_keys(smoke):
    import jax

    line = json.loads(json.dumps(smoke.result_line(True)))
    assert set(line) == {"ok", "device"} and line["ok"] is True
    assert line["device"] == {"platform": jax.devices()[0].platform,
                              "kind": jax.devices()[0].device_kind,
                              "count": len(jax.devices())}


def test_chip_smoke_has_no_cpu_mode():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["phase"] == "device" and last["ok"] is False
    assert "no TPU" in last["error"]
