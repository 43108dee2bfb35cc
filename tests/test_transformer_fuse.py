"""FuseTransformerBlockPass end to end on the transformer LM: the
fused program (fused_qkv_matmul / fused_matmul_bias_act /
fused_add_ln + their explicit grad ops) must train identically to the
unfused build — parity pinned at fp32 losses <=2e-4 / params <=4e-7
over 3 Adam steps, AMP at bf16 tolerance (ISSUE 7 acceptance)."""
import collections

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core.flags import FLAGS
from paddle_tpu.core.scope import Scope
from paddle_tpu.models import transformer

VOCAB, SEQ, DM, HEADS, LAYERS, DFF = 101, 16, 32, 4, 2, 64


def _run(fuse, params=None, steps=3, amp=False):
    main, startup = fluid.Program(), fluid.Program()
    scope = Scope()
    with fluid.scope_guard(scope):
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                avg_cost, (src, label), _ = transformer.get_model(
                    vocab_size=VOCAB, seq_len=SEQ, d_model=DM,
                    n_head=HEADS, n_layers=LAYERS, d_ff=DFF,
                    fuse_transformer=fuse)
        if amp:
            fluid.transpiler.Float16Transpiler().transpile(main)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        if params is not None:
            for n, v in params.items():
                scope.set(n, v)
        snap = {n: np.asarray(scope.find_var(n)).copy()
                for n in scope.local_var_names()}
        rng = np.random.RandomState(0)
        feed = {src.name: rng.randint(0, VOCAB, (2, SEQ)).astype(
            np.int64),
            label.name: rng.randint(0, VOCAB, (2, SEQ, 1)).astype(
                np.int64)}
        losses = []
        for _ in range(steps):
            l, = exe.run(main, feed=feed, fetch_list=[avg_cost])
            losses.append(float(np.asarray(l).ravel()[0]))
        post = {n: np.asarray(scope.find_var(n)).copy()
                for n in scope.local_var_names()}
    ops = [o.type for o in main.desc.blocks[0].ops]
    return losses, snap, post, ops


def test_fused_transformer_training_parity():
    base_losses, params, base_post, base_ops = _run(False)
    losses, _, post, ops = _run(True, params=dict(params))
    counts = collections.Counter(ops)
    # per layer: 1 QKV triple, 3 epilogue matmuls (out-proj, mlp
    # up+act, mlp down) + the lm_head, 2 residual+LN seams
    assert counts["fused_qkv_matmul"] == LAYERS
    assert counts["fused_matmul_bias_act"] == 3 * LAYERS + 1
    assert counts["fused_add_ln"] == 2 * LAYERS
    assert counts["mul"] == 0
    # the first LN stays unfused (its input is the broadcast emb+pos
    # add, not a same-shape residual seam)
    assert counts["layer_norm"] == 1
    assert counts["fused_qkv_matmul_grad"] == LAYERS
    assert counts["fused_matmul_bias_act_grad"] == 3 * LAYERS + 1
    assert counts["fused_add_ln_grad"] == 2 * LAYERS
    # ISSUE 7 acceptance: fp32 losses <=2e-4 over 3 steps
    np.testing.assert_allclose(base_losses, losses, rtol=2e-4,
                               atol=2e-4)
    # params <=4e-7 (covers every explicit grad lowering end to end,
    # Adam state included)
    for n, v in base_post.items():
        w = post.get(n)
        if w is None or v.dtype.kind != "f" or v.shape != w.shape:
            continue
        np.testing.assert_allclose(v, w, rtol=1e-4, atol=4e-7,
                                   err_msg=n)


def test_fused_transformer_amp_parity():
    """Under the bf16 Float16Transpiler the fused ops take the same
    autocast slots as the unfused chain (AMP_WHITE matmuls, pass-through
    LN) — bf16 tolerance."""
    base_losses, params, _, _ = _run(False, amp=True)
    losses, _, _, ops = _run(True, params=dict(params), amp=True)
    assert "fused_matmul_bias_act" in ops
    np.testing.assert_allclose(base_losses, losses, rtol=2e-2,
                               atol=2e-2)


def test_flag_gating():
    """FLAGS.transformer_fuse default-off: get_model builds the unfused
    program unless the flag (or the explicit argument) says otherwise."""
    assert FLAGS.transformer_fuse is False
    _, _, _, ops = _run(None)       # None -> FLAGS (off)
    assert not any(o.startswith("fused_") for o in ops)
    FLAGS.transformer_fuse = True
    try:
        _, _, _, ops = _run(None)
        assert any(o == "fused_qkv_matmul" for o in ops)
    finally:
        FLAGS.transformer_fuse = False


def test_residual_goes_to_add_ln_not_matmul():
    """The pre-LN policy: a residual add feeding a layer_norm belongs
    to fused_add_ln (statistics from the VMEM sum); the matmul
    epilogue only absorbs residual adds that do NOT feed an LN."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            transformer.get_model(
                vocab_size=VOCAB, seq_len=SEQ, d_model=DM,
                n_head=HEADS, n_layers=LAYERS, d_ff=DFF,
                fuse_transformer=True)
    for op in main.desc.blocks[0].ops:
        if op.type == "fused_matmul_bias_act":
            assert not op.inputs.get("Residual"), (
                "residual absorbed into a matmul whose sum feeds an "
                "LN seam")
        if op.type == "fused_add_ln":
            # the residual stream reads the sum: it must stay an output
            assert op.outputs.get("Sum")


def test_fused_program_structure_survives_sum_consumers():
    """fused_add_ln's Sum output is the residual stream: the NEXT
    block's seam consumes it, so each fused_add_ln (except the final
    one) has its Sum read downstream."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            transformer.get_model(
                vocab_size=VOCAB, seq_len=SEQ, d_model=DM,
                n_head=HEADS, n_layers=LAYERS, d_ff=DFF,
                fuse_transformer=True)
    block = main.desc.blocks[0]
    sums = [op.output("Sum")[0] for op in block.ops
            if op.type == "fused_add_ln"]
    consumed = set()
    for op in block.ops:
        for n in op.input_arg_names():
            consumed.add(n)
    # all but the last seam's sum feed downstream ops (forward alone;
    # grads consume the rest)
    assert all(s in consumed for s in sums[:-1])


@pytest.mark.slow
def test_fused_transformer_cpu_step_wall():
    """ISSUE 7 acceptance: fused block stages measurably reduce the
    transformer step wall on the CPU-tier microbench vs unfused.
    Shape bs4 seq256 d256 L2; asserted with margin (best-of-3 fused
    must not be slower than best-of-3 unfused by more than 2%)."""
    import time

    def bench(fuse, iters=12):
        main, startup = fluid.Program(), fluid.Program()
        scope = Scope()
        with fluid.scope_guard(scope):
            with fluid.program_guard(main, startup):
                with fluid.unique_name.guard():
                    avg_cost, (src, label), _ = transformer.get_model(
                        vocab_size=1024, seq_len=256, d_model=256,
                        n_head=8, n_layers=2, d_ff=1024,
                        fuse_transformer=fuse)
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            rng = np.random.RandomState(0)
            feed = {src.name: rng.randint(0, 1024, (4, 256)).astype(
                np.int64),
                label.name: rng.randint(0, 1024, (4, 256, 1)).astype(
                    np.int64)}
            for _ in range(2):
                exe.run(main, feed=feed, fetch_list=[avg_cost])
            t0 = time.time()
            loss = None
            for _ in range(iters):
                loss, = exe.run(main, feed=feed, fetch_list=[avg_cost],
                                return_numpy=False)
            np.asarray(loss)
            return (time.time() - t0) / iters

    unfused = min(bench(False) for _ in range(3))
    fused = min(bench(True) for _ in range(3))
    assert fused <= unfused * 1.02, (
        "fused transformer step slower than unfused on CPU: "
        "%.2f ms vs %.2f ms" % (fused * 1e3, unfused * 1e3))


def test_fused_transformer_mfu_bench_fields():
    """bench.py's transformer JSON must report fused_stages > 0 with
    per-category counts when BENCH_FUSED_TRANSFORMER=1 (acceptance) —
    checked here at the program level the bench reads them from."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        with fluid.unique_name.guard():
            transformer.get_model(
                vocab_size=VOCAB, seq_len=SEQ, d_model=DM,
                n_head=HEADS, n_layers=LAYERS, d_ff=DFF,
                fuse_transformer=True)
    fwd_fused = [op.type for op in main.desc.blocks[0].ops
                 if op.type.startswith("fused_") and
                 not op.type.endswith("_grad")]
    assert len(fwd_fused) == 6 * LAYERS + 1
