"""Registry-wide TPU op sweep.

Parity: reference python/paddle/fluid/tests/unittests/op_test.py:261
(check_output_with_place) and :320 (check_output sweeping every available
place): the reference runs every op test on CPU *and* CUDA; this tool runs
every registered op on CPUPlace *and* TPUPlace (needs a process that
holds a chip) and holds the TPU result to the CPU result (the CPU path being the one the
full pytest suite validates numerically against references / finite
differences).

Three coverage modes, recorded per-op in the artifact:
  - "exact":      one-op program (tests/op_test.py harness) run on both
                  places, outputs allclose; for ops with `grad` in the spec
                  the analytic gradients (calc_gradient program) are compared
                  across places too.
  - "composite":  ops that only exist inside structured programs (While /
                  conditional_block / recurrent / TensorArray / LoD
                  plumbing): a full program is built with the fluid layers
                  front-end, run on both places, fetches compared; every op
                  type appearing in the program (+ its emitted grad ops) is
                  credited to that composite.
  - "skip":       host ops (OpInfo.host_op — the Executor runs them on the
                  host regardless of place, so there is no device lowering
                  to check) and the handful with a stated reason.

Stateful (PRNG) ops are compared exactly too: jax.random is counter-based
and platform-deterministic, so CPU and TPU must agree bit-for-bit modulo
float rounding.

Usage (driver):  TPU_OPTEST=1 python tools/tpu_optest.py
Writes TPU_OPTEST_r05.json at the repo root.  Without TPU_OPTEST=1 (or with
TPU_OPTEST_SELFCHECK=1) it compares CPUPlace against CPUPlace — a fast
validity check of every spec that needs no chip.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import paddle_tpu.fluid as fluid  # noqa: E402
from paddle_tpu.core.flags import FLAGS  # noqa: E402
from paddle_tpu.core import registry  # noqa: E402
from paddle_tpu.core.lod import LoDTensor  # noqa: E402
from paddle_tpu.core.types import np_dtype_to_proto  # noqa: E402
from paddle_tpu.core.scope import Scope  # noqa: E402
from op_test import OpTest  # noqa: E402

layers = fluid.layers
rng = np.random.RandomState(7)


def F(*shape):
    return rng.uniform(-1.0, 1.0, shape).astype(np.float32)


def P(*shape):
    return rng.uniform(0.5, 2.0, shape).astype(np.float32)


def I(shape, hi=5, lo=0):
    return rng.randint(lo, hi, shape).astype(np.int64)


def lodt(padded, lens):
    """LoDTensor from a padded [N,T,...] array + per-row lengths."""
    parts = [padded[i, :l] for i, l in enumerate(lens)]
    flat = np.concatenate(parts, 0)
    offs = np.concatenate([[0], np.cumsum(lens)]).tolist()
    return LoDTensor(flat, [offs])


# ---------------------------------------------------------------------------
# One-op specs.  inputs: slot -> array | LoDTensor | [(name, array), ...];
# outs: output slot names to fetch; grad: input names for the cross-place
# analytic-gradient check; tol: (atol, rtol) override.  The matmul-family
# default tolerance is loose because this host's CPU matmul runs reduced
# precision (see .claude/skills/verify/SKILL.md).
# ---------------------------------------------------------------------------

TOL = (1e-5, 1e-5)
TOL_MM = (2e-3, 2e-3)     # CPU reduced-precision matmul vs TPU
TOL_EXP = (1e-4, 1e-4)    # transcendental-heavy chains

SPECS = {}


def spec(op, inputs, attrs=None, outs=("Out",), grad=None, tol=TOL):
    SPECS[op] = dict(inputs=inputs, attrs=attrs or {}, outs=list(outs),
                     grad=grad, tol=tol)


# --- unary elementwise / activations ---
_UNARY_PLAIN = [
    "abs", "brelu", "ceil", "cos", "elu", "exp", "floor", "hard_shrink",
    "hard_sigmoid", "leaky_relu", "logsigmoid", "relu", "relu6", "round",
    "sigmoid", "sign", "sin", "soft_relu", "softplus", "softshrink",
    "softsign", "square", "stanh", "swish", "tanh", "tanh_shrink",
    "thresholded_relu", "fill_zeros_like", "isfinite",
]
for _op in _UNARY_PLAIN:
    _x = F(3, 5)
    _x[np.abs(_x) < 0.05] = 0.5   # stay off kinks for grad checks
    _info = registry._registry[_op]
    spec(_op, {"X": _x}, grad=None if _info.grad_maker is None else ["X"],
         tol=TOL_EXP)
for _op in ("log", "sqrt", "reciprocal"):
    spec(_op, {"X": P(3, 5)}, grad=["X"], tol=TOL_EXP)

spec("pow", {"X": P(3, 4)}, {"factor": 1.7}, grad=["X"], tol=TOL_EXP)
spec("scale", {"X": F(3, 4)}, {"scale": 2.5, "bias": 0.5}, grad=["X"])
spec("increment", {"X": F(1)}, {"step": 2.0})
spec("clip", {"X": F(3, 4)}, {"min": -0.4, "max": 0.4}, grad=["X"])
spec("clip_by_norm", {"X": F(3, 4)}, {"max_norm": 0.7}, tol=TOL_EXP)
spec("l1_norm", {"X": F(3, 4)}, grad=["X"])
spec("squared_l2_norm", {"X": F(3, 4)}, grad=["X"])
spec("mean", {"X": F(3, 4)}, grad=["X"])
spec("cumsum", {"X": F(3, 4)}, {"axis": 1, "exclusive": False,
                                "reverse": False}, grad=["X"])
spec("logical_not", {"X": I((3, 4), hi=2).astype(bool)})
spec("cast", {"X": F(3, 4)}, {"out_dtype": np_dtype_to_proto("int32")})
spec("softmax", {"X": F(4, 6)}, grad=["X"], tol=TOL_EXP)
spec("log_softmax", {"X": F(4, 6)}, {"axis": -1}, grad=["X"], tol=TOL_EXP)
spec("maxout", {"X": F(2, 6, 4, 4)}, {"groups": 2}, grad=["X"])

# --- binary elementwise + comparisons ---
for _op in ("elementwise_add", "elementwise_sub", "elementwise_mul",
            "elementwise_max", "elementwise_min"):
    spec(_op, {"X": F(3, 4), "Y": F(3, 4)}, {"axis": -1}, grad=["X", "Y"])
spec("elementwise_div", {"X": F(3, 4), "Y": P(3, 4)}, {"axis": -1},
     grad=["X", "Y"])
spec("elementwise_pow", {"X": P(3, 4), "Y": P(3, 4)}, {"axis": -1},
     tol=TOL_EXP)
spec("elementwise_mod", {"X": I((3, 4), hi=17, lo=1),
                         "Y": I((3, 4), hi=5, lo=1)})
spec("elementwise_floordiv", {"X": I((3, 4), hi=17, lo=1),
                              "Y": I((3, 4), hi=5, lo=1)})
spec("minus", {"X": F(3, 4), "Y": F(3, 4)}, grad=["X", "Y"])
for _op in ("equal", "not_equal", "less_than", "less_equal",
            "greater_than", "greater_equal"):
    spec(_op, {"X": I((3, 4), hi=3).astype(np.float32),
               "Y": I((3, 4), hi=3).astype(np.float32)})
for _op in ("logical_and", "logical_or", "logical_xor"):
    spec(_op, {"X": I((3, 4), hi=2).astype(bool),
               "Y": I((3, 4), hi=2).astype(bool)})

# --- reductions / indexing ---
for _op in ("reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
            "reduce_prod"):
    spec(_op, {"X": P(3, 4, 5)}, {"dim": [1], "keep_dim": False,
                                  "reduce_all": False}, grad=["X"])
spec("arg_max", {"X": F(3, 5)}, {"axis": 1})
spec("arg_min", {"X": F(3, 5)}, {"axis": 1})
spec("argsort", {"X": F(3, 5)}, {"axis": 1}, outs=["Out", "Indices"])
spec("top_k", {"X": F(3, 6)}, {"k": 2}, outs=["Out", "Indices"])

# --- matmul family ---
spec("mul", {"X": F(4, 6), "Y": F(6, 3)},
     {"x_num_col_dims": 1, "y_num_col_dims": 1}, grad=["X", "Y"],
     tol=TOL_MM)
spec("matmul", {"X": F(2, 4, 6), "Y": F(2, 6, 3)},
     {"transpose_X": False, "transpose_Y": False, "alpha": 1.0},
     grad=["X", "Y"], tol=TOL_MM)
spec("bilinear_tensor_product",
     {"X": F(4, 3), "Y": F(4, 5), "Weight": F(2, 3, 5), "Bias": F(1, 2)},
     grad=["X", "Y", "Weight"], tol=TOL_MM)
spec("cos_sim", {"X": F(4, 5), "Y": F(4, 5)},
     outs=["Out", "XNorm", "YNorm"], grad=["X", "Y"], tol=TOL_EXP)
spec("conv_shift", {"X": F(3, 8), "Y": F(3, 3)}, grad=["X", "Y"],
     tol=TOL_MM)

# --- nn ---
spec("conv2d", {"Input": F(2, 3, 8, 8), "Filter": F(4, 3, 3, 3)},
     {"strides": [1, 1], "paddings": [1, 1], "dilations": [1, 1],
      "groups": 1}, outs=["Output"], grad=["Input", "Filter"], tol=TOL_MM)
spec("depthwise_conv2d", {"Input": F(2, 4, 8, 8), "Filter": F(4, 1, 3, 3)},
     {"strides": [1, 1], "paddings": [1, 1], "dilations": [1, 1],
      "groups": 4}, outs=["Output"], grad=["Input", "Filter"], tol=TOL_MM)
spec("conv2d_transpose", {"Input": F(2, 3, 6, 6), "Filter": F(3, 4, 3, 3)},
     {"strides": [2, 2], "paddings": [1, 1], "dilations": [1, 1]},
     outs=["Output"], grad=["Input", "Filter"], tol=TOL_MM)
spec("conv3d", {"Input": F(1, 2, 5, 6, 6), "Filter": F(3, 2, 3, 3, 3)},
     {"strides": [1, 1, 1], "paddings": [1, 1, 1],
      "dilations": [1, 1, 1], "groups": 1},
     outs=["Output"], grad=["Input", "Filter"], tol=TOL_MM)
spec("fused_conv2d_bn_act",
     # NHWC input, HWIO filter — the layout-pinned contract the fuse
     # pass (fluid/transpiler/layout_transpiler.py) emits; the explicit
     # grad lowering (residual-consuming, no forward re-run) is covered
     # through the forward spec's cross-place grad check
     {"Input": F(2, 8, 8, 3), "Filter": F(3, 3, 3, 4),
      "Scale": P(4), "Bias": F(4), "Mean": F(4) * 0.1, "Variance": P(4)},
     {"strides": [1, 1], "paddings": [1, 1], "epsilon": 1e-5,
      "momentum": 0.9, "is_test": False, "act": "relu",
      "data_format": "NHWC"},
     outs=["Y", "ConvOut", "MeanOut", "VarianceOut", "SavedMean",
           "SavedInvStd"],
     grad=["Input", "Filter", "Scale", "Bias"], tol=TOL_MM)
# --- fused transformer block stages (ISSUE 7) --- the explicit
# saved-activation grad lowerings are covered through each forward
# spec's cross-place grad check, like fused_conv2d_bn_act above
spec("gelu", {"X": F(3, 5)}, grad=["X"], tol=TOL_EXP)
spec("fused_matmul_bias_act",
     {"X": F(3, 4, 6), "W": F(6, 5), "Bias": F(5),
      "Residual": F(3, 4, 5)},
     {"x_num_col_dims": 2, "act": "gelu", "dropout_prob": 0.0},
     outs=["Out", "MulOut"], grad=["X", "W", "Bias", "Residual"],
     tol=TOL_MM)
spec("fused_qkv_matmul",
     {"X": F(3, 4, 6), "W": [("qkv_wq", F(6, 5)), ("qkv_wk", F(6, 5)),
                             ("qkv_wv", F(6, 4))]},
     {"x_num_col_dims": 2},
     outs=[("Out", 3)], grad=["X", "qkv_wq", "qkv_wv"], tol=TOL_MM)
spec("fused_add_ln",
     {"X": F(3, 4, 6), "Y": F(3, 4, 6), "Scale": P(6), "Bias": F(6)},
     {"begin_norm_axis": 2, "epsilon": 1e-5},
     outs=["Out", "Sum", "Mean", "Variance"],
     grad=["X", "Y", "Scale", "Bias"], tol=TOL_EXP)
spec("pool2d", {"X": F(2, 3, 8, 8)},
     {"pooling_type": "max", "ksize": [2, 2], "strides": [2, 2],
      "paddings": [0, 0], "global_pooling": False, "exclusive": True,
      "adaptive": False}, grad=["X"])
spec("max_pool2d_with_index", {"X": F(2, 3, 8, 8)},
     {"ksize": [2, 2], "strides": [2, 2], "paddings": [0, 0]},
     outs=["Out", "Mask"], grad=["X"])
spec("unpool", {"X": F(2, 3, 4, 4),
                "Indices": np.tile(
                    (np.arange(16).reshape(4, 4) * 4 +
                     (np.arange(16).reshape(4, 4) // 4) * 8 % 4)[None, None],
                    (2, 3, 1, 1)).astype(np.int32) % 64},
     {"ksize": [2, 2], "strides": [2, 2], "paddings": [0, 0]})
spec("spp", {"X": F(2, 3, 8, 8)},
     {"pyramid_height": 2, "pooling_type": "max"}, grad=["X"])
spec("lrn", {"X": P(2, 6, 4, 4)},
     {"n": 5, "k": 2.0, "alpha": 1e-4, "beta": 0.75},
     outs=["Out", "MidOut"], grad=["X"], tol=TOL_EXP)
spec("batch_norm",
     {"X": F(4, 3, 5, 5), "Scale": P(3), "Bias": F(3),
      "Mean": F(3) * 0.1, "Variance": P(3)},
     {"epsilon": 1e-5, "momentum": 0.9, "is_test": False,
      "data_layout": "NCHW"},
     outs=["Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance"],
     grad=["X", "Scale", "Bias"], tol=TOL_EXP)
spec("layer_norm", {"X": F(4, 6), "Scale": P(6), "Bias": F(6)},
     {"begin_norm_axis": 1, "epsilon": 1e-5},
     outs=["Y", "Mean", "Variance"], grad=["X", "Scale", "Bias"],
     tol=TOL_EXP)
spec("norm", {"X": F(3, 4, 5)}, {"axis": 1, "epsilon": 1e-10},
     outs=["Out", "Norm"], grad=["X"], tol=TOL_EXP)
spec("row_conv", {"X": F(2, 6, 4), "Filter": F(3, 4)},
     grad=["X", "Filter"], tol=TOL_MM)
spec("im2sequence", {"X": F(2, 3, 6, 6)},
     {"kernels": [2, 2], "strides": [2, 2], "paddings": [0, 0, 0, 0]},
     grad=["X"], tol=TOL_MM)   # patches lower to conv on TPU
spec("dropout", {"X": P(4, 6)},
     {"dropout_prob": 0.5, "is_test": False,
      "dropout_implementation": "upscale_in_train"},
     outs=["Out", "Mask"])
spec("dropout_grad",
     {"Out@GRAD": [("out_grad", F(4, 6))], "Mask": [("mask", (
         rng.uniform(0, 1, (4, 6)) > 0.5).astype(np.float32))]},
     outs=["X@GRAD"])
spec("prelu", {"X": F(3, 4), "Alpha": P(1)}, {"mode": "all"},
     grad=["X", "Alpha"])

# --- losses ---
spec("cross_entropy",
     {"X": (lambda p: p / p.sum(1, keepdims=True))(P(4, 5)),
      "Label": I((4, 1), hi=5)},
     {"soft_label": False}, outs=["Y"], grad=["X"], tol=TOL_EXP)
spec("softmax_with_cross_entropy",
     {"Logits": F(4, 5), "Label": I((4, 1), hi=5)},
     {"soft_label": False}, outs=["Loss", "Softmax"], grad=["Logits"],
     tol=TOL_EXP)
spec("sigmoid_cross_entropy_with_logits",
     {"X": F(4, 5), "Label": rng.uniform(0, 1, (4, 5)).astype(np.float32)},
     grad=["X"], tol=TOL_EXP)
spec("hinge_loss", {"Logits": F(4, 1),
                    "Labels": I((4, 1), hi=2).astype(np.float32)},
     outs=["Loss"], grad=["Logits"])
spec("huber_loss", {"X": F(4, 1), "Y": F(4, 1)}, {"delta": 0.5},
     outs=["Out", "Residual"], grad=["X"])
spec("log_loss", {"Predicted": rng.uniform(0.1, 0.9, (4, 1)).astype(
    np.float32), "Labels": I((4, 1), hi=2).astype(np.float32)},
     {"epsilon": 1e-4}, outs=["Loss"], grad=["Predicted"], tol=TOL_EXP)
spec("modified_huber_loss", {"X": F(4, 1),
                             "Y": I((4, 1), hi=2).astype(np.float32)},
     outs=["Out", "IntermediateVal"], grad=["X"])
spec("rank_loss", {"Left": F(4, 1), "Right": F(4, 1),
                   "Label": I((4, 1), hi=2).astype(np.float32)},
     grad=["Left", "Right"], tol=TOL_EXP)
spec("margin_rank_loss", {"X1": F(4, 1), "X2": F(4, 1),
                          "Label": (I((4, 1), hi=2) * 2 - 1).astype(
                              np.float32)},
     {"margin": 0.1}, outs=["Out", "Activated"], grad=["X1", "X2"])
spec("smooth_l1_loss",
     {"X": F(4, 3), "Y": F(4, 3), "InsideWeight": P(4, 3),
      "OutsideWeight": P(4, 3)}, {"sigma": 1.0},
     outs=["Out", "Diff"], grad=["X"])
spec("squared_l2_distance", {"X": F(4, 3), "Y": F(4, 3)},
     outs=["Out", "sub_result"], grad=["X", "Y"])
spec("nce", {"Input": F(4, 6), "Label": I((4, 1), hi=20),
             "Weight": F(20, 6), "Bias": F(20)},
     {"num_total_classes": 20, "num_neg_samples": 5},
     outs=["Cost", "SampleLogits", "SampleLabels"], tol=TOL_MM)
spec("label_smooth", {"X": (lambda p: p / p.sum(1, keepdims=True))(P(4, 5)),
                      "PriorDist": [("prior", (lambda p: p / p.sum())(
                          P(1, 5)))]},
     {"epsilon": 0.1}, grad=["X"])

# --- optimizer ops (LearningRate is an extra input slot) ---
_LR = np.asarray([0.1], np.float32)
spec("sgd", {"Param": F(4, 3), "Grad": F(4, 3), "LearningRate": _LR},
     outs=["ParamOut"])
spec("momentum", {"Param": F(4, 3), "Grad": F(4, 3), "Velocity": F(4, 3),
                  "LearningRate": _LR}, {"mu": 0.9, "use_nesterov": False},
     outs=["ParamOut", "VelocityOut"])
spec("adam", {"Param": F(4, 3), "Grad": F(4, 3), "Moment1": F(4, 3) * 0.1,
              "Moment2": P(4, 3) * 0.1, "LearningRate": _LR,
              "Beta1Pow": np.asarray([0.9], np.float32),
              "Beta2Pow": np.asarray([0.999], np.float32)},
     {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8},
     outs=["ParamOut", "Moment1Out", "Moment2Out", "Beta1PowOut",
           "Beta2PowOut"], tol=TOL_EXP)
spec("adamax", {"Param": F(4, 3), "Grad": F(4, 3), "Moment": F(4, 3) * 0.1,
                "InfNorm": P(4, 3), "LearningRate": _LR,
                "Beta1Pow": np.asarray([0.9], np.float32)},
     {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8},
     outs=["ParamOut", "MomentOut", "InfNormOut", "Beta1PowOut"],
     tol=TOL_EXP)
spec("adagrad", {"Param": F(4, 3), "Grad": F(4, 3), "Moment": P(4, 3) * 0.1,
                 "LearningRate": _LR}, {"epsilon": 1e-6},
     outs=["ParamOut", "MomentOut"], tol=TOL_EXP)
spec("decayed_adagrad",
     {"Param": F(4, 3), "Grad": F(4, 3), "Moment": P(4, 3) * 0.1,
      "LearningRate": _LR}, {"decay": 0.95, "epsilon": 1e-6},
     outs=["ParamOut", "MomentOut"], tol=TOL_EXP)
spec("adadelta",
     {"Param": F(4, 3), "Grad": F(4, 3), "AvgSquaredGrad": P(4, 3) * 0.1,
      "AvgSquaredUpdate": P(4, 3) * 0.1},
     {"rho": 0.95, "epsilon": 1e-6},
     outs=["ParamOut", "AvgSquaredGradOut", "AvgSquaredUpdateOut"],
     tol=TOL_EXP)
spec("rmsprop",
     {"Param": F(4, 3), "Grad": F(4, 3), "MeanSquare": P(4, 3) * 0.1,
      "Moment": F(4, 3) * 0.1, "LearningRate": _LR},
     {"decay": 0.9, "momentum": 0.9, "epsilon": 1e-6},
     outs=["ParamOut", "MeanSquareOut", "MomentOut"], tol=TOL_EXP)
spec("ftrl", {"Param": F(4, 3), "Grad": F(4, 3),
              "SquaredAccumulator": P(4, 3) * 0.1,
              "LinearAccumulator": F(4, 3) * 0.1, "LearningRate": _LR},
     {"l1": 0.1, "l2": 0.1, "lr_power": -0.5},
     outs=["ParamOut", "SquaredAccumOut", "LinearAccumOut"], tol=TOL_EXP)
spec("proximal_gd", {"Param": F(4, 3), "Grad": F(4, 3),
                     "LearningRate": _LR}, {"l1": 0.01, "l2": 0.01},
     outs=["ParamOut"], tol=TOL_EXP)
spec("proximal_adagrad",
     {"Param": F(4, 3), "Grad": F(4, 3), "Moment": P(4, 3) * 0.1,
      "LearningRate": _LR}, {"l1": 0.01, "l2": 0.01},
     outs=["ParamOut", "MomentOut"], tol=TOL_EXP)
spec("average_accumulates",
     {"Param": F(4, 3), "in_sum_1": F(4, 3), "in_sum_2": F(4, 3),
      "in_sum_3": F(4, 3),
      "in_num_accumulates": np.asarray([3], np.int64),
      "in_old_num_accumulates": np.asarray([2], np.int64),
      "in_num_updates": np.asarray([5], np.int64)},
     {"average_window": 0.15, "max_average_window": 10,
      "min_average_window": 2},
     outs=["out_sum_1", "out_sum_2", "out_sum_3", "out_num_accumulates",
           "out_old_num_accumulates", "out_num_updates"])

# --- tensor manipulation ---
spec("assign", {"X": F(3, 4)}, grad=["X"])
spec("assign_value", {}, {"shape": [2, 3], "dtype": np_dtype_to_proto("float32"),
                          "fp32_values": [float(v) for v in F(6)]})
spec("fill", {}, {"shape": [2, 3], "dtype": np_dtype_to_proto("float32"),
                  "value": [float(v) for v in F(6)]})
spec("fill_constant", {}, {"shape": [2, 3], "dtype": np_dtype_to_proto("float32"),
                           "value": 1.5})
spec("fill_constant_batch_size_like", {"Input": F(4, 3)},
     {"shape": [-1, 7], "dtype": np_dtype_to_proto("float32"), "value": 2.0,
      "input_dim_idx": 0, "output_dim_idx": 0})
spec("concat", {"X": [("cc_a", F(3, 2)), ("cc_b", F(3, 4))]}, {"axis": 1},
     grad=["cc_a", "cc_b"])
spec("sum", {"X": [("sm_a", F(3, 4)), ("sm_b", F(3, 4)),
                   ("sm_c", F(3, 4))]}, grad=["sm_a", "sm_b"])
spec("split", {"X": F(4, 6)}, {"axis": 1, "num": 2, "sections": []},
     outs=[("Out", 2)], grad=["X"])
spec("reshape", {"X": F(3, 4)}, {"shape": [2, 6]}, grad=["X"])
spec("reshape2", {"X": F(3, 4)}, {"shape": [2, 6]},
     outs=["Out", "XShape"], grad=["X"])
spec("squeeze", {"X": F(3, 1, 4)}, {"axes": [1]}, grad=["X"])
spec("unsqueeze", {"X": F(3, 4)}, {"axes": [1]}, grad=["X"])
spec("transpose", {"X": F(3, 4, 5)}, {"axis": [0, 2, 1]}, grad=["X"])
spec("transpose2", {"X": F(3, 4, 5)}, {"axis": [0, 2, 1]},
     outs=["Out", "XShape"], grad=["X"])
spec("reverse", {"X": F(3, 4)}, {"axis": [1]}, grad=["X"])
spec("expand", {"X": F(2, 3)}, {"expand_times": [2, 2]}, grad=["X"])
spec("pad", {"X": F(3, 4)}, {"paddings": [1, 1, 0, 2], "pad_value": 0.5},
     grad=["X"])
spec("crop", {"X": F(5, 6), "Y": F(3, 4)}, {"offsets": [1, 1]},
     grad=["X"])
spec("slice", {"Input": F(4, 6)},
     {"axes": [0, 1], "starts": [1, 2], "ends": [3, 5]}, grad=["Input"])
spec("gather", {"X": F(6, 3), "Index": I((4,), hi=6)}, grad=["X"])
spec("scatter", {"X": F(6, 3), "Ids": np.asarray([1, 3], np.int64),
                 "Updates": F(2, 3)}, grad=["X", "Updates"])
spec("one_hot", {"X": I((4, 1), hi=6)}, {"depth": 6})
spec("shape", {"Input": F(3, 4)})
spec("lookup_table", {"W": F(10, 4), "Ids": I((5, 1), hi=10)},
     {"padding_idx": -1}, grad=["W"])
spec("lookup_table_grad",
     {"W": F(10, 4), "Ids": I((5, 1), hi=10),
      "Out@GRAD": [("lt_og", F(5, 4))]},
     {"padding_idx": -1, "is_sparse": False}, outs=["W@GRAD"])
spec("multiplex", {"Ids": I((4, 1), hi=2),
                   "X": [("mx_a", F(4, 3)), ("mx_b", F(4, 3))]},
     grad=["mx_a", "mx_b"])
spec("bilinear_interp", {"X": F(2, 3, 4, 4)}, {"out_h": 8, "out_w": 8},
     grad=["X"])
spec("mean_iou", {"Predictions": I((8,), hi=4), "Labels": I((8,), hi=4)},
     {"num_classes": 4}, outs=["OutMeanIou", "OutWrong", "OutCorrect"])
spec("fake_dequantize_max_abs",
     {"X": I((3, 4), hi=127, lo=-127).astype(np.float32),
      "Scale": np.asarray([0.5], np.float32)}, {"max_range": 127.0})
spec("is_empty", {"X": F(2, 3)})

# --- metrics ---
spec("accuracy", {"Indices": I((4, 2), hi=5), "Label": I((4, 1), hi=5)},
     outs=["Accuracy", "Correct", "Total"])
spec("auc", {"Predict": rng.uniform(0, 1, (8, 2)).astype(np.float32),
             "Label": I((8, 1), hi=2),
             "TP": np.zeros(200, np.int64), "FP": np.zeros(200, np.int64),
             "TN": np.zeros(200, np.int64), "FN": np.zeros(200, np.int64)},
     {"num_thresholds": 200},
     outs=["AUC", "TPOut", "FPOut", "TNOut", "FNOut"])
spec("precision_recall",
     {"MaxProbs": rng.uniform(0, 1, (6, 1)).astype(np.float32),
      "Indices": I((6, 1), hi=3), "Labels": I((6, 1), hi=3),
      "Weights": P(6, 1), "StatesInfo": np.zeros((3, 4), np.float32)},
     {"class_number": 3},
     outs=["BatchMetrics", "AccumMetrics", "AccumStatesInfo"])

# --- random (stateful; jax PRNG is platform-deterministic) ---
spec("uniform_random", {}, {"shape": [4, 5], "min": -1.0, "max": 1.0,
                            "dtype": np_dtype_to_proto("float32")})
spec("gaussian_random", {}, {"shape": [4, 5], "mean": 0.0, "std": 1.0,
                             "dtype": np_dtype_to_proto("float32")})
spec("uniform_random_batch_size_like", {"Input": F(3, 2)},
     {"shape": [-1, 5], "min": -1.0, "max": 1.0, "dtype": np_dtype_to_proto("float32"),
      "input_dim_idx": 0, "output_dim_idx": 0})
spec("gaussian_random_batch_size_like", {"Input": F(3, 2)},
     {"shape": [-1, 5], "mean": 0.0, "std": 1.0, "dtype": np_dtype_to_proto("float32"),
      "input_dim_idx": 0, "output_dim_idx": 0})
spec("sampling_id", {"X": (lambda p: p / p.sum(1, keepdims=True))(P(4, 6))})
spec("random_crop", {"X": F(2, 3, 8, 8), "Seed": np.asarray([7], np.int64)},
     {"shape": [6, 6]}, outs=["Out"])

# --- sequence ops (LoD feeds) ---
_sq = F(3, 5, 4)
spec("sequence_pool", {"X": lodt(_sq, [5, 3, 2])}, {"pooltype": "SUM"},
     grad=["X"])
spec("sequence_softmax", {"X": lodt(F(3, 5, 1), [5, 3, 2])}, grad=["X"],
     tol=TOL_EXP)
spec("sequence_reshape", {"X": lodt(F(2, 4, 6), [4, 2])}, {"new_dim": 12})
spec("sequence_concat",
     {"X": [("sq_a", lodt(F(2, 4, 3), [4, 2])),
            ("sq_b", lodt(F(2, 3, 3), [2, 3]))]})
spec("sequence_erase", {"X": lodt(I((2, 5, 1), hi=6).astype(np.int64),
                                  [5, 4])}, {"tokens": [2, 3]})
spec("sequence_expand", {"X": F(2, 3), "Y": lodt(F(2, 5, 1), [2, 5])})
spec("sequence_slice", {"X": lodt(F(2, 5, 3), [5, 4]),
                        "Offset": np.asarray([[1], [0]], np.int64),
                        "Length": np.asarray([[2], [3]], np.int64)})
spec("sequence_conv", {"X": lodt(F(2, 6, 4), [6, 4]),
                       "Filter": F(3 * 4, 5)},
     {"contextLength": 3, "contextStart": -1},
     grad=["Filter"], tol=TOL_MM)
spec("lod_reset", {"X": lodt(F(2, 4, 3), [4, 2])},
     {"target_lod": [0, 2, 6]})
spec("gru", {"Input": lodt(F(2, 5, 9), [5, 3]), "Weight": F(3, 9),
             "H0": F(2, 3), "Bias": F(1, 9)},
     {"activation": "tanh", "gate_activation": "sigmoid",
      "is_reverse": False}, outs=["Hidden"], tol=TOL_MM)
spec("gru_unit", {"Input": F(4, 9), "HiddenPrev": F(4, 3),
                  "Weight": F(3, 9), "Bias": F(1, 9)},
     {"activation": "tanh", "gate_activation": "sigmoid"},
     outs=["Hidden", "Gate", "ResetHiddenPrev"],
     grad=["Input", "HiddenPrev", "Weight"], tol=TOL_MM)
spec("lstm", {"Input": lodt(F(2, 5, 12), [5, 3]), "Weight": F(3, 12),
              "Bias": F(1, 12), "H0": F(2, 3), "C0": F(2, 3)},
     outs=["Hidden", "Cell"], tol=TOL_MM)
spec("lstm_unit", {"X": F(4, 12), "C_prev": F(4, 3)},
     {"forget_bias": 0.0}, outs=["C", "H"],
     grad=["X", "C_prev"], tol=TOL_EXP)
spec("lstmp", {"Input": lodt(F(2, 5, 12), [5, 3]), "Weight": F(2, 12),
               "ProjWeight": F(3, 2), "Bias": F(1, 12),
               "H0": F(2, 2), "C0": F(2, 3)},
     {"proj_activation": "tanh"}, outs=["Projection", "Cell"], tol=TOL_MM)
spec("edit_distance",
     {"Hyps": lodt(I((2, 4, 1), hi=6), [4, 3]),
      "Refs": lodt(I((2, 4, 1), hi=6), [3, 4])},
     {"normalized": False}, outs=["Out", "SequenceNum"])
spec("seq_cross_attention",
     {"Q": lodt(F(2, 4, 6), [4, 3]), "K": lodt(F(2, 5, 6), [5, 2]),
      "V": lodt(F(2, 5, 6), [5, 2])}, {},
     grad=["Q", "K", "V"], tol=TOL_MM)

def lodt2(n_inner, width, dim):
    """Level-2 LoDTensor: outer offsets over inner seqs, inner over
    tokens."""
    rng2 = np.random.RandomState(3)
    inner_lens = [rng2.randint(1, width + 1) for _ in range(sum(n_inner))]
    total = sum(inner_lens)
    data = rng2.randn(total, dim).astype(np.float32)
    inner_offs = np.concatenate([[0], np.cumsum(inner_lens)]).tolist()
    outer_offs = np.concatenate([[0], np.cumsum(n_inner)]).tolist()
    return LoDTensor(data, [outer_offs, inner_offs])


spec("sub_nested_seq",
     {"X": lodt2([2, 3], 4, 3),
      "SelectedIndices": lodt(I((2, 2, 1), hi=2), [1, 2])},
     grad=["X"])

spec("scale_sub_region",
     {"X": F(2, 3, 4, 4),
      "Indices": np.asarray([[1, 2, 1, 3, 2, 4], [2, 3, 2, 2, 1, 1]],
                            np.int64)},
     {"value": 2.0}, grad=["X"])

spec("kmax_seq_score", {"X": lodt(F(2, 6, 1), [6, 3])},
     {"beam_size": 2})

spec("lambda_rank",
     {"Score": lodt(F(2, 5, 1), [5, 3]),
      "Label": lodt(I((2, 5, 1), hi=3).astype(np.float32), [5, 3])},
     {"NDCG_num": 3}, grad=["Score"], tol=TOL_EXP)

# --- CRF / CTC ---
spec("linear_chain_crf",
     {"Emission": lodt(F(2, 5, 4), [5, 3]),
      "Label": lodt(I((2, 5, 1), hi=4), [5, 3]),
      "Transition": F(6, 4)},
     outs=["LogLikelihood"], grad=["Emission", "Transition"], tol=TOL_EXP)
spec("crf_decoding",
     {"Emission": lodt(F(2, 5, 4), [5, 3]), "Transition": F(6, 4)},
     outs=["ViterbiPath"])
spec("warpctc",
     {"Logits": lodt(F(2, 6, 5), [6, 5]),
      "Label": lodt(I((2, 3, 1), hi=4, lo=1), [3, 2])},
     {"blank": 0, "norm_by_times": False},
     outs=["Loss"], grad=["Logits"], tol=TOL_EXP)
spec("ctc_align", {"Input": lodt(I((2, 6, 1), hi=4), [6, 5])},
     {"blank": 0, "padding_value": 0}, outs=["Output"])

# --- detection ---
spec("iou_similarity", {"X": rng.uniform(0, 10, (4, 4)).astype(np.float32),
                        "Y": rng.uniform(0, 10, (5, 4)).astype(np.float32)})
spec("box_coder",
     {"PriorBox": rng.uniform(0, 10, (5, 4)).astype(np.float32),
      "PriorBoxVar": P(5, 4) * 0.1,
      "TargetBox": rng.uniform(-1, 1, (3, 5, 4)).astype(np.float32)},
     {"code_type": "decode_center_size"}, outs=["OutputBox"], tol=TOL_EXP)
spec("prior_box", {"Input": F(1, 3, 4, 4), "Image": F(1, 3, 32, 32)},
     {"min_sizes": [4.0], "max_sizes": [8.0], "aspect_ratios": [2.0],
      "flip": True, "clip": True, "variances": [0.1, 0.1, 0.2, 0.2],
      "offset": 0.5, "step_w": 0.0, "step_h": 0.0},
     outs=["Boxes", "Variances"])
spec("bipartite_match",
     {"DistMat": rng.uniform(0, 1, (2, 3, 6)).astype(np.float32)},
     {"match_type": "per_prediction", "dist_threshold": 0.5},
     outs=["ColToRowMatchIndices", "ColToRowMatchDist"])
spec("mine_hard_examples",
     {"ClsLoss": rng.uniform(0, 2, (2, 8)).astype(np.float32),
      "MatchIndices": np.asarray([[0, -1, -1, 1, -1, -1, -1, -1],
                                  [-1, 0, -1, -1, -1, 1, -1, -1]],
                                 np.int64)},
     {"mining_type": "max_negative", "neg_pos_ratio": 2.0,
      "sample_size": -1}, outs=["NegIndices", "UpdatedMatchIndices"])
spec("target_assign",
     {"X": F(2, 3, 4),
      "MatchIndices": np.asarray([[0, -1, 2, -1], [1, -1, -1, 0]],
                                 np.int64)},
     {"mismatch_value": 0}, outs=["Out", "OutWeight"])
spec("gather_encoded_target",
     {"Encoded": F(2, 3, 4, 4),
      "MatchIndices": np.asarray([[0, -1, 2, -1], [1, -1, -1, 0]],
                                 np.int64)},
     outs=["Out", "OutWeight"])
spec("polygon_box_transform", {"Input": F(1, 4, 3, 3)}, outs=["Output"])
spec("roi_pool",
     {"X": F(1, 2, 8, 8),
      "ROIs": np.asarray([[0, 1, 1, 5, 5], [0, 2, 2, 7, 7]], np.float32)},
     {"pooled_height": 2, "pooled_width": 2, "spatial_scale": 1.0},
     outs=["Out", "Argmax"])

# --- parallel / kernels (dense single-place paths) ---
spec("ring_attention", {"Q": F(2, 2, 8, 4), "K": F(2, 2, 8, 4),
                        "V": F(2, 2, 8, 4)}, {"causal": True},
     grad=["Q", "K", "V"], tol=TOL_MM)
spec("moe_ffn", {"X": F(6, 4), "RouterW": F(4, 2), "W1": F(2, 4, 8),
                 "W2": F(2, 8, 4)}, {"capacity_factor": 2.0},
     grad=["X", "W1", "W2"], tol=TOL_MM)
spec("sharding_constraint", {"X": F(4, 4)}, {"spec": ("dp", None)},
     grad=["X"])

# --- beam search (one-op device form; cf. tests/test_beam_search.py) ---
spec("beam_search",
     {"pre_ids": I((4, 1), hi=5, lo=1),
      "pre_scores": rng.uniform(-2, 0, (4, 1)).astype(np.float32),
      "ids": I((4, 6), hi=6),
      "scores": np.log((lambda p: p / p.sum(1, keepdims=True))(
          P(4, 6))).astype(np.float32)},
     {"beam_size": 2, "end_id": 0},
     outs=["selected_ids", "selected_scores", "parent_idx"])

SKIPS = {
    "beam_search_decode": "host-side trace reconstruction over per-step "
                          "host arrays (covered by tests/test_beam_search.py"
                          " and the v2 generation workflow test)",
}


# ---------------------------------------------------------------------------
# Justified-refusal ledger — the op-parity TAIL, closed explicitly.
#
# Every v2 surface that deliberately raises NotImplementedError is
# enumerated here with its justification and the supported route.  The
# artifact carries the ledger verbatim, and tests/test_refusal_ledger.py
# asserts the set of in-tree NotImplementedError guards equals this set,
# so the tail cannot grow (or rot) silently: adding a new refusal without
# a ledger entry — or listing one that no longer exists — fails the suite.
#
# kind="refusal": the whole v2 symbol is refused (callable exists for
# source compatibility; every call raises).  kind="partial": the layer IS
# ported and a specific argument/mode raises; ``param`` names it.
# ---------------------------------------------------------------------------

REFUSALS = {
    # -- whole-symbol refusals (3) --
    "get_output": dict(
        kind="refusal",
        reason="layers here have exactly one output value; auxiliary "
               "outputs ride as attributes (e.g. lstm_step(...).state)",
        use=".state attribute / fluid.layers"),
    "cross_entropy_over_beam": dict(
        kind="refusal",
        reason="beam-training (CRF-over-beam) requires the gserver beam "
               "expansion records, which the XLA lowering never builds",
        use="layer.beam_search for generation + per-step "
            "cross_entropy_cost for training"),
    "SubsequenceInput": dict(
        kind="refusal",
        reason="nested-sequence (level-2) recurrent_group: level-k LoD "
               "data is ported but the scan-over-subsequences control "
               "form is not",
        use="fluid.layers.sequence_* on the inner level, or seq_reshape"),
    # -- partial guards: the layer works, one argument/mode refuses --
    "context_projection": dict(
        kind="partial", param="padding_attr",
        reason="trainable context padding is a gserver parameter; zero "
               "padding (padding_attr=False) is the ported semantics",
        use="padding_attr=False"),
    "conv_operator": dict(
        kind="partial", param="trans / per-sample kernels",
        reason="transposed variant and reference ConvOperator's "
               "per-sample kernel stream have no grouped-conv lowering",
        use="conv_projection(trans=True) / img_conv_layer"),
    "seq_reshape": dict(
        kind="partial", param="bias_attr",
        reason="reshape is data movement; the reference bias add after "
               "it is not ported",
        use="seq_reshape(...) + layer.addto with a bias layer"),
    "selective_fc": dict(
        kind="partial", param="select",
        reason="column selection is a gserver execution optimization; "
               "the full fc computes identical selected values",
        use="select=None (full fc)"),
    "upsample": dict(
        kind="partial", param="mask-free / upsample_size / pad_out_*",
        reason="needs the paired max-pool mask; explicit output sizing "
               "is not ported (output is scale * input)",
        use="bilinear_interp for mask-free interpolation"),
    "img_conv3d": dict(
        kind="partial", param="trans",
        reason="transposed 3-D convolution has no lowering",
        use="img_conv3d(trans=False)"),
    "prelu": dict(
        kind="partial", param="partial_sum>1",
        reason="per-group alpha sharing is not ported",
        use="partial_sum=1 (per-element) or channel_shared=True"),
    "sub_seq": dict(
        kind="partial", param="bias_attr",
        reason="subsequence extraction is data movement; the post-slice "
               "bias is not ported",
        use="sub_seq(...) + layer.addto"),
    "lstm_step": dict(
        kind="partial", param="gate/state activations",
        reason="the lstm_unit op fixes the standard tanh/sigmoid gate "
               "math; non-default step activations are not ported",
        use="default activations"),
    "multibox_loss": dict(
        kind="partial", param="label / neg_overlap",
        reason="the v1 packed-label stream and the mining op's "
               "negative-overlap threshold are not ported",
        use="(gt_box, gt_label) layers; tune neg_pos_ratio"),
    "nce": dict(
        kind="partial", param="neg_distribution / weight / multi-input",
        reason="only the uniform sampler is ported; per-example "
               "weighting and implicit multi-input concat are not",
        use="uniform sampler; layer.scaling; concat inputs first"),
    "hsigmoid": dict(
        kind="partial", param="multi-input",
        reason="implicit multi-input concat is not ported",
        use="concat inputs first"),
    "lambda_cost": dict(
        kind="partial", param="max_sort_size",
        reason="partial-sort truncation is a CPU-side optimization; the "
               "whole candidate list is ranked",
        use="default (full ranking)"),
}


# ---------------------------------------------------------------------------
# Composite programs: build with the fluid front-end, run on both places,
# compare every fetch; credit every op type in the program (fwd + emitted
# grad ops) to the composite.
# ---------------------------------------------------------------------------

def _run_program(build, place):
    main = fluid.Program()
    startup = fluid.Program()
    scope = Scope()
    with fluid.scope_guard(scope):
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                feed, fetch_list = build()
        exe = fluid.Executor(place)
        exe.run(startup)
        outs = exe.run(main, feed=feed, fetch_list=fetch_list)
    op_types = set()

    def _collect(block):
        for op in block.ops:
            op_types.add(op.type)
            sub = op.attr("sub_block")
            if sub is not None:
                _collect(main.block(sub) if isinstance(sub, int) else sub)

    for block in main.blocks:
        _collect(block)
    return [np.asarray(o) for o in outs], op_types


def composite_while_array():
    """While + TensorArray: while, create_array, write_to_array,
    read_from_array, lod_array_length, increment, less_than."""
    i = layers.fill_constant(shape=[1], dtype="int64", value=0)
    n = layers.fill_constant(shape=[1], dtype="int64", value=5)
    x = layers.fill_constant(shape=[3], dtype="float32", value=1.0)
    arr = layers.create_array("float32", element_shape=[3], capacity=8)
    cond = layers.less_than(x=i, y=n)
    w = layers.While(cond=cond)
    with w.block():
        xi = layers.scale(x=x, scale=2.0)
        layers.array_write(xi, i, array=arr)
        layers.increment(x=i, value=1.0, in_place=True)
        layers.less_than(x=i, y=n, cond=cond)
    j = layers.fill_constant(shape=[1], dtype="int64", value=3)
    read = layers.array_read(arr, j)
    length = layers.array_length(arr)
    return {}, [read, length]


def composite_ifelse():
    """IfElse: conditional_block, split_lod_tensor, merge_lod_tensor."""
    x = layers.data(name="ie_x", shape=[4], dtype="float32")
    zero = layers.fill_constant(shape=[1], dtype="float32", value=0.0)
    row_sum = layers.reduce_sum(x, dim=1, keep_dim=True)
    cond = layers.greater_than(row_sum, zero)
    ie = layers.IfElse(cond)
    with ie.true_block():
        xt = ie.input(x)
        ie.output(layers.scale(xt, scale=3.0))
    with ie.false_block():
        xf = ie.input(x)
        ie.output(layers.scale(xf, scale=-1.0))
    pred = ie()
    xv = np.random.RandomState(3).randn(6, 4).astype(np.float32)
    return {"ie_x": xv}, [pred]


def composite_dynrnn():
    """DynamicRNN: recurrent, lod_rank_table, lod_tensor_to_array,
    array_to_lod_tensor, max_sequence_len, shrink_rnn_memory, ..."""
    x = layers.data(name="dr_x", shape=[3], dtype="float32", lod_level=1)
    rnn = layers.DynamicRNN()
    with rnn.block():
        x_t = rnn.step_input(x)
        h = rnn.memory(shape=[3], batch_ref=x, init_value=0.0)
        h_new = layers.elementwise_add(x=h, y=x_t)
        rnn.update_memory(h, h_new)
        rnn.output(h_new)
    out = rnn()
    final = rnn.final_states[0]
    padded = np.random.RandomState(4).randn(3, 4, 3).astype(np.float32)
    feed = {"dr_x": lodt(padded, [4, 2, 3])}
    return feed, [out, final]


def composite_lod_array_round_trip():
    """lod_rank_table + lod_tensor_to_array + array_to_lod_tensor +
    max_sequence_len + reorder_lod_tensor_by_rank + shrink_rnn_memory."""
    x = layers.data(name="rt_x", shape=[2], dtype="float32", lod_level=1)
    table = layers.lod_rank_table(x)
    arr = layers.lod_tensor_to_array(x, table)
    back = layers.array_to_lod_tensor(arr, table)
    mlen = layers.max_sequence_len(table)
    reordered = layers.reorder_lod_tensor_by_rank(x, table)
    i0 = layers.fill_constant(shape=[1], dtype="int64", value=0)
    shrunk = layers.shrink_memory(back, i0, table)
    feed = {"rt_x": lodt(np.random.RandomState(5).randn(2, 3, 2)
                         .astype(np.float32), [3, 2])}
    return feed, [back, mlen, reordered, shrunk]


def composite_conditional_block():
    """ConditionalBlock (conditional_block op) scalar gating."""
    flag = layers.data(name="cb_flag", shape=[1], dtype="float32",
                       append_batch_size=False)
    zero = layers.fill_constant(shape=[1], dtype="float32", value=0.0)
    out = layers.fill_constant(shape=[1], dtype="float32", value=-1.0)
    cond = layers.greater_than(flag, zero)
    cb = layers.ConditionalBlock([cond])
    with cb.block():
        v = layers.scale(x=flag, scale=10.0)
        layers.assign(v, out)
    return {"cb_flag": np.asarray([3.0], np.float32)}, [out]


def composite_select():
    """In-program CSP select (ISSUE 8 parity rider; reference
    operators/select_op.cc): channel_create + go producer +
    channel_send + select(recv|recv) + the device consumer of the
    received value.  Credits: select, channel_create, channel_send,
    go."""
    from paddle_tpu.fluid import concurrency as C

    x = layers.data(name="sel_x", shape=[3], dtype="float32")
    ch_idle = C.program_make_channel(dtype="float32", capacity=1)
    ch_live = C.program_make_channel(dtype="float32", capacity=1)
    with C.ProgramGo():
        C.program_channel_send(ch_live, layers.scale(x, scale=2.0))
    got_a = layers.data(name="sel_got_a", shape=[3], dtype="float32")
    got_b = layers.data(name="sel_got_b", shape=[3], dtype="float32")
    idx = C.program_select([("recv", ch_idle, got_a),
                            ("recv", ch_live, got_b)], timeout=10.0)
    out = layers.scale(got_b, scale=10.0)
    xv = np.random.RandomState(6).randn(2, 3).astype(np.float32)
    return {"sel_x": xv}, [idx, out]


COMPOSITES = {
    "while_array": composite_while_array,
    "ifelse": composite_ifelse,
    "dynrnn": composite_dynrnn,
    "lod_array_round_trip": composite_lod_array_round_trip,
    "conditional_block": composite_conditional_block,
    "select": composite_select,
}


# ---------------------------------------------------------------------------
# Runners
# ---------------------------------------------------------------------------

def _make_optest(op, s):
    t = OpTest()
    t.op_type = op
    t.inputs = s["inputs"]
    t.attrs = s["attrs"]
    outs = {}
    for o in s["outs"]:
        if isinstance(o, tuple):   # multi-output slot: (slot, count)
            slot, cnt = o
            outs[slot] = [("%s_%s_%d" % (op, slot.lower(), k),
                           np.zeros(1, np.float32)) for k in range(cnt)]
        else:
            outs[o] = np.zeros(1, np.float32)
    t.outputs = outs
    return t


def _fetch_names(t):
    names = []
    for slot, val in t.outputs.items():
        entries = val if isinstance(val, list) else [(slot, val)]
        names.extend(n for n, _ in entries)
    return names


def _compare(name, a, b, atol, rtol):
    a = np.asarray(a)
    b = np.asarray(b)
    if a.shape != b.shape:
        return "shape mismatch %s: %s vs %s" % (name, a.shape, b.shape)
    if a.dtype.kind in "iub":
        if not np.array_equal(a, b):
            return "int mismatch %s: %d differing" % (
                name, int((a != b).sum()))
        return None
    err = np.abs(a.astype(np.float64) - b.astype(np.float64))
    denom = np.maximum(np.abs(a).astype(np.float64), 1.0)
    if not (err <= atol + rtol * denom).all():
        return "float mismatch %s: max_abs %.3e max_rel %.3e" % (
            name, err.max(), (err / denom).max())
    return None


def _grad_program(t, wrt):
    """Build the one-op program + scalar head + calc_gradient; returns
    (main, startup, feed, grad_names)."""
    main, startup, feed = t._build()
    grng = np.random.RandomState(11)
    with fluid.program_guard(main, startup):
        block = main.global_block()
        parts = []
        for oname in t._first_float_outputs():
            ovar = block.var(oname)
            w = grng.uniform(0.5, 1.5, [int(d) for d in ovar.shape]
                             ).astype(np.float32)
            wvar = layers.assign(w)
            wvar.stop_gradient = True
            parts.append(layers.reduce_sum(
                layers.elementwise_mul(ovar, wvar)))
        head = parts[0] if len(parts) == 1 else layers.sums(parts)
        loss = layers.reduce_sum(head)
        grads = fluid.backward.calc_gradient(
            loss, [block.var(n) for n in wrt])
    return main, startup, feed, [g.name for g in grads]


def _run_on(place, main, feed, fetch_names):
    exe = fluid.Executor(place)
    scope = Scope()
    with fluid.scope_guard(scope):
        return exe.run(main, feed=feed, fetch_list=fetch_names)


def run_exact(op, s, cpu, dev):
    # Matmul-family ops are checked at the exact-f32 precision contract:
    # the TPU backend's DEFAULT multiplies f32 in bf16 passes (measured
    # 3e-3..4e-2 rel vs an f64 oracle on which the CPU backend sits at
    # ~1e-7), so the check pins FLAGS.matmul_precision='highest' — the
    # documented knob (MIGRATION.md) — and holds the chip to ~1e-4.
    exact_f32 = s["tol"] is TOL_MM
    prev = FLAGS.matmul_precision
    if exact_f32:
        FLAGS.matmul_precision = "highest"
    try:
        return _run_exact_inner(op, s, cpu, dev)
    finally:
        if exact_f32:
            FLAGS.matmul_precision = prev


def _run_exact_inner(op, s, cpu, dev):
    t = _make_optest(op, s)
    names = _fetch_names(t)
    atol, rtol = s["tol"]
    ref = t.run_outputs(cpu, fetch_names=names)
    got = t.run_outputs(dev, fetch_names=names)
    errs = [e for e in (_compare(n, ref[n], got[n], atol, rtol)
                        for n in names) if e]
    grad_checked = False
    if s["grad"]:
        # Grad heads need true output shapes for the weight tensors:
        # rebuild with declared shapes from the CPU run.
        t2 = _make_optest(op, s)
        outs2 = {}
        for slot, val in t.outputs.items():
            entries = val if isinstance(val, list) else [(slot, val)]
            outs2[slot] = [(n, ref[n]) for n, _ in entries] \
                if isinstance(val, list) else ref[entries[0][0]]
        t2.outputs = outs2
        main, startup, feed, gnames = _grad_program(t2, s["grad"])
        g_ref = _run_on(cpu, main, feed, gnames)
        g_dev = _run_on(dev, main, feed, gnames)
        for wname, a, b in zip(s["grad"], g_ref, g_dev):
            e = _compare("d/d%s" % wname, a, b,
                         max(atol, 1e-3), max(rtol, 1e-3))
            if e:
                errs.append(e)
        grad_checked = True
    return errs, grad_checked


def main():
    on_tpu = os.environ.get("TPU_OPTEST") == "1" and not \
        os.environ.get("TPU_OPTEST_SELFCHECK")
    cpu = fluid.CPUPlace()
    dev = fluid.TPUPlace() if on_tpu else fluid.CPUPlace()
    dev_desc = repr(dev.jax_device()) if on_tpu else "cpu-selfcheck"
    only = sys.argv[1:]  # optional op-name filter for debugging

    results = {}
    t_start = time.time()

    # 1) composites first (their credit list gates the skip accounting)
    composite_credit = {}
    for cname, build in COMPOSITES.items():
        if only and cname not in only:
            continue
        try:
            ref, ops_ref = _run_program(build, cpu)
            got, _ = _run_program(build, dev)
            errs = [e for e in (_compare("%s[%d]" % (cname, i), a, b,
                                         1e-4, 1e-4)
                                for i, (a, b) in enumerate(zip(ref, got)))
                    if e]
            status = "pass" if not errs else "fail"
            note = "; ".join(errs)
        except Exception as exc:  # noqa: BLE001 — triaged into the artifact
            status, note, ops_ref = "fail", "%s: %s" % (
                type(exc).__name__, exc), set()
            traceback.print_exc()
        for o in ops_ref:
            composite_credit.setdefault(o, []).append((cname, status, note))
        print("[composite %-22s] %s %s" % (cname, status, note))

    ops = registry.registered_ops()
    for op in ops:
        if only and op not in only:
            continue
        info = registry._registry[op]
        if info.host_op:
            results[op] = dict(
                status="skip", mode="host",
                note="host op: executed by the Executor on the host "
                     "regardless of place (no device lowering to check)")
            continue
        if op in SPECS:
            s = SPECS[op]
            t0 = time.time()
            try:
                errs, grad_checked = run_exact(op, s, cpu, dev)
                status = "pass" if not errs else "fail"
                results[op] = dict(
                    status=status, mode="exact",
                    atol=s["tol"][0], rtol=s["tol"][1],
                    precision=("highest" if s["tol"] is TOL_MM
                               else "default"),
                    grad_checked=grad_checked,
                    seconds=round(time.time() - t0, 2),
                    note="; ".join(errs))
            except Exception as exc:  # noqa: BLE001
                results[op] = dict(
                    status="fail", mode="exact",
                    seconds=round(time.time() - t0, 2),
                    note="%s: %s" % (type(exc).__name__, exc))
                traceback.print_exc()
            print("[%-34s] %s %s" % (op, results[op]["status"],
                                     results[op].get("note", "")[:120]))
        elif op in composite_credit:
            entries = composite_credit[op]
            status = ("pass" if all(s == "pass" for _, s, _ in entries)
                      else "fail")
            results[op] = dict(
                status=status, mode="composite",
                via=[c for c, _, _ in entries],
                note="; ".join(n for _, s, n in entries if n))
        elif op in SKIPS:
            results[op] = dict(status="skip", mode="declared",
                               note=SKIPS[op])
        else:
            results[op] = dict(status="fail", mode="unspecced",
                               note="no spec, no composite credit")

    # registered <op>_grad entries are exercised by the forward spec's
    # grad check (run_exact compares analytic gradients), so they carry
    # the forward op's verdict instead of counting as unspecced
    for op, r in results.items():
        if r["mode"] != "unspecced" or not op.endswith("_grad"):
            continue
        fwd = results.get(op[:-5])
        if fwd is not None and fwd.get("grad_checked"):
            results[op] = dict(
                status=fwd["status"], mode="grad-of-spec",
                via=op[:-5],
                note="checked by %s's grad comparison" % op[:-5])

    if not only:
        npass = sum(1 for r in results.values() if r["status"] == "pass")
        nskip = sum(1 for r in results.values() if r["status"] == "skip")
        nfail = len(results) - npass - nskip
        ngrad = sum(1 for r in results.values() if r.get("grad_checked"))
        artifact = dict(
            meta=dict(
                device=dev_desc,
                oracle="CPUPlace (full pytest suite validates this path "
                       "against references / finite differences)",
                precision_note="ops with precision='highest' pin "
                               "FLAGS.matmul_precision for the check: "
                               "the TPU default multiplies f32 in bf16 "
                               "passes (fast mode, 3e-3..4e-2 rel); "
                               "'highest' is the exact-f32 contract — "
                               "see MIGRATION.md",
                grad_note="grad_checked ops compare the TPU analytic "
                          "gradient (calc_gradient program) against the "
                          "CPU analytic gradient",
                date=time.strftime("%Y-%m-%d %H:%M:%S"),
                total_ops=len(results), passed=npass, failed=nfail,
                skipped=nskip, grad_checked=ngrad,
                refused=sum(1 for r in REFUSALS.values()
                            if r["kind"] == "refusal"),
                partial_guards=sum(1 for r in REFUSALS.values()
                                   if r["kind"] == "partial"),
                wall_seconds=round(time.time() - t_start, 1)),
            refusal_ledger=REFUSALS,
            results=results)
        out = os.path.join(REPO, "TPU_OPTEST_r05.json")
        with open(out, "w") as f:
            json.dump(artifact, f, indent=1, sort_keys=True)
        print("\n%d ops: %d pass, %d fail, %d skip (%d grad-checked) "
              "on %s in %.0fs -> %s" %
              (len(results), npass, nfail, nskip, ngrad, dev_desc,
               time.time() - t_start, out))
        return 1 if nfail else 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
