"""Native C serving path (reference paddle/capi): a pure-C program
links libpaddle_tpu_capi.so, loads a saved (AOT-exported) model and
serves it — outputs must match the in-process Python predictor."""
import os
import subprocess

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core.scope import Scope

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _save_model(dirname, n, d):
    main, startup = fluid.Program(), fluid.Program()
    scope = Scope()
    with fluid.scope_guard(scope):
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                x = fluid.layers.data(name="x", shape=[d],
                                      dtype="float32")
                h = fluid.layers.fc(x, size=6, act="tanh")
                out = fluid.layers.fc(h, size=3, act="softmax")
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        fluid.io.save_inference_model(
            dirname, ["x"], [out], exe, main_program=main,
            aot_feed_specs={"x": ((n, d), "float32")})
        xs = (0.01 * np.arange(n * d, dtype=np.float32)).reshape(n, d)
        infer = main.clone(for_test=True)
        ref, = exe.run(infer, feed={"x": xs}, fetch_list=[out])
    return np.asarray(ref)


@pytest.fixture(scope="module")
def capi_binary(tmp_path_factory):
    from paddle_tpu import capi

    lib = capi.build()
    exe_path = str(tmp_path_factory.mktemp("capi") / "capi_main")
    src = os.path.join(REPO, "tests", "capi_main.c")
    cmd = ["g++", "-O2", "-o", exe_path, src,
           "-I" + os.path.dirname(capi.header_path()),
           lib, "-Wl,-rpath," + os.path.dirname(lib)]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    return exe_path


@pytest.mark.parametrize("mode", ["predictor", "server"])
def test_c_program_serves_model(tmp_path, capi_binary, mode):
    """mode 'predictor': the classic pd_create_predictor path.  mode
    'server' (ISSUE 9 rider): the same C contract routed through
    pd_create_server — the continuous-batching serving tier's
    in-process API — closing the reference paddle_inference_api.h
    role gap."""
    n, d = 4, 5
    model_dir = str(tmp_path / "model")
    ref = _save_model(model_dir, n, d)

    env = dict(os.environ)
    env.pop("PYTHONPATH", None)  # repo path goes through pd_init
    # the embedded interpreter has no accelerator plugin on its path;
    # serve on host CPU (use_accelerator=0 in the C program too)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [capi_binary, REPO, model_dir, "x", str(n), str(d), mode],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    got = np.asarray([float(v) for v in
                      proc.stdout.strip().split(",")], np.float32)
    np.testing.assert_allclose(got.reshape(ref.shape), ref, atol=1e-5)
