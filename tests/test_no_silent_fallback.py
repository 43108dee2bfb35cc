"""The chip path has no quiet way onto the host CPU (ISSUE 21): asking
for an accelerator on a process that has none raises, the peaks table
has no default row, and the compile cache lands where it is found
again.  (This suite runs with JAX_PLATFORMS=cpu: no accelerator.)"""
import os
import subprocess
import sys

import jax
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core import flags
from paddle_tpu.core.peaks import DEVICE_PEAKS, V5E, device_peaks
from paddle_tpu.core.place import CPUPlace, default_place
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.utils import xplane

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_tpu_place_raises_without_an_accelerator():
    with pytest.raises(RuntimeError, match="no accelerator devices"):
        fluid.TPUPlace().jax_device()
    assert default_place() == CPUPlace()


def test_parallel_executor_use_tpu_raises_without_an_accelerator(
        prog_scope):
    main, _, scope = prog_scope
    with pytest.raises(RuntimeError, match="no accelerator"):
        fluid.ParallelExecutor(use_tpu=True, main_program=main,
                               scope=scope)


def test_make_mesh_raises_when_devices_are_short():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="needs %d devices" % (2 * n)):
        make_mesh({"dp": 2 * n})
    assert make_mesh({"dp": n}).devices.size == n


def test_unknown_device_kind_has_no_peaks():
    with pytest.raises(KeyError, match="no published peaks"):
        device_peaks(jax.devices()[0].device_kind)      # "cpu"
    with pytest.raises(KeyError, match="no published peaks"):
        xplane.category_profile("/nonexistent.xplane.pb", "TPU v9000")
    row = device_peaks(V5E)
    assert (row["bf16_tflops"], row["int8_tops"], row["hbm_gbps"],
            row["hbm_gb"]) == (197.0, 393.0, 819.0, 16.0)
    assert all(r["source"] for r in DEVICE_PEAKS.values())


def test_compile_cache_is_left_to_the_variable_when_set(monkeypatch):
    updates = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append(k))
    flags.ensure_compile_cache()
    assert "jax_compilation_cache_dir" not in updates


def test_compile_cache_stays_off_for_a_cpu_run(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert jax.config.jax_platforms == "cpu"
    assert flags.ensure_compile_cache() is None
    assert not jax.config.jax_compilation_cache_dir


def test_compile_cache_defaults_to_a_fixed_path_under_the_checkout():
    # a process NOT started as a CPU run (nothing here touches a backend)
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    code = ("import importlib.util, jax\n"
            "spec = importlib.util.spec_from_file_location('f', %r)\n"
            "f = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(f)\n"
            "print(f.ensure_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "print(jax.config.jax_persistent_cache_min_compile_time_secs)"
            % os.path.join(REPO, "paddle_tpu", "core", "flags.py"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd="/",
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    want = os.path.join(REPO, ".jax_cache")    # no temp / pid / time part
    assert out.stdout.split() == [want, want, "0"], out.stdout
