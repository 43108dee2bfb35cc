"""Test env: 8 virtual CPU devices so multi-device SPMD paths are exercised
without TPU hardware (SURVEY §4.3: reference simulates clusters with fake
multi-place lists; here a forced host-device mesh plays that role).

Tests run on the CPU: the platform is forced (not defaulted) before
jax is imported, so the suite never takes a chip even on a machine that
has one.  Spawned children inherit the environment."""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")  # in case jax was imported first

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.core.scope import Scope


@pytest.fixture
def prog_scope():
    """Fresh main/startup programs + scope + name generator per test."""
    main = fluid.Program()
    startup = fluid.Program()
    scope = Scope()
    with fluid.scope_guard(scope):
        with fluid.program_guard(main, startup):
            with fluid.unique_name.guard():
                yield main, startup, scope


@pytest.fixture
def exe():
    return fluid.Executor(fluid.CPUPlace())
