"""Test config: force JAX onto a virtual 8-device CPU platform.

Mirrors the reference's test strategy (SURVEY.md §4): CPUPlace serves as the
fake device; the 8 virtual devices let distributed tests exercise real mesh
sharding + collectives without TPU hardware (the driver separately dry-runs
the multi-chip path). Must run before jax initializes.
"""
import gc
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # tests need f32 exactness
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# jax.config.update is authoritative over the env var and must run
# pre-backend-init.
import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture(autouse=True, scope="module")
def _no_compiled_step_outlives_its_file():
    """The inference layer's process-wide executor keeps every step it
    compiled, and the profiler lists every live executable in a trace's
    metadata: a test file that reads a trace's modules by name would find
    the `jit_step`s of whichever serving file its worker ran before it.
    Dropped after each file; `shared_executor()` makes the next one."""
    yield
    predictor = sys.modules.get("paddle_tpu.inference.predictor")
    if predictor is not None and predictor._shared_executor is not None:
        predictor._shared_executor = None
        gc.collect()


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Each test gets fresh default programs + scope + name generator."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.fluid import executor as executor_mod

    old_main = framework.switch_main_program(framework.Program())
    old_startup = framework.switch_startup_program(framework.Program())
    old_gen = unique_name.switch()
    old_scope = executor_mod._global_scope
    executor_mod._global_scope = executor_mod.Scope()
    yield
    framework.switch_main_program(old_main)
    framework.switch_startup_program(old_startup)
    unique_name.switch(old_gen)
    executor_mod._global_scope = old_scope


@pytest.fixture
def pinned():
    """The Pallas kernels where their gates admit the shapes, interpreted
    on this CPU, as they run on the TPU."""
    from paddle_tpu.ops.pallas import backend

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(backend, "FORCE_PALLAS", True)
        yield


@pytest.fixture
def described_tpu():
    """Every kernel module takes its TPU branch (no interpreter, the
    in-kernel PRNG), so that a step lowers for `lowering_platforms=("tpu",)`
    or compiles for a described chip from this CPU process."""
    from paddle_tpu.ops.pallas import backend

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(backend, "interpret", lambda: False)
        yield
