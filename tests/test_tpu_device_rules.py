"""The rules that keep device numbers and the compile cache honest:
where the persistent compile cache goes, and that no utilization is ever
computed against a peak the device table does not hold."""
import json
import os
import sys
from unittest import mock

import jax
import pytest

import paddle_tpu
from paddle_tpu.telemetry import cost

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _armed_updates(monkeypatch):
    """The jax.config.update calls _arm_compile_cache makes on a host
    that is not pinned to the CPU (recorded, not applied)."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with mock.patch.object(jax.config, "update") as update:
        paddle_tpu._arm_compile_cache()
    return {c.args[0]: c.args[1] for c in update.call_args_list}


def test_compile_cache_env_var_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert paddle_tpu.compile_cache_dir() is None
    updates = _armed_updates(monkeypatch)
    # JAX reads the variable itself: the package names no directory
    assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_compile_cache_path_is_fixed_inside_the_checkout(monkeypatch,
                                                         tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir(tmp_path)
    here = paddle_tpu.compile_cache_dir()
    monkeypatch.chdir(REPO)
    assert paddle_tpu.compile_cache_dir() == here
    assert here == os.path.join(REPO, ".jax_cache")
    updates = _armed_updates(monkeypatch)
    assert updates["jax_compilation_cache_dir"] == here
    # small, quickly compiled executables are stored too
    assert updates["jax_persistent_cache_min_entry_size_bytes"] == -1


def test_peak_flops_raises_on_a_device_not_in_the_table():
    assert jax.devices()[0].device_kind.lower() == "cpu"
    with pytest.raises(ValueError, match="not in the peak table"):
        cost.peak_flops_per_chip()


def test_bench_smoke_on_cpu_prints_null_mfu(monkeypatch, capsys):
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    monkeypatch.setattr(sys, "argv", ["bench.py", "--smoke"])
    with mock.patch.dict(os.environ):  # --smoke setdefaults BENCH_* knobs
        for k in [k for k in os.environ if k.startswith("BENCH_")]:
            del os.environ[k]
        bench.main()
    (row,) = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
              if ln.startswith("{")]
    assert row["value"] > 0
    assert row["mfu"] is None and row["vs_baseline"] is None
