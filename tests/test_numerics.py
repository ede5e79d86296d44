"""Numerics policy (utils/numerics.py) and hardware-derived budgets
(utils/platform.py)."""

import numpy as np
import pytest

from arrow_matrix_tpu.utils import numerics
from arrow_matrix_tpu.utils.platform import (
    device_memory_budget,
    force_cpu_devices,
)


def test_tolerance_scales_with_terms_and_iters():
    t1 = numerics.relative_tolerance(16, 1)
    assert t1 == pytest.approx(64 * numerics.EPS_F32 * 4.0)
    assert numerics.relative_tolerance(64, 1) == pytest.approx(2 * t1)
    assert numerics.relative_tolerance(16, 10) == pytest.approx(10 * t1)
    # Degenerate inputs clamp instead of vanishing.
    assert numerics.relative_tolerance(0) > 0
    assert numerics.relative_tolerance(1, 0) > 0


def test_relative_error():
    a = np.ones((4, 4), np.float32)
    assert numerics.relative_error(a, a) == 0.0
    assert numerics.relative_error(2 * a, a) == pytest.approx(1.0)
    # Zero reference does not divide by zero.
    assert np.isfinite(numerics.relative_error(a, np.zeros_like(a)))


def test_device_memory_budget_positive():
    # On the virtual-CPU test fixture this resolves via host RAM (or the
    # backend's memory_stats); either way it must be a usable number.
    budget = device_memory_budget()
    assert budget > 0


def test_force_cpu_devices_replaces_existing_count(monkeypatch):
    import os

    # The request must win over an inherited flag value (ADVICE r1).
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    with pytest.warns(UserWarning, match="replacing"):
        force_cpu_devices(8)
    assert "--xla_force_host_platform_device_count=8" in os.environ["XLA_FLAGS"]
    # Same count: no warning, value untouched.
    force_cpu_devices(8)
    assert os.environ["XLA_FLAGS"].count("device_count") == 1


def test_device_memory_budget_refuses_an_unreadable_accelerator():
    # A device that reports no limit is an error on an accelerator —
    # never an assumed budget.
    from arrow_matrix_tpu.utils.platform import device_memory_budget

    class FakeTpu:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {"bytes_in_use": 0}

    with pytest.raises(RuntimeError, match="reports no memory limit"):
        device_memory_budget(FakeTpu())


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    import jax

    from arrow_matrix_tpu.utils.platform import (
        compile_cache_env,
        enable_compile_cache,
    )

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: nothing else is set.
    assert jax.config.jax_compilation_cache_dir == before
    assert compile_cache_env({})["JAX_COMPILATION_CACHE_DIR"] == \
        str(tmp_path)
    assert compile_cache_env({"JAX_COMPILATION_CACHE_DIR": "x"}) == \
        {"JAX_COMPILATION_CACHE_DIR": "x"}


def test_compile_cache_is_repo_anchored_from_any_cwd(tmp_path):
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = repo
    code = ("from arrow_matrix_tpu.utils.platform import "
            "compile_cache_dir; print(compile_cache_dir())")
    got = set()
    for cwd in (repo, str(tmp_path)):
        out = subprocess.run([sys.executable, "-c", code], cwd=cwd,
                             env=env, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr
        got.add(out.stdout.strip())
    assert got == {os.path.join(repo, "bench_cache", "xla_cache")}
