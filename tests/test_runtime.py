"""Process-wide JAX settings (``repro.runtime``) and the import graph."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np

from repro import runtime

ROOT = Path(__file__).resolve().parents[1]


def _record_updates(monkeypatch):
    calls = {}
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.__setitem__(k, v))
    return calls


def test_compile_cache_env_var_is_left_to_jax(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert runtime.init_compile_cache() == "/elsewhere/cache"
    assert "jax_compilation_cache_dir" not in calls
    assert calls["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_compile_cache_defaults_to_fixed_ignored_dir_in_checkout(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert runtime.init_compile_cache() == str(runtime.CACHE_DIR)
    assert calls["jax_compilation_cache_dir"] == str(runtime.CACHE_DIR)
    assert runtime.CACHE_DIR.parent == ROOT
    ignored = (ROOT / ".gitignore").read_text().split()
    assert f"{runtime.CACHE_DIR.name}/" in ignored


def test_x64_scope_is_float64_and_restores():
    before = jax.numpy.result_type(float)
    with runtime.x64():
        assert jax.numpy.asarray(np.float64(1.0)).dtype == np.float64
    assert jax.numpy.result_type(float) == before


def test_kernels_import_on_their_own():
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src"), "JAX_PLATFORMS": "cpu"}
    subprocess.run(
        [sys.executable, "-c", "import repro.kernels.sojourn_eval"],
        env=env, check=True, cwd=ROOT,
    )
