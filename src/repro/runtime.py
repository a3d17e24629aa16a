"""Process-wide JAX settings shared by the library and its entry points.

* :func:`x64` — the one float64 scope.  The exact evaluator and the
  host-side references run inside it; the Pallas kernels still run in
  float32 (see :mod:`repro.kernels.sojourn_eval.ops`).
* :func:`init_compile_cache` — called first by every entry point
  (``chip_smoke.py``, ``benchmarks/run.py``, ``python -m
  repro.obs.report``, the examples) so that a second run of the same
  shapes reads its compiled programs back instead of compiling again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "x64", "init_compile_cache"]

#: Compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is unset.  A fixed
#: path inside the checkout (listed in ``.gitignore``): the cache key
#: includes the path, so a directory that moved between runs would never hit.
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def x64():
    """Context manager enabling float64 for the enclosed JAX calls."""
    return jax.enable_x64(True)


def init_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX (which reads
    it itself) and no other directory is configured.  Every program is
    cached, however quickly it compiled: the kernels compile in about a
    second, under JAX's default threshold.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
