"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks.run``) call :func:`enable_compile_cache` before their first
compile, so a second run of the same programs loads them instead of
compiling again. The directory is part of the cache key, so it is a
fixed path, never a temporary, pid- or time-derived one.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads that
    directory and nothing is set here; otherwise the cache goes to the
    repository's ``.jax_cache`` (listed in ``.gitignore``).
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
