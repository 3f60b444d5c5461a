"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``repro.launch.serve.main``,
``benchmarks.run.main``) call :func:`enable` before they compile; nothing
calls it at import time, so tests and library users keep JAX's default.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path inside the checkout: the directory is part of the cache's
# key, so a path built from a temp name, a pid or the time never hits
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.  Otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
