"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points (``serve.main``, ``chip_smoke.py``, the benchmark scripts)
call ``enable_compile_cache()`` first thing, so every process of one
command shares compiled programs.  It is never called at import time:
importing a module must not change ``jax.config`` (cimlint's
AST-IMPORT-CONFIG rule).
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache")


def enable_compile_cache() -> str:
    """Return the directory JAX caches compiled programs in.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed.  Otherwise the cache goes to ``<repo>/.jax_cache``:
    a fixed path, since the path is part of what a cache entry is found
    by (git ignores it).
    """
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    path = os.path.normpath(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
