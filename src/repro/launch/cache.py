"""JAX's persistent compilation cache, placed for the entry points.

Entry points (``serve_cnn``, ``benchmarks/run.py``, ``chip_smoke.py``)
call :func:`enable_compile_cache` from their ``main()``; importing this
module changes nothing. The cache's path is part of its key, so it is
fixed: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself
and nothing else is set here; otherwise the cache lives in
``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
