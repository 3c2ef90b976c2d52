"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
:func:`enable_compile_cache` once before they compile anything, so a cold
run on a fresh machine reuses what an earlier run compiled at the same
path.  Tests do not call it.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: ``<checkout>/.jax_cache`` — fixed, because the path is part of the
#: cache key: a directory that moves never hits.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left alone: JAX reads it
    itself and no other directory is set in code.  Otherwise the cache goes
    to ``<checkout>/.jax_cache``."""
    import jax

    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
