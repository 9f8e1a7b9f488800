"""JAX's persistent compilation cache for the entry points.

``chip_smoke.py``, ``repro.launch.fl_train`` and
``benchmarks/bench_round_engine.py`` call :func:`use_compile_cache` from
their ``main``; library imports and tests set no cache.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing else
is set here.  Otherwise the cache lives at a fixed ``<checkout>/.jax_cache``
(listed in ``.gitignore``): the path is part of what a cached entry is
found under, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
