"""JAX's persistent compilation cache, placed from outside.

Entry points (``launch/serve.py``, ``launch/train.py``, the fleet engine,
``chip_smoke.py``) call :func:`use_compile_cache` once at start-up; nothing
calls it at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, that
directory is the cache and no other is set.  Otherwise the cache lives at
one fixed directory inside the checkout, ``<repo>/.jax_cache/`` (listed in
``.gitignore``): the path is part of what a later process looks up, so it
never comes from a temporary name, a pid or the clock.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py: the checkout is three directories up
CHECKOUT_CACHE_DIR = str(
    Path(os.path.abspath(__file__)).parents[3] / ".jax_cache"
)


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    import jax

    path = os.environ.get(ENV_VAR) or CHECKOUT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
