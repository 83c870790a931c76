"""JAX's persistent compilation cache, switched on by entry points only.

Library modules never touch it: importing `repro` leaves JAX's config as
the caller set it.  Launchers, `benchmarks/run.py` and `chip_smoke.py`
call :func:`enable_compile_cache` once, before their first compile.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and this
sets no other directory.  Otherwise the cache lives at one fixed path
inside the checkout (`.jax_cache/`, gitignored): the path is part of
what makes a cached program findable again, so it never depends on a
temp name, a pid or the time.
"""
from __future__ import annotations

import os

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
