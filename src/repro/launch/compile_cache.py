"""JAX's persistent compile cache, placed from outside the program.

Entry points (the serving launcher, the benchmark mains, ``chip_smoke.py``)
call :func:`use_compile_cache` once, before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a
fixed path, because the directory is part of what a later process must find
again, so a name built from a temp dir, a pid or the time would never hit.
"""
from __future__ import annotations

import os

import jax

__all__ = ["use_compile_cache"]

# .../src/repro/launch/compile_cache.py -> the checkout root
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    Every program is cached, however quickly it compiled: a serving process
    compiles one program per bucket rung, each in about a second, and the
    sum of them is what a warm start saves."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
