"""JAX's persistent compilation cache at a fixed, placeable path.

Entry points (``chip_smoke.py``, the benchmarks, the examples) call
``enable_compile_cache()`` once, before their first compile; library
modules never do, so importing the package changes no global setting.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  this helper sets nothing.
- unset: the cache goes to ``<repo>/.jax_cache``. The path is part of
  every cache key, so it must not move between runs: no temporary
  directory, pid or timestamp.
"""
from __future__ import annotations

import os

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(_REPO, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
