"""JAX's persistent compilation cache, placed once for every entry point.

A cold TPU run compiles every kernel and jitted program it touches; the
persistent cache lets later runs of the same programs skip that. The
cache key includes its directory, so the directory must not move between
runs: it is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the
variable itself, so nothing is set in code), and otherwise one fixed,
git-ignored directory in the checkout, ``.jax_cache/``.

`chip_smoke.py` and `benchmarks/run.py` call `enable_compile_cache()`
before their first compile.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT_DIR, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
