"""JAX's persistent compilation cache, at one directory per checkout.

Every entry point (``chip_smoke.py``, ``examples/``, ``benchmarks/run.py``)
calls :func:`enable_compile_cache` before it compiles anything, so a second
run — or another entry point — reuses the first one's compiled steps and
kernels instead of compiling them again. The directory is part of the
cache's key, so it never depends on a temp name, a pid or the time:

* ``JAX_COMPILATION_CACHE_DIR``, when the environment sets it;
* otherwise ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]


def compile_cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`compile_cache_dir`
    and return that directory."""
    import jax
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
