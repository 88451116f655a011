"""JAX's persistent compilation cache, at one directory per checkout.

Every entry point (``chip_smoke.py``, ``examples/``, ``benchmarks/run.py``)
calls :func:`enable_compile_cache` before it compiles anything, so a second
run — or another entry point — reuses the first one's compiled steps and
kernels instead of compiling them again. The directory is part of the
cache's key, so it never depends on a temp name, a pid or the time:

* ``JAX_COMPILATION_CACHE_DIR``, when the environment sets it;
* otherwise ``<checkout>/.jax_cache`` (listed in ``.gitignore``).

:func:`compile_counter` is the process's one count of backend compiles
(persistent-cache loads included), their seconds and the persistent-cache
hits, from JAX's own monitoring events: the trainer reads it for its
``compile`` set-up phase and each epoch's ``compiles``, ``chip_smoke.py``
for its ``compile_s`` and ``cache_hits``.
"""
from __future__ import annotations

import os
import threading
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


# JAX's monitoring events: one backend-compile duration per executable it
# builds or loads from the persistent cache, one cache-hit event per load
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Backend compiles, their seconds and persistent-cache hits in this
    process since :func:`compile_counter` first ran. Read the attributes
    before and after a stretch of work; their differences are its own."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


_COUNTER = None
_LOCK = threading.Lock()


def compile_counter() -> CompileCounter:
    """The process-wide :class:`CompileCounter`, whose JAX listeners are
    registered on the first call and never again."""
    global _COUNTER
    with _LOCK:
        if _COUNTER is None:
            import jax
            counter = CompileCounter()
            jax.monitoring.register_event_duration_secs_listener(
                counter._duration)
            jax.monitoring.register_event_listener(counter._event)
            _COUNTER = counter
    return _COUNTER
