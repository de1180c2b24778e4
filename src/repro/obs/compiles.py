"""Process-wide count of XLA backend compiles.

JAX reports every executable it builds to ``jax.monitoring`` as the
duration event ``/jax/core/compile/backend_compile_duration``, a build
served from the persistent compile cache included; such a load also
records the event ``/jax/compilation_cache/cache_hits``.  A compile is a
build that is not a cache hit.  The listeners are registered when this
module is imported, so the count covers every compile after ``import
repro.obs``.  The scheduler reads it around each ``run()`` into
``ServeReport.n_compiles``: a serve call that compiled inside its window
shows there.
"""
from __future__ import annotations

import threading

import jax

BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_builds = 0
_hits = 0


def _on_duration(event: str, duration: float, **kwargs) -> None:
    global _builds
    if event == BUILD_EVENT:
        with _lock:
            _builds += 1


def _on_event(event: str, **kwargs) -> None:
    global _hits
    if event == CACHE_HIT_EVENT:
        with _lock:
            _hits += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def backend_compiles() -> int:
    """XLA backend compiles in this process since ``repro.obs`` loaded
    (executables built, less those loaded from the compile cache)."""
    return _builds - _hits
