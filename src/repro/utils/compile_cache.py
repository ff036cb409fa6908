"""JAX's persistent compilation cache, placed by the entry points.

Library code never turns the cache on by itself: importing ``repro``
leaves JAX's configuration alone.  Entry points (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before
anything compiles.
"""
from __future__ import annotations

import os
from dataclasses import dataclass

# <checkout>/src/repro/utils/compile_cache.py -> <checkout>
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


@dataclass
class CompileCacheEvents:
    """Where the cache lives and what it did in this process: ``hits``
    counts executables read back, ``writes`` executables stored."""

    path: str
    hits: int = 0
    writes: int = 0

    def _on_event(self, event: str, **_kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1


def enable_compile_cache() -> CompileCacheEvents:
    """Turn on the persistent compilation cache and count its traffic.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory
    from the environment and this sets no other.  Otherwise the cache is
    ``<checkout>/.jax_cache``: a fixed path, because the path is part of
    what a later run must find again."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    events = CompileCacheEvents(path=path)
    jax.monitoring.register_event_listener(events._on_event)
    return events
