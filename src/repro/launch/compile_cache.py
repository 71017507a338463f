"""JAX's persistent compilation cache for the repository's entry points.

Every entry point that compiles for a device (``chip_smoke.py``, the
examples, the launchers) calls :func:`enable_compile_cache` once, before
its first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing is set here; otherwise the cache lives at the fixed
``<repo>/.jax_cache``.  The path is part of what a cache entry is found
by, so it never carries a temp name, a pid or a time.

Library code and tests never call this: a compile for a described (not
attached) TPU writes entries that cannot be read back without the chip.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the repository root (this file is ``<repo>/src/repro/launch/...``)
REPO_ROOT = Path(__file__).resolve().parents[3]

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class CacheEvents:
    """Counts the persistent cache's hits and misses from JAX's
    monitoring events, from construction on."""

    def __init__(self):
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == _HIT:
            self.hits += 1
        elif event == _MISS:
            self.misses += 1
