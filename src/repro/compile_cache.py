"""JAX's persistent compilation cache, set up by the entry points.

``enable()`` is called by ``chip_smoke.py``, ``examples/*.py`` and the
benchmark harness (``bench/harness.py``) before they compile anything. It
is deliberately not called on ``import repro``: the test suite compiles
for described (not attached) TPUs, and such compiles must not land in a
cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
module sets nothing. Otherwise the cache lives at a fixed path inside the
checkout, ``<repo>/.jax_cache``: the directory is part of the cache key, so
a path derived from a temp dir, a pid or the clock would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
