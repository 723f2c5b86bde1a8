"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable` once, before their first compile; the
package never does so on import, so library users and the tests stay
cache-free. ``JAX_COMPILATION_CACHE_DIR``, when set, wins and JAX reads it
itself. Otherwise the cache lives at a fixed path in the checkout: the
path is part of the cache key, so a directory that moved would never hit.
"""
from __future__ import annotations

import os
import pathlib

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # a service warm-up is dozens of sub-second compiles (MLP buckets,
    # forest block counts) that JAX's default 1 s threshold never caches
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
