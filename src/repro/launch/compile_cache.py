"""JAX's persistent compilation cache for the entry points.

Called from ``main()`` of the serving CLI, the benchmark runner and
``chip_smoke.py`` — never at import. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX already keeps its cache there and nothing else is chosen here;
otherwise the cache lives at ``<checkout>/.jax_cache`` (git-ignored). The
path is fixed because it is part of the cache's key: a directory that moved
between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on for this process and return its
    directory. Every executable is kept, however quick its compile: the
    serving warmup ladder is many small programs."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
