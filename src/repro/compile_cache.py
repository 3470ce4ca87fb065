"""Where JAX keeps compiled programs between processes.

Both device paths (the lockstep fill solver and the MapReduce engine)
call :func:`enable_compile_cache` before their first compile, so a cold
process reloads their executables instead of compiling them again.
"""
from __future__ import annotations

import os

import jax

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path (the path is part of the cache key) inside the checkout
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache at :data:`CACHE_DIR`,
    caching every executable however small or quick to compile. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    this sets nothing."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
