"""JAX's persistent compilation cache, at one fixed place per checkout.

A cold process on the chip spends minutes compiling the serve graphs; the
cache lets the next process in the same checkout skip that. Launchers call
:func:`enable_compile_cache` from ``main``; importing this module changes
nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

# <checkout>/.jax_cache: fixed, because the directory is part of what a
# later process looks entries up by (gitignored)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is JAX's own setting and is
    left alone; otherwise the cache goes to :data:`DEFAULT_CACHE_DIR`.
    Every compile is kept, not only those of a second or more (JAX's
    default): the serve tick and a dozen set-up programs compile in less,
    and each process would compile them again."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
