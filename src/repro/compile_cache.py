"""Where JAX's persistent compilation cache lives.

Every entry point calls :func:`enable_compile_cache` before its first
compile; nothing else in the repo sets a cache directory.

* ``JAX_COMPILATION_CACHE_DIR`` set: that directory is the cache (JAX reads
  the variable itself), and no other is set in code.
* Otherwise the cache is ``<checkout>/.jax_cache`` (git-ignored). The path
  is fixed, never derived from a temporary name, a pid or the time: it is
  part of every entry's key, so a moving directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    import jax

    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
