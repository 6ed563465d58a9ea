"""Where JAX keeps its persistent compile cache.

The TPU programs of the write path take seconds to minutes to compile
cold, so the command-line entry points (`tools/vstart.py` and
`tools/erasure_code_benchmark.py`) call `configure_compile_cache()`
once at start and a second run of the same program loads what the
first compiled.
Importing a module never touches the cache.

When `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
helper sets nothing. Otherwise the cache lives at a fixed path inside
the checkout, `.jax_cache/` (listed in `.gitignore`): the directory is
part of the cache key, so it is never built from a temp name, a pid or
the time.
"""

from __future__ import annotations

import os

__all__ = ["configure_compile_cache"]

# <checkout>/.jax_cache: the directory that holds the ceph_tpu package
_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    return _CACHE_DIR
