"""JAX's persistent compilation cache, placed from outside.

Called first thing by the chip entry points (``chip_smoke.py``, ``bench.py``)
and by nothing at import time. ``JAX_COMPILATION_CACHE_DIR``
decides where the cache lives: when it is set JAX reads it itself and no
directory is set in code; otherwise the cache sits at the fixed
``<checkout>/.jax_cache`` (the path is part of the cache key, so it never
moves between runs).
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on, for every program however
    quick its compile (a chip run is dozens of sub-second compiles); returns
    the cache's directory."""
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))
    return jax.config.jax_compilation_cache_dir
