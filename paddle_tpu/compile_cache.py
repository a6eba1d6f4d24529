"""The one place that turns on jax's persistent compilation cache.

Every process entry point that compiles for the chip (``chip_smoke.py``,
the bench children, ``serving/replica.py``'s ``main``) calls ``enable()``
before its first compile. The directory is part of the cache key's
lookup, so it must not move between runs: where the environment names one
(``JAX_COMPILATION_CACHE_DIR``, which jax reads itself) no directory is
set in code; otherwise it is ``<checkout>/.jax_cache`` — never a
temporary, per-pid or timestamped path.
"""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable():
    """Turn the cache on for this process; returns the directory in use."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every executable, however small or quick to compile: a serving
    # ladder is dozens of sub-second programs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
