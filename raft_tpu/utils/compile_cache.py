"""One place that turns on XLA's persistent compilation cache.

Every entry point that compiles (``train.py``, ``evaluate.py``,
``demo.py``, ``bench.py``, the serving engine, ``chip_smoke.py``) calls
:func:`enable_compile_cache` before its first compile. The directory is
placed from outside: ``JAX_COMPILATION_CACHE_DIR`` when it is set — JAX
reads the variable itself, and this code then sets no directory — else
``<checkout>/.jax_cache`` (git-ignored). The path is part of the cache
key, so it is never a temp name, a pid or a time.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Enable the persistent compilation cache; returns the directory in
    use. The min-compile-time / entry-size floors drop to zero so every
    executable is cached. Call before the first compile."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
