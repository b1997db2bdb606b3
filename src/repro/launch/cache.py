"""Where JAX's persistent compilation cache lives.

The cache directory is part of an entry's identity: a directory that moves
never hits.  ``JAX_COMPILATION_CACHE_DIR``, where set, places it from
outside and nothing else is set; otherwise it is ``.jax_cache/`` at the
root of the checkout, the same path in every process.  The entry points
(``launch.purify``, ``launch.serve``, ``launch.train``, ``chip_smoke.py``)
call :func:`enable_compile_cache` before their first compile; importing
the library never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """The cache directory: ``JAX_COMPILATION_CACHE_DIR`` or
    ``<checkout>/.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT_ROOT / ".jax_cache"
    )


def enable_compile_cache() -> str:
    """Point this process's compilation cache at :func:`compile_cache_dir`
    and return the directory.  Programs are cached whatever their compile
    time, so a repeated run of a small program hits too."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    path = compile_cache_dir()
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        compilation_cache.reset_cache()  # drop a cache opened elsewhere
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
