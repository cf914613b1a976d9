"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` once, before they compile
anything; importing this module changes nothing.  A directory given in
``JAX_COMPILATION_CACHE_DIR`` wins (JAX reads that variable itself).
Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed path, since
the path is part of each entry's key and a moving directory never hits.
"""

from __future__ import annotations

import os
from pathlib import Path

_DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """The directory compiled programs go to."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(_DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`cache_dir`; returns it."""
    import jax

    path = cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
