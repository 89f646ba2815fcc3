"""Where JAX keeps its persistent compilation cache for runs on the chip.

A cold chip run compiles the served round program (tens of seconds at the
paper's scale); the persistent cache lets the next process on the same
machine load it instead.  Entry points that run on the chip call
:func:`enable_compile_cache` once, before their first compile.  Tests never
call it.
"""
from __future__ import annotations

import os
from pathlib import Path

#: Fixed fallback location: ``<repo>/.jax_cache`` (listed in .gitignore).
#: A fixed path, never a temporary or per-process name, so a second process
#: finds what the first one wrote.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and the
    cache stays there: nothing is set in code.  Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
