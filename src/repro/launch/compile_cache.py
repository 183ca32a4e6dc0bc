"""Persistent compilation cache for the entry points.

Only entry points call :func:`enable`; importing the library or running the
tests never touches the cache. When ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and this sets nothing. Otherwise the cache lives at the
fixed path ``<checkout>/.jax_cache`` (gitignored): the directory is part of
the cache key, so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def enable() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT / ".jax_cache"))
