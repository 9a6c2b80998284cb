"""Where the program's entry points keep JAX's persistent compilation cache.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so when the caller sets it
the cache lives there and nothing here changes any setting. Otherwise the
cache goes to ``<repo root>/.jax_cache`` — a fixed path, because the
directory is part of what the cache is looked up by, and a cache that
moves between runs never hits. Called once at the top of each entry
point's ``main``, before anything compiles.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<repo root>/.jax_cache`` (listed in ``.gitignore``).
DEFAULT_CACHE_DIR: Path = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
