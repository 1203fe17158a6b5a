"""JAX's persistent compilation cache, set up by each entry point's main.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here. Otherwise the cache lives at one fixed path inside
the checkout, ``<repo>/.jax_cache`` (gitignored): a cache directory that
moves between runs is never hit again. Importing this module sets
nothing."""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the persistent cache uses in this process."""
    return os.environ.get(ENV) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
