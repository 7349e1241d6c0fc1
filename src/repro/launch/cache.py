"""JAX's persistent compilation cache, placed from outside or at a fixed path.

The path is part of what a cache entry is found by, so it never comes from a
temporary name, a process id or the time: with ``JAX_COMPILATION_CACHE_DIR``
set, JAX uses that directory and this module sets none of its own; otherwise
the cache lives in ``<checkout>/.jax_cache`` (gitignored).
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns its
    directory. Every compile is cached, however short: a kernel that
    compiles in half a second is still worth not compiling again."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
