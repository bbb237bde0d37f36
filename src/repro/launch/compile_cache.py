"""Persistent XLA compilation cache, placed from outside the program.

A cold 28-layer serving engine compiles several large programs; JAX's
persistent cache lets the next process on the same machine reuse them.
The cache key includes the directory, so the path must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (and no other
directory is set in code); otherwise the cache lives at a fixed path
inside the checkout, ``<repo>/.jax_cache``, which git ignores.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that path.  Call before the first compile."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
