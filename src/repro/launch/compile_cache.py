"""Where JAX's persistent compilation cache lives.

Entry points call :func:`use_compile_cache` once, before their first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root's ``.jax_cache/`` (listed in .gitignore). A fixed
#: path: the cache directory is part of what a later run must find again.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the cache directory in use. Where ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX reads it itself and nothing is set here; otherwise the cache
    goes to :data:`DEFAULT_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
