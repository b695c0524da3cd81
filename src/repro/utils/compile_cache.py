"""Where JAX's persistent compilation cache lives for the entry points.

A later run finds a compiled program again only where an earlier one left
it, so the directory never depends on the process or the time: it is
either what ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable
itself) or the fixed ``<repo>/.jax_cache``. Entry
points call ``enable_compile_cache()`` before their first compile; tests
never do.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    unless ``JAX_COMPILATION_CACHE_DIR`` already does. Returns the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
