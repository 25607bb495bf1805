"""Persistent XLA compilation cache for the repo's entry points.

One place decides where compiled programs are kept, so every entry point
(``chip_smoke.py``, ``benchmarks/run.py``, ``examples/*``) reuses the same
cache. JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; when it is set,
nothing here overrides it. Otherwise the cache lives at ``<repo>/.jax_cache``
(listed in ``.gitignore``): a fixed path, because the directory is part of
each entry's key and a moving one never hits.
"""
from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_repo_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``<repo>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` is set (an empty value turns the
    cache off, as in ``tools/check.sh``). Call once from an entry point,
    before the first compile. Returns the directory in use, "" for none."""
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
