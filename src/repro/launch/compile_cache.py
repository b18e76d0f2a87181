"""Placement of JAX's persistent compilation cache for the launchers.

A cold fused Euler program takes minutes to compile for a TPU at real
bucket sizes, so every entry point that runs one (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``) calls :func:`setup_compile_cache`
once, before its first compile.  The library itself never configures the
cache at import.

The cache key includes the directory, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads the
variable itself, nothing is set here), else the fixed ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<repo>/.jax_cache`` — listed in the repository's ``.gitignore``.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
