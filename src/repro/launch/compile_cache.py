"""The one place that turns on JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``launch.train``, ``launch.serve``,
``launch.switch_driver``, ``benchmarks.run``) call
:func:`enable_compile_cache` before their first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache there
and nothing is changed; otherwise the cache lives at ``<repo>/.jax_cache``
(git-ignored).  The path is fixed on purpose: it is part of what a later
run must find again, so it never depends on a temp dir, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
