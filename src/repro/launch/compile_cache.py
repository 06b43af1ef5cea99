"""JAX's persistent compilation cache, turned on by the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks.run``)
call :func:`enable_compile_cache` before their first JAX computation; library
modules never do, and neither do the tests.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here sets another directory.  Otherwise the cache lives at the fixed path
``<repo>/.jax_cache``: the path is part of the cache key, so a directory
named after a temp dir, a PID or the time would never be hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the persistent cache uses."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
