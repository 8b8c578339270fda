"""Where the program's entry points keep JAX's persistent compile cache.

A cold chip run compiles the whole cohort chain; the persistent cache lets
a later process (or a later run on a machine that keeps the directory)
load those executables instead. The cache key includes the directory, so
the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when it is set
(JAX reads the variable itself), else ``<repo root>/.jax_cache``.

Only entry points call :func:`configure` (``chip_smoke.py``,
``repro.launch.train``, ``repro.fl.cli``, ``benchmarks.run``): library
imports and tests leave JAX's cache settings alone.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def configure() -> str:
    """Point JAX's persistent compile cache at its fixed directory and
    return that directory. Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
