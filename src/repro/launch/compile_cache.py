"""JAX's persistent compilation cache, in one place for every entry point.

The entry points (``launch.train``, ``launch.serve``, ``benchmarks/run.py``
and ``chip_smoke.py``) call :func:`enable_compile_cache` once, before their
first compile.  Library code and tests never call it: importing the package
changes no global JAX setting.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# a fixed path inside the checkout (listed in .gitignore): the directory is
# part of what a later run must find, so it never depends on a temporary
# name, a pid or the time
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here; otherwise the cache goes to
    :data:`DEFAULT_DIR`."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
