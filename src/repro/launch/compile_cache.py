"""JAX's persistent compilation cache at a place that can be set from outside.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
``enable_compile_cache()`` before they compile anything.  The cache key
includes the directory, so the directory is fixed: the environment's
``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads that variable
itself, so nothing else is set), else ``<checkout>/.jax_cache``, which
``.gitignore`` lists.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir(environ=os.environ) -> pathlib.Path:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    the fixed in-checkout ``DEFAULT_DIR``."""
    env = environ.get(ENV_VAR)
    return pathlib.Path(env) if env else DEFAULT_DIR


def enable_compile_cache() -> pathlib.Path:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``
    and return that directory."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
