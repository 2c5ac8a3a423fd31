"""Persistent XLA compilation cache for the entry points.

Called by ``repro.launch.gnn_serve.main``, ``chip_smoke.py`` and
``benchmarks/run.py`` — never at import, so a program that imports the
library keeps control of jax's configuration.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# A fixed directory inside the checkout (listed in .gitignore): the cache
# only pays off when a later process looks in the same place, so the path
# never comes from a temp name, a pid or the time.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point jax's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    when it is set, else at :data:`DEFAULT_DIR`; returns the directory."""
    path = os.environ.get(ENV) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
