"""JAX's persistent compilation cache, placed for the program's entry points.

``enable()`` is called by the command-line entry points (``chip_smoke.py``,
the launchers, ``train/pnn.py``, ``benchmarks/run.py``, the examples) and
never on import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and nothing is set here; otherwise the cache lives at one fixed
path inside the checkout, ``<repo>/.jax_cache`` (git-ignored).  The path is
part of each entry's key, so a directory that moved would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
