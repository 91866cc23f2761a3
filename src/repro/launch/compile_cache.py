"""Where JAX's persistent compilation cache lives, decided in one place.

``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: JAX reads
it at import and nothing here overrides it.  Otherwise the cache goes to
``<checkout>/.jax_cache`` — a fixed path, never a temporary name, a pid
or a time, so a later process on the same checkout finds what an
earlier one compiled.  Entry points (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks/run.py``) call
``setup_compile_cache()`` once before their first compile; importing
this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["ENV_VAR", "DEFAULT_CACHE_DIR", "setup_compile_cache"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point the persistent compile cache at its directory and return
    that directory: the environment's when ``JAX_COMPILATION_CACHE_DIR``
    is set (left to JAX), else ``DEFAULT_CACHE_DIR``."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
