"""Persistent XLA compilation cache for the entry points that drive a chip.

``use_compile_cache()`` runs first in ``chip_smoke.py``,
``repro.launch.serve`` and ``benchmarks.run``.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and this sets
nothing.  Otherwise the cache lives in ``.jax_cache/`` at the checkout
root: a fixed path, never a temporary directory, so every later process
of the same checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_ENV_VAR", "DEFAULT_CACHE_DIR", "use_compile_cache"]

CACHE_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(CACHE_ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
