"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
``use_compile_cache()`` once, before their first compile; nothing does so
at import.  A cold compile of the engine at the paper's widths takes about
a minute on a TPU, and processes of one checkout that share this cache
compile each program once.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that directory
and this sets nothing.  Otherwise the cache goes to one fixed directory
inside the checkout: the path is part of the cache key, so it must not
move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout's own cache directory (listed in ``.gitignore``).
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
