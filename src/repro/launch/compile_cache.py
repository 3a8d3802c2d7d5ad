"""Where JAX keeps its persistent compilation cache for the launchers.

Every ``repro.launch`` entry point (and ``chip_smoke.py``) calls
:func:`use_compile_cache` before it compiles anything. A directory named
by ``JAX_COMPILATION_CACHE_DIR`` wins, and JAX reads that variable itself.
Otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored). The
path is fixed on purpose: it is part of what a later run has to find, so a
temporary or per-process name would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
