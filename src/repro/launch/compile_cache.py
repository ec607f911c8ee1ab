"""JAX's persistent compilation cache, placed from outside or in the checkout.

Every entry point (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``) calls :func:`use_compile_cache` before it compiles.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here.  Otherwise the cache lives at ``<checkout>/.jax_cache`` — a fixed
path, because the path is part of the cache's key and a moving directory
never hits — which ``.gitignore`` lists.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's compilation cache at its directory; returns the path."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
