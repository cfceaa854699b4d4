"""Where JAX keeps its persistent compilation cache for this repo's entry
points (``chip_smoke.py``, ``launch/serve.py``, ``benchmarks/batch_bench.py``).

The cache is keyed by its path, so it must not move between runs: a
``JAX_COMPILATION_CACHE_DIR`` set by the caller wins (JAX reads it itself and
nothing is set here); otherwise the cache lives at ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory;
    returns that directory. Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
