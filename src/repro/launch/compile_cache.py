"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``
and the ``benchmarks/`` mains) call ``use_compile_cache()`` first thing;
tests never do.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this sets no directory.  Otherwise the cache goes to
``<checkout>/.jax_cache``: a fixed path, because the path is part of what
a cached entry is found by.

Either way every compile is kept, however short: JAX's default keeps only
compiles of a second or more, and on a TPU v5e most of these programs
compile in less (a whole ``chip_smoke.py`` run keeps 12 programs at the
default and 77 with no threshold).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["use_compile_cache", "CHECKOUT_CACHE_DIR"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
