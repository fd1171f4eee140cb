"""JAX's persistent compilation cache for the entry points.

Each entry point's ``main`` calls ``enable_compile_cache()``; importing a
module never touches the cache. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already caches there and nothing is changed. Otherwise the cache goes
to ``<checkout>/.jax_cache``: a fixed path, because the cache key includes
it, so a directory that moves never hits. CPU runs are not cached: this
JAX's CPU executables fail a host-feature check when loaded back
(``cpu_aot_loader`` errors on every hit), and the compiles worth keeping
are the accelerator's.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on; returns the directory it uses (None
    on the CPU)."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
