"""Persistent XLA compile cache for the entry points (never for tests).

A cold run compiles every prefill bucket, chunk, segment and DPU program;
the persistent cache lets the next run of the same checkout load them
instead. JAX keys cache entries on the directory, so the directory is
fixed: `JAX_COMPILATION_CACHE_DIR` when the environment sets it (JAX reads
that itself and nothing is set here), else `<checkout>/.jax_cache`.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
