"""One persistent XLA compilation cache for every entry point.

JAX keys cache entries by program, and the cache directory is part of what
finds them again, so the directory must not move between runs: either the
one named by `JAX_COMPILATION_CACHE_DIR` (JAX reads that variable itself),
or a fixed `.jax_cache` directory at the root of this checkout."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
