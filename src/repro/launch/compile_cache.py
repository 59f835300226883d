"""JAX's persistent compilation cache, placed the same way by every entry
point (`chip_smoke.py`, `repro.launch.serve`, `benchmarks/run.py`).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache lives in ``.jax_cache/`` at the
root of the checkout: a fixed path, so a later run of the same checkout
finds what an earlier one compiled. The test suite never turns it on.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir(environ=os.environ) -> Path:
    """Where compiled programs are cached under this environment."""
    return Path(environ[ENV]) if environ.get(ENV) else CHECKOUT_CACHE


def enable_compile_cache() -> Path:
    """Turn the persistent cache on; returns its directory."""
    import jax
    path = cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
