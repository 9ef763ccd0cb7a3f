"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks.run``
and the process-isolated actor child) call :func:`enable_compile_cache`
once, before they compile.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it by itself and nothing else is configured here; otherwise the cache
lives in ``<checkout>/.jax_cache`` (gitignored), a fixed place where a
later process of the same checkout finds what an earlier one compiled.
Importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on (idempotent); returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
