"""JAX's persistent compilation cache, placed from outside the program.

A cold run of a 1500-wide model compiles every kernel and step program
from scratch; the persistent cache lets later processes on the same
machine reuse those compilations. Where the cache lives is the caller's
decision: ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads that
variable itself, so nothing else is set), otherwise a fixed ``.jax_cache/``
at the root of this checkout. The path is part of the cache key, so it is
never built from a temporary name, a process id or the time.

Entry points call ``enable_compile_cache()`` first; importing this module
does nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
