"""JAX's persistent compilation cache, placed by the program's entry
points (``chip_smoke.py``, ``examples/schedule_search.py``, a measuring
``python -m repro.engine.server``) before their first compile — never
on import.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and it is the
only cache directory; otherwise the cache lives at one fixed path in
the checkout (``.jax_cache``, git-ignored). The path is part of what a
cache hit needs, so it never depends on a temp dir, pid or clock.
Every compile is cached, however short: a kernel sweep is many
sub-second candidate compiles.
"""
from __future__ import annotations

import os
import pathlib

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    import jax

    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir",
                          str(DEFAULT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir
