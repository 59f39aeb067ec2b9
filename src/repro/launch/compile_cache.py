"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``,
``python -m benchmarks.run``) call :func:`enable_compile_cache` before they
compile anything; importing the library never turns the cache on.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and
nowhere else. Otherwise the cache lives at a fixed path
inside the checkout (``<repo>/.jax_cache``, listed in ``.gitignore``): the
path is part of what makes an entry findable again, so it is never built
from a temporary name, a pid or the time.

:func:`without_compile_cache` turns the cache off for a block: around a
measured compile time (a cache read is not a compile), and around compiles
for a described chip (written entries could never be read back).
"""
from __future__ import annotations

import contextlib
import os
from pathlib import Path

import jax
from jax.experimental.compilation_cache import compilation_cache

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def without_compile_cache():
    """Neither read nor write the persistent cache inside the block.

    JAX decides once per process whether the cache is in use and memoises
    it, so the flag alone does not stop a process that has already used
    the cache: ``reset_cache()`` drops that decision on the way in and
    again on the way out. Usable as a decorator.
    """
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()
