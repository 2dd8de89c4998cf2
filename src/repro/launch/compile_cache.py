"""JAX's persistent compilation cache for the chip entry points.

A 32-layer serving program takes tens of seconds to compile, and each
fresh process starts with none. ``enable()`` keeps compiled programs on
disk so that the next process with the same programs loads them instead.
``chip_smoke.py`` and ``python -m repro.launch.serve`` call it before
their first compile; importing the package never changes the cache.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

#: The checkout's own cache, used when ``JAX_COMPILATION_CACHE_DIR`` is not
#: set. A fixed path: it is part of every entry's key, so a directory that
#: moved between runs would never hit. Listed in ``.gitignore``.
DEFAULT_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable() -> str:
    """Turn the persistent cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it as the
    cache directory and nothing else is configured here; otherwise the
    cache lives at ``DEFAULT_DIR`` inside the checkout."""
    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
