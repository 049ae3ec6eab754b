"""JAX persistent compilation cache for the entry points.

Every entry point (``perfbench/run.py``, ``repro.launch.serve``,
``repro.launch.train``) calls :func:`enable` before it compiles
anything, so a second run of the same program on the same
machine reads its compiled executables back instead of compiling again.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (listed in ``.gitignore``): the directory is part
of what a cache hit needs, so it must not depend on a temp name, a pid or
the time.  Tests never call :func:`enable`.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
