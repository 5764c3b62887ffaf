"""Persistent XLA compile cache for the entry points that run on a chip.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing is set
here. Otherwise the cache lives at ``<checkout>/.jax_cache``, found from
this file's location: a fixed path, so a second run in the same checkout
finds what the first one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
