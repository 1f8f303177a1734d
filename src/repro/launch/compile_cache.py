"""JAX's persistent compilation cache, kept at a fixed path in the checkout.

A full-width step takes a minute or more to compile for a TPU. With the
cache, a second process that compiles the same program for the same chip
loads it instead. The directory is part of what a cached entry is found
by, so it is a fixed path and never a temporary name. The CPU backend's
programs compile in seconds and are not cached.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` (listed in .gitignore)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn the persistent cache on before the first compile. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that directory
    and nothing is changed here."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    if jax.default_backend() != "cpu":
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
