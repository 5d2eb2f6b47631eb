"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
nothing here overrides it. Otherwise the cache lives at one fixed path
inside the checkout, ``<checkout>/.jax_cache`` (listed in ``.gitignore``),
so that every run of the same checkout finds what earlier runs compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one place; returns it.

    Call from an entry point before its first compilation.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
