"""Where JAX keeps its persistent compilation cache.

Every entry point calls `use_compile_cache()` before its first compile.
A later run finds an entry only in the directory that holds it, so the
directory must not move between runs: when ``JAX_COMPILATION_CACHE_DIR``
is set, JAX already uses it and nothing else is set; otherwise the cache
lives at the fixed path ``<checkout>/.jax_cache`` (listed in
``.gitignore``).
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
