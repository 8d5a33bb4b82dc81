"""JAX's persistent compilation cache, at one fixed place.

A run finds what an earlier run cached only if both look in the same
directory, so the place never depends on a temporary name, a pid or a
time: ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it
itself); otherwise the cache lives at ``<checkout>/.jax_cache``
(git-ignored).  Entry points call ``enable_compile_cache()``; importing
this module sets nothing."""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return jax.config.jax_compilation_cache_dir
