"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compilation_cache` from their ``main()``;
library modules never do, so importing ``repro`` (and the test suite)
leaves the cache as JAX's configuration has it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: a fixed path, because the path is part of what
# makes an entry hit again
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Use ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
    itself), else ``<checkout>/.jax_cache``.  Returns the directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
