"""Where JAX's persistent compilation cache lives.

A later run only finds what an earlier one compiled if both use the same
directory.  :func:`enable_compile_cache` fixes the place:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
  module sets no other directory;
* otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored),
  never to a name made from a temp dir, a pid or the time.

Entry points call it once, before their first compile; importing this
module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Fix the persistent compile cache's directory; return it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
