"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module changes nothing.  The cache directory is
part of what makes a later run find an entry, so it never moves:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that variable itself
  and nothing else is set here;
* otherwise the cache lives at ``<repo>/.jax_cache`` (listed in
  ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    and return that directory."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
