"""Persistent XLA compilation cache for the entry points.

A cold run at TeraPool's size spends most of its first minutes in the
compiler (one N=1024 tuner grid takes about a minute to compile for a
TPU), so the scripts keep JAX's persistent compilation cache on.  The
cache directory is part of the cache's key, so it must not move between
runs:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
  nothing here overrides it;
* otherwise the cache lives at the fixed ``<repo>/.jax_cache``
  (ignored by git).

Call :func:`enable` from a script's ``main`` — never at import, so that
importing the library leaves JAX's configuration alone.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    path = os.environ.get(ENV)
    if not path:
        path = str(REPO_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
