"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so a directory that moves between
runs never hits: the default is a fixed path inside the checkout, never a
name made from a temp dir, a pid or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<repo>/.jax_cache`` (git-ignored): this file is
#: ``<repo>/src/repro/launch/compile_cache.py``.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is configured here. Otherwise the cache goes to
    ``DEFAULT_DIR``. ``LIBTPU_INIT_ARGS`` is never touched.

    Either way the ops' metadata joins the cache key. JAX leaves it out by
    default, so a program compiled before a ``jax.named_scope`` was added
    or renamed would be loaded with its old op names, and a profile of it
    would charge its device time to scopes the code no longer has."""

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
