"""Persistent XLA compilation cache placement for the entry points.

Called by ``chip_smoke.py`` and the launchers before their first compile,
never on import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and this sets no other directory; otherwise the cache lives at
the fixed ``<repo>/.jax_cache``.  The path is part of what a later run
must find again, so it never holds a temporary name, a process id or the
time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
#: ``<repo>/.jax_cache`` (this file is ``<repo>/src/repro/launch/...``)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    import jax

    if os.environ.get(ENV_DIR):
        return os.environ[ENV_DIR]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
