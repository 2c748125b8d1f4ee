"""JAX's persistent compilation cache, placed from outside the program.

Called from the launchers' ``main()`` (never on import): a process that
compiles the same step twice — a rerun, or several launches in one job —
then reads the second compile from disk.  A later process finds the
cache only at the same path, so the default is one fixed directory and
never a temporary name.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the default cache directory: ``.jax_cache/`` at the repository root
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no directory is set here; otherwise the cache goes to ``.jax_cache/``
    at the repository root.  Either way the cache key includes the
    program's debug metadata: JAX strips it from the key by default, so a
    step compiled before its ``obs.scope`` names changed would be loaded
    with the old op names, and the device trace would attribute its ops
    to scopes the program no longer has."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
