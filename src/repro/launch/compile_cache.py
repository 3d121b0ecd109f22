"""JAX's persistent compilation cache, for the launchers and the chip smoke.

A fresh process compiles every program again, and at published widths a
cold serving run spends most of its start-up compiling. The persistent
cache lets a later process (or a second engine built in the same one)
load those programs instead. Call ``enable_compile_cache()`` before the
first compile: JAX reads the setting when it compiles for the first time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache(root: Path = REPO_ROOT) -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache is the fixed, git-ignored
    ``<root>/.jax_cache``: a directory that moved between runs would
    never be found again, so the name holds no pid, time or temp path."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    path = str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
