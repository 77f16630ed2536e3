"""JAX's persistent compilation cache, kept at one fixed path.

Entry points (`chip_smoke.py`, `launch.train`, `launch.serve`) call
`enable_compile_cache()` before their first compile, so a second run of
the same program on the same chip loads its executables instead of
compiling them again.  Library code and tests never turn it on.
"""
from __future__ import annotations

import os
import pathlib

import jax

# src/repro/launch/compile_cache.py -> the repository root
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent cache at its directory and return that path.

    `JAX_COMPILATION_CACHE_DIR`, when set, stays in charge (jax reads it
    itself) and no other directory is set.  Otherwise the cache lives at
    `<repo>/.jax_cache`: a fixed path, never one built from a temporary
    name, a pid or the time, so the next run finds it again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
