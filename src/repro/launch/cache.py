"""JAX's persistent compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``repro.launch.train``,
``benchmarks.run``) call :func:`use_compile_cache` before their first
compile; importing the library never turns the cache on.
"""
from __future__ import annotations

import os
import pathlib

# a fixed path inside the checkout (listed in .gitignore): the cache
# directory is part of JAX's cache key, so a path that moved would never hit
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left for JAX to read and
    no other directory is set.  Otherwise the cache lives at
    :data:`CACHE_DIR`.  Either way an entry's key covers the program's
    metadata: a profile attributes device time by the op names the train
    step's named scopes give (``repro.obs``), and JAX's default key leaves
    them out, so a hit on a build with other names would bring those."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
