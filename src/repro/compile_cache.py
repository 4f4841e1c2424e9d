"""Where JAX keeps compiled programs between processes.

A process on the chip compiles every program it runs; JAX's persistent
compilation cache lets the next process load them instead. The cache is
placed from outside: ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX
itself and this module sets no other directory. Otherwise the cache lives
at a fixed ``<repo>/.jax_cache`` — the directory is part of what the cache
is keyed on, so it must not move between runs.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"

#: fixed default directory (gitignored), at the root of the checkout
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the cache uses: the environment's, else the default."""
    return os.environ.get(ENV) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its directory.

    Every program is cached, however quickly it compiled: on the chip even
    a one-op program takes a noticeable fraction of a second to compile.
    """
    import jax

    path = compile_cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
