"""JAX's persistent compilation cache: where every entry point keeps it.

A cold run compiles the long scan programs again (tens of seconds each);
the cache lets a second run of the same program load them instead.  The
directory is part of what a cached entry matches, so it must not move
between runs: it is never a temporary, per-process or time-stamped path.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and
    nothing else is set here.  Otherwise the cache is `<checkout>/.jax_cache`,
    set for this process and, through the same variable, for the child
    processes it starts.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    return path
