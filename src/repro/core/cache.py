"""Shared bounded-LRU helpers for the process-wide constant caches.

One implementation behind every cache in the compile pipeline (transfer
planes, plans, executables, models, batched inputs, resample matrices):
plain dicts in insertion order, where a lookup reinserts the hit entry at
the back (most recently used) and eviction pops the front — a DSE sweep
alternating more geometries than a bound can hold never evicts its own
hot entries.

``use_persistent_compile_cache`` points XLA's on-disk compilation cache at
a fixed directory for the entry points (the chip smoke run, the serving
launcher, the benchmark harness).  It is never called at import time, so
library users and tests keep whatever cache setting they chose.
"""
from __future__ import annotations

import os
import pathlib
from typing import Optional

REPO_COMPILE_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def lru_get(cache: dict, key, stats: Optional[dict] = None):
    """LRU lookup: refresh recency on hit (dicts iterate in insertion order)."""
    entry = cache.pop(key, None)
    if entry is None:
        if stats is not None:
            stats["misses"] += 1
        return None
    if stats is not None:
        stats["hits"] += 1
    cache[key] = entry  # reinsert at the back: most recently used
    return entry


def lru_put(cache: dict, key, value, max_size: int) -> None:
    while len(cache) >= max_size:
        cache.pop(next(iter(cache)))  # front = least recently used
    cache[key] = value


def use_persistent_compile_cache() -> str:
    """Keep XLA's persistent compilation cache at a fixed path; return it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to ``<repo>/.jax_cache``
    (gitignored).  The path is fixed on purpose: a temporary, pid- or
    time-derived directory would never be hit by the next run.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_COMPILE_CACHE))
    return str(REPO_COMPILE_CACHE)
