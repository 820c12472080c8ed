"""Persistent XLA compile cache for the repo's entry points.

`enable_compile_cache()` is called first thing by `chip_smoke.py`,
`benchmarks/run.py`, `benchmarks/profile_grid.py` and the `examples/`, so a
second run of the same programs loads them instead of compiling the whole
sweep again.  Library code and the tests never call it.

Placement: when `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and
this module sets nothing.  Otherwise the cache is the fixed directory
`<repo root>/.jax_cache`, derived from this file's location (a directory
that moved between runs would never hit).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_hits = 0


def _count_hit(event: str, **_kw) -> None:
    global _hits
    if event == _HIT_EVENT:
        _hits += 1


jax.monitoring.register_event_listener(_count_hit)


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory.  Call
    before the first compilation of the process."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def cache_hits() -> int:
    """Programs loaded from the persistent cache since it was enabled."""
    return _hits
