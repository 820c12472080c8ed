"""`make profile`: capture a JAX profiler trace of one warm batched grid.

Writes a TensorBoard-compatible trace under bench_out/profile/ (open with
`tensorboard --logdir bench_out/profile` or xprof).  The grid is the same
18-lane sweep bench_engine times, compiled first so the trace contains only
the steady-state epoch scan, not tracing/compilation.
"""
from __future__ import annotations

import os

import jax

from benchmarks.bench_engine import _grid
from repro.compile_cache import enable_compile_cache

LOG_DIR = os.environ.get("PROFILE_DIR", "bench_out/profile")


def run():
    from repro.nmp.sweep import run_grid

    _, grid = _grid()
    run_grid(grid)                        # compile + warm outside the trace
    os.makedirs(LOG_DIR, exist_ok=True)
    with jax.profiler.trace(LOG_DIR):
        res = run_grid(grid)
        jax.block_until_ready(res.final_env)
    print(f"profile trace written to {LOG_DIR}")


if __name__ == "__main__":
    enable_compile_cache()
    run()
