"""Shared benchmark infrastructure.

Every benchmark prints `name,us_per_call,derived` CSV rows (one per paper
table/figure datapoint). `us_per_call` is the wall time of the underlying
simulator/compile call; `derived` is the paper-comparable quantity.

Two execution paths are provided:

  cached_episode : one serial (app, technique, mapper) cell, memoized —
                   used by benchmarks that need the full EpisodeResult
                   (per-epoch metrics, final env state).
  cached_grid    : a whole scenario grid through the batched sweep engine
                   (`repro.nmp.sweep.run_grid`), memoized — one compile and
                   one dispatch for every cell of the grid.
"""
from __future__ import annotations

import contextlib
import os
import time

import numpy as np

FULL = os.environ.get("BENCH_FULL", "0") == "1"

# paper protocol: 5 episodes, DNN persisted; FULL widens the app set
N_OPS = 16384
EPISODES = 5
APPS_FAST = ("BP", "KM", "PR", "RBM", "SPMV") if not FULL else None
# seed replicas per figure cell: the sweep folds them into a vmapped seed
# axis (variance bands come back per lane); BENCH_SEEDS widens the axis.
_raw_seeds = os.environ.get("BENCH_SEEDS", "3" if FULL else "1")
try:
    _n_seeds = int(_raw_seeds)
except ValueError:
    raise ValueError(f"BENCH_SEEDS={_raw_seeds!r}: expected a positive "
                     "integer") from None
if _n_seeds < 1:
    raise ValueError(f"BENCH_SEEDS={_n_seeds} must be >= 1")
SEEDS = tuple(range(_n_seeds))


def apps():
    from repro.nmp.traces import APPS
    return APPS if FULL else APPS_FAST


def emit(name: str, us: float, derived) -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)


class Timer:
    def __enter__(self):
        self.t0 = time.time()
        return self

    def __exit__(self, *a):
        self.us = (time.time() - self.t0) * 1e6


# ---------------------------------------------------------------------------
# Interleaved A/B harness (benchmarks/README.md "measurement protocol").
# The performance benchmarks used to hand-roll this loop; they share one
# implementation so every A/B record means the same thing.
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def env_overrides(**kv):
    """Temporarily set/clear env knobs (None clears).  Knobs like
    REPRO_SWEEP_MESH are read per run_grid call (the
    resolved value is a static jit argument), so flipping them between calls
    selects distinct resident programs without recompiling."""
    old = {k: os.environ.get(k) for k in kv}
    try:
        for k, v in kv.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def metrics_equal(a, b) -> bool:
    """Exact metric-dict equality of two SweepResults (same keys, every
    array bit-identical) — the exactness check each A/B record reports."""
    return (set(a.metrics) == set(b.metrics)
            and all(np.array_equal(np.asarray(a.metrics[k]),
                                   np.asarray(b.metrics[k]))
                    for k in a.metrics))


def ab_orders(reps: int):
    """Arm orders for an interleaved best-of A/B: alternate which arm runs
    first each rep, so neither arm systematically sees the warmer host."""
    for rep in range(reps):
        yield (0, 1) if rep % 2 == 0 else (1, 0)


def ab_compare(run_a, run_b, reps: int = 5, env_a: dict | None = None,
               env_b: dict | None = None, warmup: bool = True) -> dict:
    """Interleaved A/B, min-of-warm-reps: both arms stay resident (distinct
    compiled programs) and alternate, the min of each arm's warm reps is the
    signal on a noisy shared-core container.  `env_a`/`env_b` are
    env-override dicts applied around the corresponding arm (None values
    clear).  Returns {"a_s", "b_s", "a_all", "b_all", "improvement",
    "last_a", "last_b"} — improvement = a_s / b_s (B is the new path)."""
    def arm(fn, env):
        with env_overrides(**(env or {})):
            t0 = time.time()
            out = fn()
            return time.time() - t0, out
    last = [None, None]
    if warmup:                           # compile both resident program sets
        _, last[0] = arm(run_a, env_a)
        _, last[1] = arm(run_b, env_b)
    walls: list[list[float]] = [[], []]
    for order in ab_orders(reps):
        for i in order:
            w, last[i] = arm((run_a, run_b)[i], (env_a, env_b)[i])
            walls[i].append(w)
    a_s, b_s = min(walls[0]), min(walls[1])
    return {"a_s": a_s, "b_s": b_s, "a_all": walls[0], "b_all": walls[1],
            "improvement": a_s / b_s if b_s else float("inf"),
            "last_a": last[0], "last_b": last[1]}


def min_warm(fn, reps: int) -> tuple[float, list[float]]:
    """Min-of-N warm wall time of a single resident path (the single-arm
    guard rows); returns (min_s, all_s)."""
    walls = []
    for _ in range(reps):
        t0 = time.time()
        fn()
        walls.append(time.time() - t0)
    return min(walls), walls


_EPISODE_CACHE: dict = {}


def cached_episode(app: str, technique: str, mapper: str, **kw):
    """Memoized (app, technique, mapper) runs shared across benchmarks."""
    from repro.nmp import NMPConfig, make_trace, run_episode, run_program
    key = (app, technique, mapper, N_OPS, tuple(sorted(kw.items())))
    if key in _EPISODE_CACHE:
        return _EPISODE_CACHE[key]
    cfg = kw.pop("cfg", NMPConfig())
    tr = make_trace(app, n_ops=N_OPS)
    with Timer() as t:
        if mapper == "aimm":
            results = run_program(tr, cfg, technique=technique, mapper="aimm",
                                  episodes=EPISODES, seed=0, **kw)
            # converged behaviour: greedy evaluation episode with the trained
            # DNN (paper's steady-state claim; exploration off)
            res = run_episode(tr, cfg, technique=technique, mapper="aimm",
                              agent=results[-1].agent, explore=False, **kw)
            res_all = results + [res]
        else:
            res = run_episode(tr, cfg, technique=technique, mapper=mapper,
                              **kw)
            res_all = [res]
    out = {"res": res, "all": res_all, "us": t.us, "trace": tr}
    _EPISODE_CACHE[key] = out
    return out


_GRID_CACHE: dict = {}


def cached_grid(grid_name: str, cfg=None, **kw):
    """Memoized batched run of a named scenario grid (see repro.nmp.scenarios).

    `cfg` overrides the NMPConfig the sweep runs under (it is part of the
    memo key, so e.g. mesh-scaling and sensitivity points cache separately;
    the device-mesh signature is part of the key too, so cached results
    never cross a REPRO_SWEEP_DEVICES change, and builder kwargs — including
    figure_grid's seeds=SEEDS — key as before).
    Returns {"res": SweepResult, "grid": [Scenario], "us": wall_us}; lanes are
    addressed by `Scenario.name` via `lane_summary`."""
    from repro.nmp import NMPConfig, partition, scenarios, sweep
    cfg = cfg or NMPConfig()
    # seeds (when a builder takes them, e.g. figure_grid's seeds=SEEDS) are
    # part of kw and therefore of the key already.
    key = (grid_name, str(cfg), partition.mesh_signature(),
           tuple(sorted((k, str(v)) for k, v in kw.items())))
    if key in _GRID_CACHE:
        return _GRID_CACHE[key]
    grid = scenarios.build(grid_name, **kw)
    res = sweep.run_grid(grid, cfg)
    out = {"res": res, "grid": grid, "us": res.wall_s * 1e6}
    _GRID_CACHE[key] = out
    return out


def figure_grid(cfg=None, techniques=("bnmp", "ldb", "pei"),
                mappers=("none", "tom", "aimm"), apps_=None):
    """The shared app x technique x mapper grid behind the single-program
    figures (fig6-11, 14): every AIMM lane trains for EPISODES episodes and
    appends a greedy eval episode (the paper's converged-behaviour protocol).
    One `sweep.run_grid` call (memoized) covers all of them; with
    BENCH_SEEDS > 1 every cell carries a folded seed axis and figures can
    report mean±std bands via `lane_band`."""
    return cached_grid("single", cfg=cfg, apps=apps_ or apps(),
                       techniques=techniques, mappers=mappers, n_ops=N_OPS,
                       seeds=SEEDS, aimm_episodes=EPISODES,
                       eval_episode=True)


_STREAM_CACHE: dict = {}

# Shared continual-stream protocol: bench_continual and the fig9/continual
# rows must request the *same* stream or the cached_stream memo splits and
# the most expensive computation (warm stream + cold final phase) runs twice.
STREAM_N_OPS_PER_APP = N_OPS // 4 if FULL else N_OPS // 8
STREAM_EPISODES = 5 if FULL else 3


def cached_stream(name: str = "switch", cfg=None, **kw):
    """Memoized continual-stream run shared by the continual benchmarks.

    Executes a named program-phase stream (`repro.nmp.scenarios.STREAMS`)
    twice over its final phase: once *warm* (one PolicyStore threaded through
    every phase — the paper's continual-learning protocol) and once *cold*
    (the final phase alone with a fresh store), so warm-vs-cold rows come
    from one cached computation.  Returns {"stream", "res" (StreamResult),
    "cold" (SweepResult of the final phase), "us"}."""
    from repro.nmp import NMPConfig, partition, scenarios, sweep
    from repro.nmp.continual import run_stream
    cfg = cfg or NMPConfig()
    key = (name, str(cfg), partition.mesh_signature(),
           tuple(sorted((k, str(v)) for k, v in kw.items())))
    if key in _STREAM_CACHE:
        return _STREAM_CACHE[key]
    stream = scenarios.build_stream(name, **kw)
    with Timer() as t:
        res = run_stream(stream, cfg)
        cold = sweep.run_grid(stream[-1], cfg)   # fresh store => cold lineage
    out = {"stream": stream, "res": res, "cold": cold, "us": t.us}
    _STREAM_CACHE[key] = out
    return out


def grid_us(cached: dict) -> float:
    """Per-lane wall-time attribution for a cached grid's CSV rows: the whole
    sweep's wall time split evenly over its lanes."""
    return cached["us"] / len(cached["grid"])


def lane_index(cached: dict, name: str) -> int:
    for i, sc in enumerate(cached["grid"]):
        if sc.name == name:
            return i
    raise KeyError(name)


def lane_summary(cached: dict, name: str, episode: int | None = None) -> dict:
    """Summary dict for the lane whose Scenario.name == `name`."""
    return cached["res"].episode_summary(lane_index(cached, name), episode)


def lane_band(cached: dict, name: str, episode: int | None = None) -> dict:
    """Variance band (mean±std across the folded seed axis) for the seed
    group containing the lane named `name` — see SweepResult.variance_band."""
    return cached["res"].variance_band(lane_index(cached, name), episode)
