"""The one traffic generator: reads a mix file (`chipbench/mixes/<name>.json`)
and makes the run's inputs from `--seed`.

A `grid` mix is the grid a `run_grid` caller submits: app x technique x
mapper x seed, each app's trace `n_ops` long.  Set-up makes `trace_sets`
sets of one trace per app from the seed; call k of the run simulates set
k mod `trace_sets` with fresh scenario seeds, so consecutive calls are
different work of the same size.

Every seed gives the same sizes (apps, op counts, footprints, cells); the
seed changes only the trace contents and the simulator's seeds.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import NamedTuple

import numpy as np

from chipbench.traces import make_trace

MIX_DIR = Path(__file__).resolve().parent / "mixes"
SEED_SPAN = 1 << 30          # simulator seeds stay well inside int32


class Protocol(NamedTuple):
    """One scenario of a call."""
    trace: object                # chipbench.traces.Trace
    technique: str
    mapper: str
    seed: int
    episodes: int


def load_mix(name: str) -> dict:
    return json.loads((MIX_DIR / f"{name}.json").read_text())


def _rng(*key: int) -> np.random.Generator:
    return np.random.default_rng([int(k) for k in key])


def trace_sets(mix: dict, seed: int) -> list[dict]:
    """`trace_sets` sets of one trace per app, made from the run's seed."""
    out = []
    for k in range(mix["trace_sets"]):
        seeds = _rng(seed, 0, k).integers(0, SEED_SPAN, len(mix["apps"]))
        out.append({app: make_trace(app, n_ops=mix["n_ops"], seed=int(s))
                    for app, s in zip(mix["apps"], seeds)})
    return out


def grid_call(mix: dict, sets: list[dict], seed: int,
              call: int) -> list[Protocol]:
    """The protocols of call number `call` of a grid mix."""
    tr = sets[call % len(sets)]
    base = int(_rng(seed, 1, call).integers(0, SEED_SPAN - 1000))
    return [Protocol(trace=tr[app], technique=tech, mapper=mapper,
                     seed=base + 100 * s, episodes=mix["episodes"])
            for app in mix["apps"] for tech in mix["techniques"]
            for mapper in mix["mappers"]
            for s in range(mix["seeds_per_cell"])]


def pick_calls(seed: int, n_calls: int, n_lanes: int) -> list[int]:
    """For each lane of the grid, the call whose answer is compared,
    drawn from the seed."""
    return [int(c) for c in _rng(seed, 3, n_calls).integers(0, n_calls,
                                                              n_lanes)]
