#!/usr/bin/env python3
"""The control of the comparison that decides `correct`: the reference put
in the program's place with each epoch's cycle count kept in bfloat16, the
nearest precision below the float32 the simulator states, compared with
the float32 reference by the run's own numbers (`compare.compare`).  It has
to come out as not correct, on every seed.

    python3 chipbench/control.py --workload <cell> --seeds 1 2 3

For each seed it takes the scenarios a run of the cell compares (each
lane of the grid in a call drawn from the seed, over `--calls` calls) and
prints the numbers beside the cell's limits.  It needs no chip: the
reference runs on the host.  The benchmark's own runs never run it."""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import ml_dtypes

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import compare, reference, traffic  # noqa: E402

CONTROL_DTYPE = ml_dtypes.bfloat16


def readings(cfg: dict, mix: dict, seed: int, calls: int = 10) -> dict:
    """The control's numbers on the scenarios a run of the cell with
    `calls` calls in its window compares."""
    sets = traffic.trace_sets(mix, seed)
    per_call = [traffic.grid_call(mix, sets, seed, c + 1)
                for c in range(calls)]
    bad, gap = 0, 0.0
    for i, c in enumerate(traffic.pick_calls(seed, calls,
                                             len(per_call[0]))):
        p = per_call[c][i]
        want = reference.scenario(p.trace, p.technique, p.mapper,
                                  p.episodes, cfg)
        got = reference.scenario(p.trace, p.technique, p.mapper,
                                 p.episodes, cfg, CONTROL_DTYPE)
        b, g, _ = compare.compare(got, want, p.episodes)
        bad, gap = bad + b, max(gap, g)
    return {"mismatched_counts": bad, "max_rel_gap": gap}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--calls", type=int, default=10)
    args = ap.parse_args()
    from chipbench import run
    _, cell, cfg_file, mix = run.load_cell(args.workload)
    for seed in args.seeds:
        got = readings(cfg_file["nmp_config"], mix, seed, args.calls)
        print(json.dumps({"cell": cell["name"], "seed": seed,
                          "control": got, "limits": mix["limits"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
