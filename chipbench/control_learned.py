#!/usr/bin/env python3
"""The control of a learned grid cell's comparison: the reference with
each epoch's cycle count kept in bfloat16, the nearest precision below the
float32 the simulator states, put in the program's place and compared with
the float32 reference, both replaying the actions the program recorded.
It has to fail `max_rel_gap` alone, on every seed.

    python3 chipbench/control_learned.py --workload <cell> --seeds 1 2 3

For each seed it runs the cell's warm call and a window of `--seconds`
(the program supplies the actions, so this needs the cell's device), then
prints the control's numbers beside the cell's limits.  The benchmark's
own runs never run it."""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import ml_dtypes

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import learned_grid, run  # noqa: E402

CONTROL_DTYPE = ml_dtypes.bfloat16


def readings(cell: learned_grid.Cell) -> dict:
    """The control's numbers over the cells a run's check compares."""
    bad, gap, illegal, _, _ = cell.compared(CONTROL_DTYPE)
    return {"mismatched_counts": bad, "max_rel_gap": gap,
            "illegal_actions": illegal}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args()
    run.configure_cache()
    _, c, cfg_file, mix = run.load_cell(args.workload)
    for seed in args.seeds:
        cell = learned_grid.Cell(cfg_file["nmp_config"], mix, seed)
        cell.setup()
        cell.window(args.seconds)
        print(json.dumps({"cell": c["name"], "seed": seed,
                          "control": readings(cell),
                          "limits": mix["limits"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
