"""Learned grid cells: whole `run_grid` calls of AIMM lanes back to back,
each (lane, seed) cell with its own agent learning across the episodes of
the call, as an architecture researcher's sweep of the learned mapper
submits them.

The window is a grid cell's (`grid.Cell`): it starts after one warm call
and ends when the first call that finishes after `--seconds` returns.  The
check replays, for each lane in one call drawn from the seed, every seed
and every episode on `reference_aimm` under the actions and targets the
program recorded (`action_t`, `target_t`).  A program that does not record
them cannot be checked, and set-up stops right after the warm call."""
from __future__ import annotations

import json
from pathlib import Path

from chipbench import (compare, grid, program, reference_aimm, tracing,
                       traffic)

CONFIG_DIR = Path(__file__).resolve().parent / "configs"
COUNTERS = ("agent_epochs", "agent_fires", "agent_invocations")


def agent_settings(cfg_fields: dict) -> dict:
    """The `agent` block of the configuration whose `nmp_config` these are
    (`run.py` hands a `Cell` the `nmp_config` alone)."""
    for path in sorted(CONFIG_DIR.glob("*.json")):
        c = json.loads(path.read_text())
        if "agent" in c and c["nmp_config"] == cfg_fields:
            return c["agent"]
    raise SystemExit("chipbench: no configuration states the agent of "
                     "this cell")


class Cell(grid.Cell):
    def __init__(self, cfg_fields: dict, mix: dict, seed: int):
        super().__init__(cfg_fields, mix, seed)
        self.agent = agent_settings(cfg_fields)
        self.counters: dict = {}     # call -> the program's agent counters

    def _call(self, call: int):
        protos = traffic.grid_call(self.mix, self.sets, self.seed, call)
        traces = self.program_sets[call % len(self.sets)]
        scs = [program.scenario(p, f"{p.trace.name}/{p.technique}/"
                                f"{p.mapper}/{i}", traces[p.trace.name])
               for i, p in enumerate(protos)]
        with tracing.span("run_grid"):
            res = program.run_grid(scs, self.cfg)
        self.counters[call] = getattr(res, "counters", {})
        return protos, res.metrics

    def setup(self) -> None:
        _, metrics = self._call(0)
        if "action_t" not in metrics or "target_t" not in metrics:
            raise SystemExit("chipbench: the program records no action_t / "
                             "target_t, so its learned lanes cannot be "
                             "checked")

    def window(self, seconds: float) -> dict:
        """The grid cell's run record, with the program's agent counters
        summed over the window's calls."""
        rec = super().window(seconds)
        window = [self.counters[c] for c in range(1, len(self.calls) + 1)]
        for k in COUNTERS:
            if all(k in c for c in window):
                rec[k] = sum(int(c[k]) for c in window)
        return rec

    def compared(self, control_dtype=None
                 ) -> tuple[int, float, int, int, int]:
        """(mismatched counts, max relative gap, illegal actions, values
        compared, cells compared): every seed and episode of each lane, in
        one call per lane drawn from the seed, against the reference fed
        the call's recorded actions.  With `control_dtype` the reference
        with each epoch's cycle count kept in that precision takes the
        program's place (the control of the comparison)."""
        n_seeds = self.mix["seeds_per_cell"]
        n_lanes = len(self.calls[0][0]) // n_seeds
        bad = total = illegal = cells = 0
        gap = 0.0
        for lane, c in enumerate(traffic.pick_calls(
                self.seed, len(self.calls), n_lanes)):
            protos, m = self.calls[c]
            for i in range(lane * n_seeds, (lane + 1) * n_seeds):
                p = protos[i]

                def replay(dtype):
                    return reference_aimm.scenario(
                        p.trace, p.technique, p.episodes, self.cfg_fields,
                        m["action_t"][i], m["target_t"][i], learned=True,
                        batch_size=self.agent["batch_size"],
                        cycles_dtype=dtype)
                want = replay(reference_aimm.f32)
                got = ({k: v[i] for k, v in m.items()}
                       if control_dtype is None else replay(control_dtype))
                b, g, t = compare.compare(got, want, p.episodes)
                bad, gap, total = bad + b, max(gap, g), total + t
                illegal += int(want["illegal_actions"].sum())
                cells += 1
        return bad, gap, illegal, total, cells

    def check(self) -> tuple[int, int, dict, dict]:
        """(attempted, failed, {check: (value, limit)}, notes): every
        scenario of every call must be complete, and every cell of each
        lane is replayed on the reference in one call drawn from the
        seed."""
        incomplete = sum(
            not compare.complete({k: v[i] for k, v in m.items()},
                                 protos[i].trace.n_ops, protos[i].episodes)
            for protos, m in self.calls for i in range(len(protos)))
        bad, gap, illegal, total, cells = self.compared()
        limits = self.mix["limits"]
        return (sum(len(p) for p, _ in self.calls), incomplete,
                {"mismatched_counts": (bad, limits["mismatched_counts"]),
                 "max_rel_gap": (gap, limits["max_rel_gap"]),
                 "illegal_actions": (illegal, limits["illegal_actions"]),
                 "incomplete_answers": (incomplete, 0)},
                {"compared_scenarios": cells, "compared_values": total})
