"""Grid cells: whole `run_grid` calls back to back, as an architecture
researcher's sweep submits them.

The window starts after one warm call and ends when the first call that
finishes after `--seconds` returns; `sim_ops_per_s` is every simulated op of
every call in the window over the window's wall time."""
from __future__ import annotations

import time

import numpy as np

from chipbench import compare, program, reference, tracing, traffic


class Cell:
    def __init__(self, cfg_fields: dict, mix: dict, seed: int):
        self.cfg_fields = cfg_fields
        self.cfg = program.nmp_config(cfg_fields)
        self.mix, self.seed = mix, seed
        self.sets = traffic.trace_sets(mix, seed)
        self.program_sets = [{app: program.to_trace(tr)
                              for app, tr in s.items()} for s in self.sets]
        self.calls: list = []        # (protocols, metrics) of window calls

    def _call(self, call: int):
        protos = traffic.grid_call(self.mix, self.sets, self.seed, call)
        traces = self.program_sets[call % len(self.sets)]
        scs = [program.scenario(p, f"{p.trace.name}/{p.technique}/"
                                f"{p.mapper}/{i}", traces[p.trace.name])
               for i, p in enumerate(protos)]
        with tracing.span("run_grid"):
            res = program.run_grid(scs, self.cfg)
        return protos, res.metrics

    def setup(self) -> None:
        self._call(0)

    def window(self, seconds: float) -> dict:
        """Run calls until `seconds` have passed; returns the run record."""
        t0, cpu0 = time.perf_counter(), time.process_time()
        ends = []
        while True:
            self.calls.append(self._call(len(self.calls) + 1))
            ends.append(time.perf_counter() - t0)
            elapsed = ends[-1]
            if elapsed >= seconds:
                break
        ops = sum(_ops(protos, m) for protos, m in self.calls)
        walls = np.diff([0.0] + ends)
        return {"window_s": elapsed, "calls": len(self.calls),
                "ops": ops, "traced_ops": ops,
                "host_cpu_s": time.process_time() - cpu0,
                "call_wall_s": [round(float(w), 4) for w in walls],
                "e2e": {"sim_ops_per_s": ops / elapsed}}

    def check(self) -> tuple[int, int, dict, dict]:
        """(attempted, failed, {check: (value, limit)}, notes): every
        scenario of every call must be complete, and each lane of the grid
        is compared with the reference in one call drawn from the seed."""
        incomplete = sum(
            not compare.complete({k: v[i] for k, v in m.items()},
                                 protos[i].trace.n_ops, protos[i].episodes)
            for protos, m in self.calls for i in range(len(protos)))
        n_lanes = len(self.calls[0][0])
        bad = total = 0
        gap = 0.0
        for i, c in enumerate(traffic.pick_calls(self.seed, len(self.calls),
                                                 n_lanes)):
            protos, m = self.calls[c]
            p = protos[i]
            want = reference.scenario(p.trace, p.technique, p.mapper,
                                      p.episodes, self.cfg_fields)
            b, g, t = compare.compare({k: v[i] for k, v in m.items()},
                                      want, p.episodes)
            bad, gap, total = bad + b, max(gap, g), total + t
        limits = self.mix["limits"]
        return (sum(len(p) for p, _ in self.calls), incomplete,
                {"mismatched_counts": (bad, limits["mismatched_counts"]),
                 "max_rel_gap": (gap, limits["max_rel_gap"]),
                 "incomplete_answers": (incomplete, 0)},
                {"compared_scenarios": n_lanes, "compared_values": total})


def _ops(protos, metrics) -> float:
    return float(sum(np.sum(metrics["ops"][i, :p.episodes])
                     for i, p in enumerate(protos)))
