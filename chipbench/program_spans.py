#!/usr/bin/env python3
"""The program's own host spans in a profiler trace, and the split of each
`run_grid` call's host time that they give.

The program writes one host span per phase of a `run_grid` call
(`repro.<name>`, `src/repro/nmp/spans.py`) on the thread that runs it, with
its counters as the event's stats.  `tracing` reads the harness's spans
alone; this module reads the program's beside them, on the same clock:

* `load`: the program's spans in a trace directory;
* `reduce`: their totals over the traced window that `tracing.reduce`
  uses (the harness's spans), and the device's idle time that no phase
  span covers;
* `split`: the host milliseconds of each phase per call, and that idle
  time as a share of the device's idle time.

    python3 chipbench/program_spans.py --workload <cell> --seed <n> --seconds <s>

runs one cell's window under the profiler, as `run.py --trace 1` does (cut
to at most `run.TRACE_SECONDS`), and prints one JSON line: the window's
`sim_ops_per_s`, the harness's trace numbers and the program's split.  It
compares nothing, so it reports no `correct`.  Unlike `run.py`, it turns
the profiler's Python tracer off: by default that records every Python
call, which stretches the host phases (on one TPU v5e, a grid call from
0.91 s to 1.13-1.37 s, most of it in the batch build), so the split would
not be the untraced call's."""
from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import shutil
import sys
import tempfile
from collections import defaultdict
from pathlib import Path
from typing import NamedTuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import run, tracing  # noqa: E402

PREFIX = "repro."                     # the program's span names
ROOT = "run_grid"                     # the root span of one call, in the
                                      # program and in the harness
IDS = ("call", "group")               # stats that name, not count
SPLIT = {                             # per-call host ms: the spans summed
    "grid_plan_ms_per_call": ("plan",),
    "grid_build_ms_per_call": ("build",),
    "grid_transfer_ms_per_call": ("place", "fetch"),
    "grid_land_ms_per_call": ("unfold", "stack"),
}


class Span(NamedTuple):
    name: str                         # without the `repro.` prefix
    start: float                      # seconds on the trace's clock
    end: float
    thread: str                       # the host line (thread) it ran on
    stats: dict                       # its counters and ids


def load(logdir: str) -> list[Span]:
    """The program's spans in the `.xplane.pb` under `logdir`, by start."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one profiler trace under {logdir}, "
                           f"found {len(paths)}")
    spans = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            spans.extend(Span(ev.name[len(PREFIX):], ev.start_ns * 1e-9,
                              ev.end_ns * 1e-9, f"{plane.name}/{i}",
                              dict(ev.stats))
                         for ev in line.events if ev.name.startswith(PREFIX))
    return sorted(spans, key=lambda s: s.start)


def self_times(spans) -> list[float]:
    """Each span's duration less what its children cover.  The spans of one
    thread nest, so a span's parent is the innermost span still open when
    it starts."""
    out = [0.0] * len(spans)
    stack: list[int] = []
    for i in sorted(range(len(spans)),
                    key=lambda i: (spans[i].start, -spans[i].end)):
        s = spans[i]
        while stack and spans[stack[-1]].end <= s.start:
            stack.pop()
        out[i] = s.end - s.start
        if stack:
            out[stack[-1]] -= min(s.end, spans[stack[-1]].end) - s.start
        stack.append(i)
    return out


def overlap(a, b) -> float:
    """The length of the intersection of two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(trace: tracing.Trace, spans) -> dict | None:
    """The program's spans over the traced window of `tracing.reduce` (the
    harness's first span's start to its last one's end), or None when the
    trace holds no harness span, no device operation or no program span
    there:

    * `self_s`: each span name's self time, summed over the threads;
    * `counters`: each `<span>.<counter>` stat summed (not the ids `call`
      and `group`);
    * `calls`: the harness's `run_grid` spans in the window;
    * `run_grid_s`: their summed duration, and `phase_cover_s`, the part
      of it that the union of the phase spans (every program span but the
      root) over all threads covers;
    * `idle_s`: the device's idle time in the window, and
      `idle_unattributed_s`, the part of it in which no phase span is open
      on any thread (each averaged over the devices)."""
    if not trace.spans or not trace.device_ops:
        return None
    lo = min(s.start for s in trace.spans)
    hi = max(s.end for s in trace.spans)
    spans = [s._replace(start=max(s.start, lo), end=min(s.end, hi))
             for s in spans if s.end > lo and s.start < hi]
    if not spans:
        return None
    self_s: dict = defaultdict(float)
    counters: dict = defaultdict(int)
    by_thread = defaultdict(list)
    for s in spans:
        by_thread[s.thread].append(s)
        for k, v in s.stats.items():
            if k not in IDS:
                counters[f"{s.name}.{k}"] += v
    for thread_spans in by_thread.values():
        for s, t in zip(thread_spans, self_times(thread_spans)):
            self_s[s.name] += t
    phases = tracing.union(s for s in spans if s.name != ROOT)
    idle = [tracing.gaps(tracing.union(tracing.clip(events, lo, hi)), lo, hi)
            for events in trace.device_ops.values()]
    idle_total = [sum(b - a for a, b in g) for g in idle]
    calls = [e for e in trace.spans if e.name == ROOT]
    return {
        "self_s": dict(self_s),
        "counters": dict(counters),
        "calls": len(calls),
        "run_grid_s": sum(e.end - e.start for e in calls),
        "phase_cover_s": overlap(tracing.union(calls), phases),
        "idle_s": sum(idle_total) / len(idle),
        "idle_unattributed_s": sum(t - overlap(g, phases)
                                   for t, g in zip(idle_total, idle))
        / len(idle),
    }


def split(program: dict | None) -> dict:
    """The per-call host milliseconds of each phase group (`SPLIT`),
    `host_idle_unattributed_share` (% of the device's idle time) and
    `phase_cover_share` (% of the harness's `run_grid` time that the phase
    spans cover); empty without program spans or calls."""
    if not program or program["calls"] <= 0:
        return {}
    out = {name: 1e3 * sum(program["self_s"].get(n, 0.0) for n in names)
           / program["calls"] for name, names in SPLIT.items()}
    if program["idle_s"] > 0:
        out["host_idle_unattributed_share"] = (
            100.0 * program["idle_unattributed_s"] / program["idle_s"])
    if program["run_grid_s"] > 0:
        out["phase_cover_share"] = (
            100.0 * program["phase_cover_s"] / program["run_grid_s"])
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=run.TRACE_SECONDS)
    args = ap.parse_args()

    run.configure_cache()
    import jax
    _bench, cell, cfg_file, mix = run.load_cell(args.workload)
    devices = run.require_devices(jax.devices(), cell["chips"])
    workload = importlib.import_module(f"chipbench.{mix['kind']}").Cell(
        cfg_file["nmp_config"], mix, args.seed)
    workload.setup()
    logdir = tempfile.mkdtemp(prefix="chipbench-spans-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        jax.profiler.start_trace(logdir, profiler_options=options)
        try:
            rec = workload.window(run.window_seconds(args.seconds, True))
        finally:
            jax.profiler.stop_trace()
        trace = tracing.load(logdir)
        spans = load(logdir)
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    harness = tracing.reduce(trace)
    program = reduce(trace, spans)
    run.say(json.dumps({
        "cell": cell["name"], "seed": args.seed,
        "device_kind": devices[0].device_kind,
        "window_s": rec["window_s"], "calls": rec["calls"],
        "sim_ops_per_s": rec["e2e"]["sim_ops_per_s"],
        "trace": {k: v for k, v in (harness or {}).items()
                  if k != "breakdown"},
        "program": program, "split": split(program)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
