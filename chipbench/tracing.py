"""Profiler trace of a run's traced window, and its reduction to the
numbers the per-layer readers take.

The harness writes its own host spans (`span`) around every call into the
program; the profiler records them on the host's timeline beside the
device's operations.  `reduce` turns one trace into:

* `window_s`: the traced window, from the first harness span's start to
  the last one's end;
* `busy_s`: the union of the intervals in which an operation ran on the
  device (averaged over the devices), inside the window;
* `program_s`: the device time of the sweep program, found by its jit name;
* `breakdown`: the device operations that took the most time (leaf ops
  summed by instruction name), and the longest idle gaps, each labelled by
  the harness span the host was in.
"""
from __future__ import annotations

import contextlib
import glob
import os
from collections import defaultdict
from typing import NamedTuple

import jax

SPAN_PREFIX = "chipbench."
PROGRAM_NAME = "_run_sweep"          # the sweep program's jit name
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


class Event(NamedTuple):
    name: str
    start: float                      # seconds on the trace's clock
    end: float


class Trace(NamedTuple):
    device_ops: dict                  # device name -> [Event] (XLA ops)
    device_modules: dict              # device name -> [Event] (programs)
    spans: list                       # harness host spans [Event]


def span(name: str):
    """A host span on the profiler's timeline (a no-op when not tracing)."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


@contextlib.contextmanager
def capture(logdir: str):
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(logdir: str) -> Trace:
    """Read the `.xplane.pb` the profiler wrote under `logdir`."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one profiler trace under {logdir}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    ops, modules, spans = defaultdict(list), defaultdict(list), []
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if is_device and line.name in (OPS_LINE, MODULES_LINE):
                dest = ops if line.name == OPS_LINE else modules
                dest[plane.name].extend(
                    Event(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                    for ev in line.events)
            elif not is_device:
                spans.extend(Event(ev.name[len(SPAN_PREFIX):],
                                   ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    return Trace(dict(ops), dict(modules), sorted(spans, key=lambda e: e.start))


def clip(events, lo: float, hi: float) -> list[Event]:
    return [Event(e.name, max(e.start, lo), min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def union(events) -> list[tuple[float, float]]:
    """The merged intervals covered by `events`."""
    out: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return [(a, b) for a, b in out]


def gaps(busy: list[tuple[float, float]], lo: float,
         hi: float) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi] between the busy ones."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap: tuple[float, float], spans) -> str:
    """The harness span that covers most of `gap`; of spans that cover it
    alike, the innermost (the one that started last)."""
    covers = [(min(gap[1], s.end) - max(gap[0], s.start), s.start, s.name)
              for s in spans]
    covers = [c for c in covers if c[0] > 0]
    return max(covers)[2] if covers else "outside spans"


def leaves(events) -> list[Event]:
    """The events that contain no other: a device trace nests the ops of a
    loop or a branch inside the op that runs it."""
    ev = sorted(events, key=lambda e: (e.start, -e.end))
    return [e for e, nxt in zip(ev, ev[1:] + [None])
            if nxt is None or nxt.start >= e.end]


def op_name(name: str) -> str:
    """An XLA op's instruction name (`%fusion.12 = f32[...] ...` ->
    `fusion.12`)."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce(trace: Trace) -> dict | None:
    """The traced window's numbers (see the module docstring), or None when
    the trace holds no harness span or no device operation."""
    if not trace.spans or not trace.device_ops:
        return None
    lo = min(s.start for s in trace.spans)
    hi = max(s.end for s in trace.spans)
    window = hi - lo
    busy_by_dev, op_time, gap_list = [], defaultdict(float), []
    for dev, events in trace.device_ops.items():
        inside = clip(events, lo, hi)
        busy = union(inside)
        busy_by_dev.append(sum(b - a for a, b in busy))
        for e in leaves(inside):
            op_time[op_name(e.name)] += e.end - e.start
        gap_list += gaps(busy, lo, hi)
    program = sum(e.end - e.start
                  for events in trace.device_modules.values()
                  for e in clip(events, lo, hi) if PROGRAM_NAME in e.name)
    n_dev = len(trace.device_ops)
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gap_list, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "window_s": window,
        "busy_s": sum(busy_by_dev) / n_dev,
        "program_s": program / n_dev,
        "breakdown": {
            "device_ops": [[k, v / n_dev] for k, v in top_ops],
            "idle_gaps": [[label(g, trace.spans), g[1] - g[0]]
                          for g in top_gaps],
        },
    }
