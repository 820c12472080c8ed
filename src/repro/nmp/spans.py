"""Host spans of the grid path on the profiler's timeline.

`span(name, **counters)` is a `jax.profiler.TraceAnnotation` named
`repro.<name>`: while `jax.profiler` traces, it records the enclosed host
phase on the thread that runs it, on the same clock as the device's
operations, with `counters` (whole numbers) as the event's stats.  When the
profiler is off it is a no-op in native code, so counters are only values
already at hand (`len`, `.nbytes`).

Every span of one `run_grid` call carries `call=<n>` (`next_call`), so the
spans the landing thread writes can be tied to their call."""
from __future__ import annotations

import itertools

import jax

PREFIX = "repro."

_calls = itertools.count(1)


def span(name: str, **counters) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(PREFIX + name, **counters)


def next_call() -> int:
    """A process-wide number for each `run_grid` call, from 1."""
    return next(_calls)


def nbytes(tree) -> int:
    """Total bytes of a pytree's array leaves (shape metadata only)."""
    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))
