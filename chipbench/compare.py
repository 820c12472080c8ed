"""The comparison that decides `correct`: one scenario's statistics, as the
program returned them, against the reference's.

* `mismatched_counts`: elements that differ, bit for bit, among the
  statistics that are exact counts whatever the arithmetic (ops, epochs,
  accesses, migrations, migrated pages and accesses, and the per-epoch
  valid-op and invocation timelines).
* `max_rel_gap`: the largest relative gap, |program - reference| /
  |reference|, over every element of the statistics that float32
  arithmetic produces (cycles, hops, compute utilisation, the ten energy
  counters and the per-epoch OPC timeline).  The program and the
  reference round their divisions and sums differently, so these agree
  to float32 rounding, not to the bit.

A statistic that is missing or has another shape counts as wholly wrong.
"""
from __future__ import annotations

import numpy as np

COUNTS = ("ops", "epochs", "access_total", "migrations", "pages_migrated",
          "access_on_migrated", "valid_t", "invoke_t")
FLOATS = ("cycles", "hops_sum", "util_sum", "energy", "opc_t")
WRONG = 1.0                  # the gap of a statistic that is not there


def compare(got: dict, want: dict, episodes: int) -> tuple[int, float, int]:
    """(mismatched counts, max relative gap, elements compared) over the
    first `episodes` episodes of every statistic."""
    bad, gap, total = 0, 0.0, 0
    for k in COUNTS + FLOATS:
        w = np.asarray(want[k])[:episodes]
        total += w.size
        g = got.get(k)
        g = None if g is None else np.asarray(g)[:episodes]
        if g is None or g.shape != w.shape:
            bad, gap = (bad + w.size, gap) if k in COUNTS else (bad, WRONG)
            continue
        if k in COUNTS:
            bad += int(np.count_nonzero(g != w))
            continue
        g64, w64 = g.astype(np.float64), w.astype(np.float64)
        if not np.isfinite(g64).all():
            gap = max(gap, WRONG)
            continue
        diff = np.abs(g64 - w64)
        scale = np.abs(w64)
        rel = np.where(scale > 0, diff / np.where(scale > 0, scale, 1.0),
                       np.where(diff > 0, WRONG, 0.0))
        gap = max(gap, float(rel.max(initial=0.0)))
    return bad, gap, total


def complete(got: dict, n_ops: int, episodes: int) -> bool:
    """Whether one scenario's answer is all there: finite statistics and
    `n_ops` ops simulated in each of its real episodes."""
    for k in COUNTS + FLOATS:
        v = np.asarray(got.get(k, np.array([np.nan])))[:episodes]
        if v.dtype.kind == "f" and not np.isfinite(v).all():
            return False
    ops = np.asarray(got["ops"])[:episodes]
    return ops.shape == (episodes,) and bool(np.all(ops == n_ops))
