"""A grid cell's run on the CPU at a small size, every lane compared with
the reference: sound, `correct` holds; with the timed path broken
underneath, `correct` comes out false."""
from __future__ import annotations

import numpy as np
import pytest


def _half_left_out(run_grid):
    """The first half of the scenarios simulated, the rest given copies of
    their answers."""
    def broken(scs, cfg, *a, **kw):
        h = (len(scs) + 1) // 2
        res = run_grid(scs[:h], cfg, *a, **kw)
        res.metrics = {k: np.concatenate([v, v[:len(scs) - h]])
                       for k, v in res.metrics.items()}
        return res
    return broken


def _answer_altered(run_grid):
    """One scenario's cycle count off by a thousandth where the sweep
    produces it."""
    def broken(scs, cfg, *a, **kw):
        res = run_grid(scs, cfg, *a, **kw)
        cycles = res.metrics["cycles"].copy()
        cycles[-1, 0] *= 1.001
        res.metrics["cycles"] = cycles
        return res
    return broken


def _answers_swapped(run_grid):
    """The first and the last scenario's answers landed on each other."""
    def broken(scs, cfg, *a, **kw):
        res = run_grid(scs, cfg, *a, **kw)
        order = [len(scs) - 1] + list(range(1, len(scs) - 1)) + [0]
        res.metrics = {k: v[order] for k, v in res.metrics.items()}
        return res
    return broken


def _step_unchanged(epoch_apply):
    """The epoch step hands back the state it was given."""
    def broken(env, mid, *a, **kw):
        _, metrics = epoch_apply(env, mid, *a, **kw)
        return env, metrics
    return broken


def _plant(fault, monkeypatch):
    from repro.nmp import engine, sweep
    if fault == "step_returns_state_unchanged":
        monkeypatch.setattr(engine, "_epoch_apply",
                            _step_unchanged(engine._epoch_apply))
        return
    wrap = {"half_the_batch_left_out": _half_left_out,
            "answer_altered": _answer_altered,
            "answers_swapped": _answers_swapped}[fault]
    monkeypatch.setattr(sweep, "run_grid", wrap(sweep.run_grid))


def test_sound_run_is_correct(small_run):
    res = small_run()
    assert res["correct"] is True
    lim = res["limits"]
    assert lim["mismatched_counts"]["value"] == 0
    assert lim["max_rel_gap"]["value"] <= lim["max_rel_gap"]["limit"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("fault", ["step_returns_state_unchanged",
                                   "half_the_batch_left_out",
                                   "answer_altered", "answers_swapped"])
def test_broken_path_is_not_correct(fault, small_run, monkeypatch):
    _plant(fault, monkeypatch)
    res = small_run()
    assert res["correct"] is False
