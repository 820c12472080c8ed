"""The learned grid cell on the CPU at a small size (the paper's 4x4
network, 1,024-op traces, 2 episodes): the AIMM reference replaying the
program's recorded actions agrees with the program on every forced action
and on learned lanes; a whole small run is correct; with the program or
its answers broken underneath, `correct` comes out false; and the
bfloat16-cycles control fails `max_rel_gap` alone.

The small configuration shortens `remap_ttl` to 3 epochs so that compute
remaps expire inside an 8-epoch episode."""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import jax
import ml_dtypes
import numpy as np
import pytest

from chipbench import (compare, learned_grid, program, reference_aimm,
                       traffic)
from chipbench.traces import make_trace

HERE = Path(__file__).resolve().parent
CONFIG = json.loads((HERE / "configs" / "paper_4x4_aimm.json").read_text())
CFG = dict(CONFIG["nmp_config"], remap_ttl=3)
MIX = json.loads((HERE / "mixes" / "aimm_grid.json").read_text())
SMALL_MIX = dict(MIX, apps=["KM", "RBM"], n_ops=1024, episodes=2,
                 trace_sets=2)
BATCH = CONFIG["agent"]["batch_size"]


def test_reference_replays_forced_and_learned_lanes():
    """Every forced action 0-7 under each technique, and learned lanes
    with three seeds folded on one lane, through `run_grid`."""
    from repro.nmp.scenarios import Scenario
    tr = make_trace("SPMV", n_ops=1024, seed=5)
    protos = [(tech, fa, seed) for tech in ("bnmp", "ldb", "pei")
              for fa in range(-1, 8)
              for seed in ((3, 103, 203) if fa < 0 else (17,))]
    scs = [Scenario(name=f"{t}/{fa}/{s}", trace=program.to_trace(tr),
                    technique=t, mapper="aimm", seed=s, episodes=2,
                    forced_action=fa) for t, fa, s in protos]
    res = program.run_grid(scs, program.nmp_config(CFG))
    m = res.metrics
    bad = illegal = 0
    gap = 0.0
    for i, (tech, fa, _) in enumerate(protos):
        want = reference_aimm.scenario(tr, tech, 2, CFG, m["action_t"][i],
                                       m["target_t"][i], fa < 0, BATCH)
        b, g, _ = compare.compare({k: v[i] for k, v in m.items()}, want, 2)
        bad, gap = bad + b, max(gap, g)
        illegal += int(want["illegal_actions"].sum())
    print(f"mismatched_counts {bad} max_rel_gap {gap:.3e} "
          f"illegal_actions {illegal}")
    assert bad == 0 and illegal == 0 and gap <= MIX["limits"]["max_rel_gap"]
    # the replay exercised what it replays
    assert m["migrations"].sum() > 0 and m["access_on_migrated"].sum() > 0
    assert set(np.unique(m["action_t"])) == set(range(8))
    assert res.counters["agent_invocations"] == sum(
        int(m["invoke_t"][i].sum()) for i, p in enumerate(protos)
        if p[1] < 0)


@pytest.fixture
def learned_run(monkeypatch, capsys):
    """`learned_run()` runs the small learned grid cell through `run.main`
    and returns (result line, the run's `Cell`).  Compiled programs are
    dropped before and after, so a fault planted in the program is traced
    into it."""
    from chipbench import run
    cells = []

    class Recorded(learned_grid.Cell):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            cells.append(self)

    def go():
        def small(name):
            bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
            c = {"name": name, "config": "paper_4x4_aimm",
                 "traffic": "small_aimm_grid", "chips": 1}
            return bench, c, dict(CONFIG, nmp_config=CFG), SMALL_MIX
        monkeypatch.setattr(run, "load_cell", small)
        monkeypatch.setattr(run, "configure_cache", lambda: None)
        monkeypatch.setattr(run, "require_devices",
                            lambda devices, chips: devices)
        monkeypatch.setattr(learned_grid, "Cell", Recorded)
        monkeypatch.setattr(learned_grid, "agent_settings",
                            lambda fields: CONFIG["agent"])
        monkeypatch.setattr(sys, "argv", [
            "run.py", "--workload", "paper_4x4.small_aimm_grid",
            "--seed", str(2**33 + 9), "--seconds", "0.2", "--trace", "0"])
        jax.clear_caches()
        try:
            assert run.main() == 0
        finally:
            jax.clear_caches()
        out = capsys.readouterr().out.strip().splitlines()
        return json.loads(out[-1]), cells[-1]
    return go


def test_small_run_is_correct_and_the_control_is_not(learned_run):
    res, cell = learned_run()
    lim = res["limits"]
    print({k: v["value"] for k, v in lim.items()})
    assert res["correct"] is True
    assert lim["mismatched_counts"]["value"] == 0
    assert lim["illegal_actions"]["value"] == 0
    assert lim["max_rel_gap"]["value"] <= lim["max_rel_gap"]["limit"]
    assert res["failed"] == 0 and res["attempted"] > 0
    fires = [cell.counters[c]["agent_fires"] for c in cell.counters]
    assert len(fires) == len(cell.calls) + 1 and min(fires) > 0
    bad, gap, illegal, _, cells = cell.compared(ml_dtypes.bfloat16)
    print(f"bfloat16 control: mismatched_counts {bad} max_rel_gap "
          f"{gap:.3e} illegal_actions {illegal}")
    assert cells == len(SMALL_MIX["apps"]) * len(MIX["techniques"]) * 3
    assert bad == 0 and illegal == 0
    assert gap > 10 * MIX["limits"]["max_rel_gap"]


def _data_not_moved(epoch_apply):
    """A data action that moves no page: the mapping stays as it was."""
    def broken(env, mid, *a, **kw):
        new_env, metrics = epoch_apply(env, mid, *a, **kw)
        return new_env._replace(page_to_cube=env.page_to_cube), metrics
    return broken


def _ttl_ignored(epoch_apply):
    """Compute remaps never expire."""
    def broken(env, mid, action, rw, ctx, cfg, flags):
        return epoch_apply(env, mid, action, rw, ctx,
                           dataclasses.replace(cfg, remap_ttl=10**6), flags)
    return broken


def _near_not_a_neighbour(random_neighbor):
    """A "near" remap targets the compute cube itself."""
    def broken(rng, cube, nbr, nbr_valid):
        return cube
    return broken


def _actions_shifted(run_grid):
    """The recorded actions one epoch late."""
    def broken(scs, cfg, *a, **kw):
        res = run_grid(scs, cfg, *a, **kw)
        res.metrics["action_t"] = np.roll(res.metrics["action_t"], 1, -1)
        return res
    return broken


def _answers_swapped(run_grid):
    """The first and the last cell's statistics landed on each other."""
    def broken(scs, cfg, *a, **kw):
        res = run_grid(scs, cfg, *a, **kw)
        order = [len(scs) - 1] + list(range(1, len(scs) - 1)) + [0]
        res.metrics = {k: (v if k in ("action_t", "target_t") else v[order])
                       for k, v in res.metrics.items()}
        return res
    return broken


def _plant(fault, monkeypatch):
    from repro.core import actions
    from repro.nmp import engine, sweep
    target, name, wrap = {
        "data_action_moves_no_page": (engine, "_epoch_apply",
                                      _data_not_moved),
        "remap_ttl_ignored": (engine, "_epoch_apply", _ttl_ignored),
        "near_target_not_a_neighbour": (actions, "random_neighbor",
                                        _near_not_a_neighbour),
        "actions_shifted_one_epoch": (sweep, "run_grid", _actions_shifted),
        "two_cells_answers_swapped": (sweep, "run_grid", _answers_swapped),
    }[fault]
    monkeypatch.setattr(target, name, wrap(getattr(target, name)))


@pytest.mark.parametrize("fault", ["data_action_moves_no_page",
                                   "remap_ttl_ignored",
                                   "near_target_not_a_neighbour",
                                   "actions_shifted_one_epoch",
                                   "two_cells_answers_swapped"])
def test_broken_path_is_not_correct(fault, learned_run, monkeypatch):
    _plant(fault, monkeypatch)
    res, _ = learned_run()
    print(fault, {k: v["value"] for k, v in res["limits"].items()})
    assert res["correct"] is False
    if fault == "near_target_not_a_neighbour":
        assert res["limits"]["illegal_actions"]["value"] > 0


def test_setup_stops_without_recorded_actions(monkeypatch):
    """A program that records no actions (as before they were added)
    stops the run at set-up with a one-line reason."""
    from repro.nmp import sweep

    def without(run_grid):
        def old(scs, cfg, *a, **kw):
            res = run_grid(scs, cfg, *a, **kw)
            for k in ("action_t", "target_t"):
                res.metrics.pop(k)
            return res
        return old
    monkeypatch.setattr(sweep, "run_grid", without(sweep.run_grid))
    monkeypatch.setattr(learned_grid, "agent_settings",
                        lambda fields: CONFIG["agent"])
    tiny = dict(SMALL_MIX, apps=["KM"], techniques=["bnmp"],
                seeds_per_cell=1, n_ops=256, episodes=1, trace_sets=1)
    cell = learned_grid.Cell(CFG, tiny, 7)
    with pytest.raises(SystemExit, match="records no action_t"):
        cell.setup()


def test_agent_settings_found_by_sizes():
    assert learned_grid.agent_settings(CONFIG["nmp_config"]) \
        == CONFIG["agent"]
    with pytest.raises(SystemExit):
        learned_grid.agent_settings(CFG)


@pytest.mark.parametrize("rec,want", [
    ({"agent_fires": 30, "agent_epochs": 120}, 25.0),
    ({}, None), ({"agent_fires": 0, "agent_epochs": 0}, None)])
def test_agent_fire_share(rec, want):
    from chipbench.metrics import agent_fire_share
    assert agent_fire_share.read(rec) == want


@pytest.mark.parametrize("rec,want", [
    ({"agent_fires": 4, "trace": {"program_s": 2.0}}, 5e5),
    ({"agent_fires": 4, "trace": None}, None),
    ({"trace": {"program_s": 2.0}}, None)])
def test_sweep_device_us_per_agent_fire(rec, want):
    from chipbench.metrics import sweep_device_us_per_agent_fire as r
    assert r.read(rec) == want


def test_grid_call_of_the_cell_mix():
    sets = traffic.trace_sets(dict(MIX, n_ops=256, trace_sets=1), 5)
    protos = traffic.grid_call(dict(MIX, n_ops=256), sets, 5, 1)
    assert len(protos) == 81 and {p.mapper for p in protos} == {"aimm"}
    # seed replicas of a lane are consecutive, as the check takes them
    assert protos[0].trace is protos[2].trace
    assert protos[1].seed - protos[0].seed == 100
    assert sum(MIX["n_ops"] * p.episodes for p in protos) == 6_635_520
