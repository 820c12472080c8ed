"""Batched sweep vs serial engine: per-scenario metrics must match
bit-for-bit, including lanes whose traces are shorter than the batch
envelope (op-count and page-count padding) and scenarios folded onto a
vmapped seed axis (seed replicas of a cell share one lane and one copy of
its trace arrays — see nmp.plan).

Grids are sized so related checks share one compiled sweep signature
(same op/page envelope, episode count and agent mode => one XLA program).
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.nmp import NMPConfig, make_trace
from repro.nmp.engine import NO_TARGET, run_episode, run_program
from repro.nmp.scenarios import (Scenario, forced_action_grid, seed_variants,
                                 single_program_grid)
from repro.nmp.stats import summarize
from repro.nmp.sweep import run_grid

from tests.test_engine_golden import GOLDEN

CFG = NMPConfig()


def _assert_exact(serial: dict, batched: dict, label: str):
    for key in ("cycles", "ops", "opc"):
        assert serial[key] == batched[key], (label, key, serial[key],
                                             batched[key])


def _metrics_equal(a, b) -> bool:
    return (set(a.metrics) == set(b.metrics)
            and all(np.array_equal(np.asarray(a.metrics[k]),
                                   np.asarray(b.metrics[k]))
                    for k in a.metrics))


@pytest.fixture(scope="module")
def golden_grid():
    """Every engine-golden lane (seed 2, forced actions as keyed) in one
    `run_grid` call: the path the chip runs, padded to a common envelope."""
    keys = sorted(GOLDEN)
    traces = {(app, n): make_trace(app, n_ops=n) for app, n, *_ in keys}
    grid = [Scenario(name="/".join(map(str, k)), trace=traces[k[:2]],
                     technique=k[2], mapper=k[3], seed=2, forced_action=k[4])
            for k in keys]
    return keys, run_grid(grid, CFG)


@pytest.mark.parametrize("key", sorted(GOLDEN),
                         ids=lambda k: "/".join(map(str, k)))
def test_grid_reproduces_engine_goldens(golden_grid, key):
    keys, res = golden_grid
    s = res.episode_summary(keys.index(key), 0)
    assert (s["cycles"], s["ops"], s["opc"]) == GOLDEN[key], (key, s)


def test_grid_matches_serial_deterministic_lanes():
    """Mixed apps (different n_ops AND n_pages => padding exercised), mixed
    mappers {none, tom} and mixed techniques, one batched program: every lane
    reproduces its serial run_episode exactly."""
    grid = []
    for app, n_ops in (("KM", 384), ("RBM", 512), ("MAC", 640)):
        tr = make_trace(app, n_ops=n_ops)
        for mapper in ("none", "tom"):
            grid.append(Scenario(name=f"{app}/{mapper}", trace=tr,
                                 mapper=mapper))
    for tech in ("ldb", "pei"):
        grid.append(Scenario(name=f"KM/{tech}", trace=grid[0].trace,
                             technique=tech))
    res = run_grid(grid, CFG)
    for i, sc in enumerate(grid):
        serial = summarize(run_episode(sc.trace, CFG, sc.technique, sc.mapper,
                                       seed=sc.seed))
        _assert_exact(serial, res.episode_summary(i, 0), sc.name)
        assert res.episode_summary(i, 0)["ops"] == sc.trace.n_ops


@pytest.mark.slow
def test_grid_matches_serial_aimm_chained_episodes():
    """Multi-episode AIMM lanes (DQN persisted across the in-scan episode
    chain) match run_program per episode, even with op-count padding; the
    stacked final env stays physically valid."""
    grid = []
    for app, n_ops in (("KM", 384), ("SPMV", 768)):
        grid.append(Scenario(name=app, trace=make_trace(app, n_ops=n_ops),
                             mapper="aimm", episodes=2))
    res = run_grid(grid, CFG)
    for i, sc in enumerate(grid):
        serial = run_program(sc.trace, CFG, sc.technique, "aimm",
                             episodes=sc.episodes, seed=sc.seed)
        for e in range(sc.episodes):
            _assert_exact(summarize(serial[e]), res.episode_summary(i, e),
                          f"{sc.name}/ep{e}")
    p2c = np.asarray(res.final_env.page_to_cube)
    assert (p2c >= 0).all() and (p2c < CFG.n_cubes).all()
    assert res.metrics["cycles"].shape == (len(grid), res.n_episodes)


def test_grid_matches_serial_forced_actions():
    """Scripted-policy lanes (no DQN) match serial forced_action runs."""
    grid = forced_action_grid(app="KM", n_ops=384, actions=(0, 1, 5))
    res = run_grid(grid, CFG)
    for i, sc in enumerate(grid):
        serial = summarize(run_episode(sc.trace, CFG, sc.technique, "aimm",
                                       forced_action=sc.forced_action,
                                       seed=sc.seed))
        _assert_exact(serial, res.episode_summary(i, 0), sc.name)


def test_seed_folded_grid_matches_serial():
    """18+-cell grid with 3 seeds per cell: the plan layer folds the seed
    replicas onto a vmapped seed axis (9 lanes, not 27), and every
    (lane, seed) cell still reproduces its serial run bit-for-bit —
    including the scripted-AIMM cells, whose trajectories genuinely depend
    on the seed through the env RNG."""
    grid = []
    for app, n_ops in (("KM", 384), ("RBM", 512), ("MAC", 640)):
        tr = make_trace(app, n_ops=n_ops)
        for mapper, forced in (("none", -1), ("tom", -1), ("aimm", 1)):
            grid += seed_variants(
                Scenario(name=f"{app}/{mapper}", trace=tr, mapper=mapper,
                         forced_action=forced), seeds=(0, 1, 2))
    assert len(grid) == 27
    res = run_grid(grid, CFG)
    assert res.plan.n_lanes == 9            # 27 cells folded 3-to-1
    assert [g.n_seeds for g in res.plan.groups] == [3]
    for i, sc in enumerate(grid):
        serial = summarize(run_episode(sc.trace, CFG, sc.technique, sc.mapper,
                                       seed=sc.seed,
                                       forced_action=sc.forced_action))
        _assert_exact(serial, res.episode_summary(i, 0), f"{sc.name}/s{sc.seed}")
    # the scripted lanes' seeds must actually matter (env RNG drives the
    # random-neighbor action target), otherwise the band test is vacuous
    aimm0 = [i for i, sc in enumerate(grid)
             if sc.mapper == "aimm" and sc.trace.n_ops == 640]
    cyc = {res.episode_summary(i, 0)["cycles"] for i in aimm0}
    assert len(cyc) > 1


def test_variance_band_over_folded_seeds():
    tr = make_trace("SPMV", n_ops=384)
    grid = seed_variants(Scenario(name="SPMV/forced", trace=tr, mapper="aimm",
                                  forced_action=1), seeds=(0, 1, 2))
    res = run_grid(grid, CFG)
    assert res.seed_group(1) == [0, 1, 2]
    band = res.variance_band(0)
    assert band["n"] == 3 and band["seeds"] == [0, 1, 2]
    opcs = np.asarray([res.episode_summary(i, 0)["opc"] for i in range(3)])
    np.testing.assert_allclose(band["opc_mean"], opcs.mean())
    np.testing.assert_allclose(band["opc_std"], opcs.std())
    mean_tl, std_tl = res.opc_timeline_band(0)
    assert mean_tl.shape == std_tl.shape == (64,)
    assert (std_tl >= 0).all()


@pytest.mark.slow
def test_seed_folded_aimm_chained_matches_run_program():
    """Learned-policy lanes with a folded seed axis: every (seed, episode)
    cell of the in-scan episode chain matches its serial run_program — the
    per-seed DQNs train independently inside one compiled program."""
    tr = make_trace("KM", n_ops=384)
    grid = seed_variants(Scenario(name="KM/aimm", trace=tr, mapper="aimm",
                                  episodes=2), seeds=(0, 1, 2))
    res = run_grid(grid, CFG)
    assert res.plan.n_lanes == 1 and res.plan.groups[0].n_seeds == 3
    for i, sc in enumerate(grid):
        serial = run_program(sc.trace, CFG, sc.technique, "aimm",
                             episodes=sc.episodes, seed=sc.seed)
        for e in range(sc.episodes):
            _assert_exact(summarize(serial[e]), res.episode_summary(i, e),
                          f"s{sc.seed}/ep{e}")


def test_single_program_grid_builder_covers_cells():
    grid = single_program_grid(apps=("KM", "RBM"), mappers=("none", "aimm"),
                               n_ops=256, seeds=(0, 1))
    assert len(grid) == 2 * 2 * 2
    names = {sc.name for sc in grid}
    assert len(names) == len(grid)          # unique lane names


def test_learned_timelines_match_serial_with_seed_axis():
    """`action_t` / `target_t` of learned lanes folded three to a lane equal
    `run_program`'s per-episode `action` / `target` bit for bit; a lane of
    a group with no AIMM lane gets its timelines filled in on the host."""
    tr = make_trace("KM", n_ops=384)
    grid = seed_variants(Scenario(name="KM/aimm", trace=tr, mapper="aimm",
                                  episodes=2), seeds=(0, 1, 2))
    grid.append(Scenario(name="KM/none", trace=tr, episodes=2))
    res = run_grid(grid, CFG)
    assert res.plan.groups[0].n_seeds == 3
    m = res.metrics
    for i, sc in enumerate(grid[:3]):
        serial = run_program(sc.trace, CFG, sc.technique, "aimm",
                             episodes=sc.episodes, seed=sc.seed)
        for e in range(sc.episodes):
            for key, name in (("action_t", "action"), ("target_t", "target")):
                want = np.asarray(serial[e].metrics[name], np.uint8)
                assert m[key].dtype == np.uint8
                np.testing.assert_array_equal(m[key][i, e], want,
                                              err_msg=f"{sc.name}/{key}/{e}")
    assert (m["action_t"][3] == 0).all()
    assert (m["target_t"][3] == NO_TARGET).all()
    inv = m["invoke_t"][:3] > 0
    assert ((m["target_t"][:3] != NO_TARGET) <= inv).all()
    assert ((m["action_t"][:3] != 0) <= inv).all()


def test_mixed_commit_fires_match_serial_agents():
    """Learned cells of unequal length and two seeds each, so that in one
    fire some cells commit a transition and others do not (finished,
    between invocations, or with no previous span yet): every cell's final
    agent, replay included, equals its serial `run_episode` bit for bit."""
    grid = [Scenario(name=f"{app}/s{s}", trace=make_trace(app, n_ops=n),
                     mapper="aimm", seed=s, lineage=f"{app}-s{s}")
            for app, n in (("KM", 512), ("SPMV", 1024)) for s in (0, 1)]
    res = run_grid(grid, CFG)
    inv = res.metrics["invoke_t"][:, 0] > 0                # (cells, epochs)
    assert (inv.any(axis=0) & ~inv.all(axis=0)).any()      # mixed fires
    for sc in grid:
        serial = run_episode(sc.trace, CFG, sc.technique, "aimm",
                             seed=sc.seed).agent
        got = res.store.get(sc.lineage)
        assert int(got.replay.size) > 0
        want = jax.tree_util.tree_leaves_with_path(serial)
        assert jax.tree.structure(got) == jax.tree.structure(serial)
        for (path, w), g in zip(want, jax.tree.leaves(got)):
            np.testing.assert_array_equal(
                np.asarray(g), np.asarray(w),
                err_msg=f"{sc.name}{jax.tree_util.keystr(path)}")


def test_baseline_grid_lands_no_timelines(monkeypatch):
    """A none/tom grid neither computes nor fetches the AIMM timelines or
    the agent counters: it fetches the statistics it fetched before."""
    from repro.nmp import partition, sweep
    fetched = []
    host_fetch = partition.host_fetch

    def recording(tree):
        if isinstance(tree, dict):
            fetched.append(set(tree))
        return host_fetch(tree)
    monkeypatch.setattr(sweep.partition, "host_fetch", recording)
    tr = make_trace("RBM", n_ops=256)
    res = run_grid([Scenario(name=f"RBM/{m}", trace=tr, mapper=m)
                    for m in ("none", "tom")], CFG)
    stats = {"cycles", "ops", "hops_sum", "util_sum", "epochs", "migrations",
             "pages_migrated", "access_total", "access_on_migrated",
             "energy", "opc_t", "valid_t", "invoke_t"}
    assert fetched == [stats]
    assert set(res.metrics) == stats
    assert res.counters == {"agent_epochs": 0, "agent_fires": 0,
                            "agent_invocations": 0}


def test_agent_counters_match_invoke_t():
    """`agent_fires` counts the scanned epochs in which any learned cell
    invoked (the predicate of the agent cond), `agent_invocations` the
    invocations of every learned cell, over lanes of unequal length."""
    grid = [Scenario(name=f"{app}/aimm", trace=make_trace(app, n_ops=n),
                     mapper="aimm", episodes=2, seed=s)
            for app, n in (("KM", 256), ("SPMV", 512)) for s in (0, 1)]
    grid.append(Scenario(name="KM/forced", trace=grid[0].trace,
                         mapper="aimm", forced_action=6))
    res = run_grid(grid, CFG)
    inv = res.metrics["invoke_t"][:4]                  # learned cells
    assert res.counters["agent_fires"] == int(np.any(inv > 0, axis=0).sum())
    assert res.counters["agent_invocations"] == int(inv.sum())
    assert res.counters["agent_epochs"] == 2 * res.plan.n_epochs
    assert 0 < res.counters["agent_fires"] <= res.counters["agent_epochs"]


def test_sweep_knobs_validate(monkeypatch):
    from repro.nmp import sweep
    monkeypatch.setenv(sweep.LAND_KNOB, "later")
    with pytest.raises(ValueError, match="REPRO_SWEEP_LAND.*later"):
        sweep.land_mode()
    monkeypatch.setenv(sweep.LAND_KNOB, "sync")
    assert sweep.land_mode() == "sync"
    monkeypatch.delenv(sweep.LAND_KNOB, raising=False)
    assert sweep.land_mode() == "async"

    monkeypatch.setenv(sweep.STAGING_KNOB, "maybe")
    with pytest.raises(ValueError, match="REPRO_STORE_STAGING.*maybe"):
        sweep.staging_enabled()
    monkeypatch.setenv(sweep.STAGING_KNOB, "off")
    assert sweep.staging_enabled() is False
    monkeypatch.delenv(sweep.STAGING_KNOB, raising=False)
    assert sweep.staging_enabled() is True


def test_async_land_and_staging_bit_identical(monkeypatch):
    """Chained lineage run_grid calls under the defaults (async landing,
    staging buffers) must produce bit-identical metrics AND final store
    snapshots to the sync/per-cell path."""
    from repro.nmp import sweep
    grid = single_program_grid(apps=("KM", "PR"), mappers=("aimm",),
                               n_ops=256, seeds=(0, 1), aimm_episodes=2)
    grid += single_program_grid(apps=("KM",), mappers=("none",), n_ops=256,
                                seeds=(0,))
    grid = [dataclasses.replace(sc, lineage=f"lin{i}")
            if sc.mapper == "aimm" else sc for i, sc in enumerate(grid)]

    def chain():
        r1 = sweep.run_grid(grid)
        return r1, sweep.run_grid(grid, store=r1.store)

    monkeypatch.setenv(sweep.LAND_KNOB, "sync")
    monkeypatch.setenv(sweep.STAGING_KNOB, "off")
    a1, a2 = chain()
    monkeypatch.setenv(sweep.LAND_KNOB, "async")
    monkeypatch.setenv(sweep.STAGING_KNOB, "on")
    b1, b2 = chain()
    assert _metrics_equal(a1, b1) and _metrics_equal(a2, b2)
    sa, sb = a2.store, b2.store
    assert sa.tags == sb.tags
    for tag in sa.tags:
        for x, y in zip(jax.tree.leaves(sa.get(tag)),
                        jax.tree.leaves(sb.get(tag))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
