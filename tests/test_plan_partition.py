"""Plan + partition layers of the sweep pipeline.

Unit tests cover seed folding / lane grouping / padding arithmetic directly;
the multi-device path (lane-axis `NamedSharding` over a forced 4-device host
platform, including non-divisible lane-count padding) runs in a subprocess
because `XLA_FLAGS=--xla_force_host_platform_device_count=4` must be set
before jax initializes.  The same path runs in-process for the whole suite
on the CI job that exports that flag globally (see .github/workflows/ci.yml).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.nmp import NMPConfig, make_trace
from repro.nmp import engine, partition
from repro.nmp import plan as plan_mod
from repro.nmp.plan import build_group_batch, plan_grid
from repro.nmp.scenarios import Scenario, seed_variants

CFG = NMPConfig()


def _mixed_grid():
    grid = []
    for app, n_ops in (("KM", 384), ("RBM", 512)):
        tr = make_trace(app, n_ops=n_ops)
        for mapper in ("none", "tom"):
            grid += seed_variants(Scenario(name=f"{app}/{mapper}", trace=tr,
                                           mapper=mapper), seeds=(0, 1, 2))
    tr = make_trace("MAC", n_ops=384)
    grid += seed_variants(Scenario(name="MAC/aimm", trace=tr, mapper="aimm",
                                   episodes=2), seeds=(0, 1))
    return grid


# ---------------------------------------------------------------------------
# Plan layer
# ---------------------------------------------------------------------------

def test_plan_folds_seeds_and_groups_lanes():
    grid = _mixed_grid()
    plan = plan_grid(grid, CFG)
    assert len(plan.groups) == 2
    agent, det = plan.groups
    assert agent.has_agent and not det.has_agent
    assert (agent.n_lanes, agent.n_seeds) == (1, 2)
    # 12 deterministic cells fold 3-to-1 AND collapse their seed axis: the
    # deterministic mappers are seed-invariant, so one simulated cell per
    # lane serves all three replicas
    assert (det.n_lanes, det.n_seeds) == (4, 1)
    assert all(ln.slots == (0, 0, 0) for ln in det.lanes)
    assert det.flags.any_tom and not det.flags.has_agent
    # the index map covers every scenario exactly once
    seen = sorted(i for g in plan.groups for ln in g.lanes
                  for i in ln.indices)
    assert seen == list(range(len(grid)))
    assert plan.seed_group(1) == (0, 1, 2)
    # envelope: padded to the largest trace / longest schedule
    assert plan.n_ops_max == 512 and plan.n_episodes == 2


def test_plan_pads_ragged_seed_axes():
    """Seed-variant lanes with different seed counts share one group: the
    narrow lane's seed axis is padded by re-simulating its first seed."""
    tr = make_trace("KM", n_ops=384)
    grid = (seed_variants(Scenario(name="a", trace=tr, mapper="aimm",
                                   forced_action=1), seeds=(0, 1, 2))
            + [Scenario(name="b", trace=tr, mapper="aimm", forced_action=3,
                        seed=7)])
    plan = plan_grid(grid, CFG)
    (group,) = plan.groups
    assert group.n_seeds == 3
    narrow = group.lanes[1]
    assert narrow.seeds == (7, 7, 7) and narrow.indices == (3,)
    assert narrow.slots == (0,)
    batch = build_group_batch(plan, group, CFG)
    assert batch["ep_seed"].shape == (2, 3, 1)
    assert (batch["ep_seed"][1, :, 0] == 7).all()


def test_distinct_trace_objects_do_not_fold():
    """Folding keys on Trace object identity: equal-seed scenarios over
    different traces stay separate lanes."""
    grid = [Scenario(name="a", trace=make_trace("KM", n_ops=384)),
            Scenario(name="b", trace=make_trace("KM", n_ops=384))]
    plan = plan_grid(grid, CFG)
    assert plan.n_lanes == 2


def test_plan_groups_warm_lineage_lanes_apart_from_cold():
    """Lineage (warm-capable) agent lanes compile separately from plain
    cold-start agent lanes: cold group first (the exact historical program),
    then the lineage group, then deterministic lanes — and GridPlan records
    the per-scenario lineage map."""
    tr = make_trace("KM", n_ops=384)
    grid = [
        Scenario(name="cold", trace=tr, mapper="aimm"),
        Scenario(name="warm", trace=tr, mapper="aimm", lineage="tagA"),
        Scenario(name="det", trace=tr, mapper="tom"),
        Scenario(name="warm2", trace=tr, mapper="aimm", lineage="tagB",
                 seed=1),
    ]
    plan = plan_grid(grid, CFG)
    assert [(g.has_agent, g.lineage, g.n_lanes) for g in plan.groups] == [
        (True, False, 1), (True, True, 2), (False, False, 1)]
    assert plan.agent_lineage == (None, "tagA", None, "tagB")
    assert plan.lineage_tags() == ("tagA", "tagB")
    # lineage is part of the fold key: same trace/seed, different tag => no fold
    assert all(len(ln.indices) == 1 for g in plan.groups for ln in g.lanes)


def test_plan_lineage_on_non_agent_lane_is_inert():
    """A lineage tag on a deterministic or scripted lane carries no agent:
    the plan normalizes it away instead of spawning a warm group."""
    tr = make_trace("KM", n_ops=384)
    grid = [Scenario(name="det", trace=tr, mapper="tom", lineage="t"),
            Scenario(name="scripted", trace=tr, mapper="aimm",
                     forced_action=1, lineage="t")]
    plan = plan_grid(grid, CFG)
    assert all(not g.lineage for g in plan.groups)
    assert plan.agent_lineage == (None, None)
    assert plan.lineage_tags() == ()


def test_plan_lineage_seed_variants_fold_into_one_warm_lane():
    """Seed replicas of one lineage-tagged cell still fold onto the seed
    axis (they share the tag and the fold key)."""
    tr = make_trace("KM", n_ops=384)
    grid = seed_variants(Scenario(name="w", trace=tr, mapper="aimm",
                                  lineage="t"), seeds=(0, 1, 2))
    plan = plan_grid(grid, CFG)
    (group,) = plan.groups
    assert group.lineage and group.n_lanes == 1 and group.n_seeds == 3


def test_plan_rejects_invalid_lineage_tags_at_plan_time():
    """A malformed tag must fail before anything compiles or simulates, not
    in the post-run store write-back."""
    tr = make_trace("KM", n_ops=384)
    for bad in ("", "a/b"):
        with pytest.raises(ValueError, match="lineage tag"):
            plan_grid([Scenario(name="x", trace=tr, mapper="aimm",
                                lineage=bad)], CFG)


def test_plan_rejects_ragged_lineage_episode_counts():
    """Padding episodes would over-train a lineage's agent past its schedule;
    ragged lineage groups must be refused, not silently padded."""
    tr = make_trace("KM", n_ops=384)
    grid = [Scenario(name="a", trace=tr, mapper="aimm", lineage="t",
                     episodes=1),
            Scenario(name="b", trace=tr, mapper="aimm", lineage="u",
                     episodes=3)]
    with pytest.raises(ValueError, match="episode count"):
        plan_grid(grid, CFG)
    # cold lanes keep the historical pad-to-max behavior
    cold = [Scenario(name="a", trace=tr, mapper="aimm", episodes=1),
            Scenario(name="b", trace=tr, mapper="aimm", episodes=3)]
    assert plan_grid(cold, CFG).groups[0].n_episodes == 3


def test_empty_grid_raises_clear_error():
    """`run_grid([])` historically died with a bare IndexError deep in the
    plan layer; an empty grid (or an empty stream phase) must fail at
    `plan_grid` with an actionable message instead."""
    from repro.nmp.continual import run_stream
    from repro.nmp.plan import plan_envelope
    from repro.nmp.sweep import run_grid
    with pytest.raises(ValueError, match="empty scenario grid"):
        plan_grid([], CFG)
    with pytest.raises(ValueError, match="empty scenario grid"):
        run_grid([], CFG)
    with pytest.raises(ValueError, match="empty scenario grid"):
        run_stream([[]], CFG)               # a stream with an empty phase
    with pytest.raises(ValueError, match="empty scenario grid"):
        plan_envelope([], CFG)


def test_envelope_dominance_and_forced_plan():
    """A forced envelope must dominate the grid's own; when it does, its
    padded dims replace the derived ones (the serving layer's fixed-shape
    contract) — and episode padding of lineage lanes is still refused."""
    from repro.nmp.plan import Envelope, plan_envelope
    small = make_trace("KM", n_ops=256)
    big = make_trace("KM", n_ops=512)
    need = plan_envelope([Scenario(name="s", trace=small, mapper="aimm")],
                         CFG)
    env = plan_envelope([Scenario(name="b", trace=big, mapper="aimm",
                                  episodes=1)], CFG)
    assert env.dominates(need) and not need.dominates(env)
    forced = plan_grid([Scenario(name="s", trace=small, mapper="aimm")],
                       CFG, envelope=env)
    assert (forced.n_ops_max, forced.n_pages_max) == (env.n_ops_max,
                                                      env.n_pages_max)
    assert forced.n_epochs == env.n_epochs
    assert forced.groups[0].n_episodes == env.n_episodes
    with pytest.raises(ValueError, match="does not cover"):
        plan_grid([Scenario(name="b", trace=big, mapper="aimm")], CFG,
                  envelope=need)
    # a forced envelope must not pad a lineage lane's episode schedule
    wide = dataclasses.replace(env, n_episodes=3)
    with pytest.raises(ValueError, match="past its schedule"):
        plan_grid([Scenario(name="s", trace=small, mapper="aimm",
                            lineage="t", episodes=1)], CFG, envelope=wide)
    # ...but cold lanes simply pad (no agent schedule to corrupt)
    cold = plan_grid([Scenario(name="s", trace=small, mapper="none")], CFG,
                     envelope=wide)
    assert cold.groups[0].n_episodes == 3


# ---------------------------------------------------------------------------
# Batch build
# ---------------------------------------------------------------------------

def _build_grid():
    """Every mapper and technique, a given page table, footprints that need
    page padding (KM 512 pages, RBM 96), seed replicas folded onto one lane,
    an eval episode and a scripted (forced-action) lane."""
    km = make_trace("KM", n_ops=384)
    rbm = make_trace("RBM", n_ops=512)
    grid = [Scenario(name=f"KM/{tech}/{mapper}", trace=km, technique=tech,
                     mapper=mapper)
            for tech in ("bnmp", "ldb", "pei") for mapper in ("none", "tom")]
    pt = (np.arange(rbm.n_pages) * 5 % CFG.n_cubes).astype(np.int32)
    grid.append(Scenario(name="RBM/pt", trace=rbm, technique="ldb",
                         page_table=pt))
    grid += seed_variants(Scenario(name="KM/aimm", trace=km, technique="pei",
                                   mapper="aimm", episodes=2,
                                   eval_episode=True), seeds=(0, 1, 2))
    grid.append(Scenario(name="RBM/forced", trace=rbm, mapper="aimm",
                         forced_action=2, episodes=2))
    return grid


def _device_round_trip_build(monkeypatch, plan, group):
    """The batch built through the device wrappers (`pad_trace_ops`,
    `make_ctx`) read back with `np.asarray`."""
    with monkeypatch.context() as m:
        m.setattr(plan_mod, "pad_trace_ops_host",
                  lambda tr, n, cfg: {k: np.asarray(v) for k, v in
                                      engine.pad_trace_ops(tr, n, cfg).items()})
        m.setattr(plan_mod, "make_ctx_host",
                  lambda *a: jax.tree.map(np.asarray, engine.make_ctx(*a)))
        return build_group_batch(plan, group, CFG)


def test_build_group_batch_makes_no_device_transfer(monkeypatch):
    plan = plan_grid(_build_grid(), CFG)
    assert len(plan.groups) == 2
    assert plan.n_pages_max > min(sc.trace.n_pages for sc in plan.scenarios)
    cache = {}
    with jax.transfer_guard("disallow"):
        for group in plan.groups:
            build_group_batch(plan, group, CFG)
            build_group_batch(plan, group, CFG, host_cache=cache)
            build_group_batch(plan, group, CFG, host_cache=cache)   # hits
        # the guard is live: the device wrappers trip it
        with pytest.raises(Exception, match="Disallowed"):
            _device_round_trip_build(monkeypatch, plan, plan.groups[0])
    assert len(cache) == plan.n_lanes


def test_build_group_batch_bit_identical_to_device_wrappers(monkeypatch):
    plan = plan_grid(_build_grid(), CFG)
    agent, det = plan.groups
    assert agent.n_seeds == 3 and agent.n_episodes == 3    # 2 + eval
    assert any(ln.scenario.forced_action >= 0 for ln in det.lanes)
    for group in plan.groups:
        got = build_group_batch(plan, group, CFG)
        want = _device_round_trip_build(monkeypatch, plan, group)
        assert got.keys() == want.keys()
        for k in want:
            assert type(got[k]) is np.ndarray, k
            assert (got[k].dtype, got[k].shape) == (want[k].dtype,
                                                    want[k].shape), k
            assert got[k].tobytes() == want[k].tobytes(), k
    # the serial runner's device context and trace ops carry the same values
    for sc in plan.scenarios:
        host = engine.make_ctx_host(sc.trace, CFG, sc.technique, sc.mapper,
                                    sc.forced_action, explore=False)
        dev = engine.make_ctx(sc.trace, CFG, sc.technique, sc.mapper,
                              sc.forced_action, explore=False)
        for name, h, d in zip(host._fields, host, dev):
            assert isinstance(h, np.generic), name
            assert (h.dtype, h) == (np.asarray(d).dtype, np.asarray(d)), name
        ops = engine.pad_trace_ops_host(sc.trace, plan.n_ops_max, CFG)
        for k, v in engine.pad_trace_ops(sc.trace, plan.n_ops_max,
                                         CFG).items():
            assert type(ops[k]) is np.ndarray
            assert ops[k].dtype == v.dtype
            np.testing.assert_array_equal(ops[k], np.asarray(v))


# ---------------------------------------------------------------------------
# Partition layer
# ---------------------------------------------------------------------------

def test_single_device_degrades_to_no_mesh():
    assert partition.build_mesh([object()]) is None
    assert partition.mesh_desc(None)["n_devices"] == 1
    assert partition.padded_lane_count(5, None) == 5


def test_pad_group_batch_repeats_lane_zero():
    batch = {"x": np.arange(6).reshape(3, 2), "y": np.arange(3)}
    out = partition.pad_group_batch(batch, 4)
    assert out["x"].shape == (4, 2) and out["y"].shape == (4,)
    np.testing.assert_array_equal(out["x"][3], batch["x"][0])
    same = partition.pad_group_batch(batch, 3)
    assert same["x"].shape == (3, 2)


def test_pad_group_batch_rejects_empty_batch():
    """An empty group batch used to escape as a bare StopIteration from
    `next(iter(...))` (which a surrounding generator would silently swallow
    as exhaustion); it must be a clear ValueError."""
    with pytest.raises(ValueError, match="empty group batch"):
        partition.pad_group_batch({}, 4)


def test_sweep_devices_env_validation(monkeypatch):
    monkeypatch.setenv("REPRO_SWEEP_DEVICES", "banana")
    with pytest.raises(ValueError, match="REPRO_SWEEP_DEVICES"):
        partition.sweep_devices()
    monkeypatch.setenv("REPRO_SWEEP_DEVICES", "0")
    with pytest.raises(ValueError, match="outside"):
        partition.sweep_devices()
    monkeypatch.setenv("REPRO_SWEEP_DEVICES", "99")
    with pytest.raises(ValueError, match="outside"):
        partition.sweep_devices()
    monkeypatch.setenv("REPRO_SWEEP_DEVICES", "all")
    assert len(partition.sweep_devices()) >= 1


def test_sweep_mesh_env_validation(monkeypatch):
    """REPRO_SWEEP_MESH misuse must raise a ValueError naming the knob, the
    value, and the devices — never an opaque mesh-construction error."""
    for bad in ("banana", "2x2x2", "4", "0x4", "2x-2"):
        monkeypatch.setenv("REPRO_SWEEP_MESH", bad)
        with pytest.raises(ValueError, match="REPRO_SWEEP_MESH"):
            partition.sweep_mesh_shape(4)
    # a shape that doesn't factor the selected device count
    monkeypatch.setenv("REPRO_SWEEP_MESH", "3x2")
    with pytest.raises(ValueError) as ei:
        partition.sweep_mesh_shape(4)
    msg = str(ei.value)
    assert "REPRO_SWEEP_MESH" in msg and "3x2" in msg
    assert "6 devices" in msg and "4 device(s)" in msg
    # valid shapes parse; ""/"auto" defer to auto-factoring
    monkeypatch.setenv("REPRO_SWEEP_MESH", "2x2")
    assert partition.sweep_mesh_shape(4) == (2, 2)
    for auto in ("", "auto"):
        monkeypatch.setenv("REPRO_SWEEP_MESH", auto)
        assert partition.sweep_mesh_shape(4) is None


def test_auto_mesh_shape_minimizes_padded_cells():
    # all-S=1 plans keep the historical 1-D lane mesh
    assert partition.auto_mesh_shape(4, [(8, 1, 2)]) == (4, 1)
    # a seed-wide 2-lane group wants the seed axis sharded
    assert partition.auto_mesh_shape(4, [(2, 8, 2)]) in ((2, 2), (1, 4))
    assert partition.auto_mesh_shape(4, [(2, 8, 2), (2, 1, 1)]) == (2, 2)
    assert partition.auto_mesh_shape(1, [(3, 2, 1)]) == (1, 1)


# ---------------------------------------------------------------------------
# Sharded execution (forced 4-device host platform, subprocess)
# ---------------------------------------------------------------------------

_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    import numpy as np
    import jax
    assert jax.device_count() == 4, jax.devices()

    from repro.nmp import NMPConfig, make_trace
    from repro.nmp.scenarios import Scenario, seed_variants
    from repro.nmp.sweep import run_grid

    cfg = NMPConfig()
    grid = []
    for app, n_ops in (("KM", 256), ("RBM", 384)):
        tr = make_trace(app, n_ops=n_ops)
        for mapper in ("none", "tom"):
            grid += seed_variants(
                Scenario(name=f"{app}/{mapper}", trace=tr, mapper=mapper),
                seeds=(0, 1, 2))
    tr = make_trace("MAC", n_ops=256)
    grid += seed_variants(
        Scenario(name="MAC/forced", trace=tr, mapper="aimm",
                 forced_action=1), seeds=(0, 1, 2))

    os.environ["REPRO_SWEEP_DEVICES"] = "1"
    r1 = run_grid(grid, cfg)
    os.environ["REPRO_SWEEP_DEVICES"] = "4"
    r4 = run_grid(grid, cfg)
    assert (r1.n_devices, r4.n_devices) == (1, 4)
    # 5 folded lanes shard over 4 devices only after padding to 8
    assert r4.plan.n_lanes == 5
    for k in sorted(r1.metrics):
        np.testing.assert_array_equal(r1.metrics[k], r4.metrics[k], err_msg=k)
    print("SHARDED-OK")
""")


@pytest.mark.slow
def test_sharded_grid_bit_identical_on_forced_host_devices():
    """The same grid, single-device vs sharded over 4 forced host devices:
    per-cell metrics must match bit-for-bit (per-lane work never crosses a
    device; the only collectives are the boolean any-lane cond gates), with
    the 5-lane group padded up to the device-divisible 8."""
    env = dict(
        os.environ,
        XLA_FLAGS=("--xla_force_host_platform_device_count=4 "
                   + os.environ.get("XLA_FLAGS", "")),
        JAX_PLATFORMS="cpu",
    )
    env.pop("REPRO_SWEEP_DEVICES", None)
    proc = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED-OK" in proc.stdout
