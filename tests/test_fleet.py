"""Fleet-scale execution: 2-D (lanes x seeds) mesh equivalence, seed-
invariant work sharing, shard packing, and the jax.distributed scaffolding.

The load-bearing invariant: per-(lane, seed) work never crosses a device
and the only collectives are scalar any-lane cond gates, so EVERY mesh
shape — 1 device, 4x1, 2x2, 1x4, auto-factored — and both settings of
REPRO_SEED_SHARE produce bit-identical SweepResult metrics and variance
bands, including when the seed axis needs padding (S=3 on a 2- or 4-wide
seed dim repeats slot 0, whose outputs are never read back).
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.nmp import NMPConfig, make_trace, partition
from repro.nmp import plan as plan_mod
from repro.nmp.scenarios import Scenario, seed_variants

CFG = NMPConfig()


# ---------------------------------------------------------------------------
# Shard packing (plan layer, in-process)
# ---------------------------------------------------------------------------

def _mixed_plan():
    grid = []
    tr = make_trace("KM", n_ops=256)
    grid += seed_variants(Scenario(name="KM/aimm", trace=tr, mapper="aimm",
                                   episodes=2), seeds=(0, 1, 2))
    tr2 = make_trace("RBM", n_ops=256)
    grid += [Scenario(name="RBM/none", trace=tr2, mapper="none")]
    return plan_mod.plan_grid(grid, CFG)


def test_packed_order_and_padding_waste():
    plan = _mixed_plan()
    # declaration order (test-pinned elsewhere) is untouched; only the
    # execution order is packed, heaviest padded cost first
    order = plan_mod.packed_group_order(plan, lane_dim=2, seed_dim=2)
    assert sorted(order) == list(range(len(plan.groups)))
    costs = [plan_mod.group_padded_cells(plan.groups[i], 2, 2)
             for i in order]
    assert costs == sorted(costs, reverse=True)
    # waste is a ratio in [0, 1): zero without a mesh, positive when a
    # 4-wide lane dim pads the 1-lane groups
    assert plan_mod.padding_waste(plan) == 0.0
    assert 0.0 < plan_mod.padding_waste(plan, lane_dim=4, seed_dim=1) < 1.0
    # lanes inside each group are cost-ordered (heaviest first)
    for g in plan.groups:
        c = [plan_mod.lane_cost(ln) for ln in g.lanes]
        assert c == sorted(c, reverse=True)


def test_seed_share_env_validation(monkeypatch):
    for raw, want in (("", True), ("on", True), ("1", True),
                      ("off", False), ("0", False)):
        monkeypatch.setenv("REPRO_SEED_SHARE", raw)
        assert plan_mod.seed_share_enabled() is want
    monkeypatch.setenv("REPRO_SEED_SHARE", "maybe")
    with pytest.raises(ValueError, match="REPRO_SEED_SHARE"):
        plan_mod.seed_share_enabled()


# ---------------------------------------------------------------------------
# Seed-invariant work sharing (in-process, single device)
# ---------------------------------------------------------------------------

def test_seed_share_on_off_bit_identical(monkeypatch):
    """Hoisting the trace-derived per-epoch work out of the seed vmap must
    not change a single bit of any seed's metrics."""
    from repro.nmp.sweep import run_grid
    tr = make_trace("KM", n_ops=192)
    grid = seed_variants(Scenario(name="KM/aimm", trace=tr, mapper="aimm",
                                  episodes=2), seeds=(0, 1))
    monkeypatch.setenv("REPRO_SEED_SHARE", "off")
    r_off = run_grid(grid, CFG)
    monkeypatch.setenv("REPRO_SEED_SHARE", "on")
    r_on = run_grid(grid, CFG)
    assert not r_off.plan.groups[0].flags.share_seed_inv
    assert r_on.plan.groups[0].flags.share_seed_inv
    for k in sorted(r_off.metrics):
        np.testing.assert_array_equal(r_off.metrics[k], r_on.metrics[k],
                                      err_msg=k)
    assert r_off.variance_band(0) == r_on.variance_band(0)


# ---------------------------------------------------------------------------
# 2-D mesh equivalence (forced 4-device host platform, subprocess)
# ---------------------------------------------------------------------------

_MESH_SCRIPT = textwrap.dedent("""
    import os
    import numpy as np
    import jax
    assert jax.device_count() == 4, jax.devices()

    from repro.nmp import NMPConfig, make_trace
    from repro.nmp.scenarios import Scenario, seed_variants
    from repro.nmp.sweep import run_grid

    cfg = NMPConfig()
    grid = []
    for app in ("KM", "PR"):
        tr = make_trace(app, n_ops=256)
        # S=3 does NOT divide the 2- or 4-wide seed dims -> seed padding
        grid += seed_variants(
            Scenario(name=f"{app}/aimm", trace=tr, mapper="aimm",
                     episodes=2), seeds=(0, 1, 2))
        grid += [Scenario(name=f"{app}/none", trace=tr, mapper="none")]

    def run(env):
        for k in ("REPRO_SWEEP_DEVICES", "REPRO_SWEEP_MESH"):
            os.environ.pop(k, None)
        os.environ.update(env)
        return run_grid(grid, cfg)

    ref = run({"REPRO_SWEEP_DEVICES": "1"})
    assert (ref.n_devices, ref.mesh_shape) == (1, (1, 1))
    runs = {"4x1": run({"REPRO_SWEEP_MESH": "4x1"}),
            "2x2": run({"REPRO_SWEEP_MESH": "2x2"}),
            "1x4": run({"REPRO_SWEEP_MESH": "1x4"}),
            "auto": run({})}
    for name, r in runs.items():
        assert r.n_devices == 4, (name, r.n_devices)
        if name != "auto":
            assert r.mesh_shape == tuple(
                int(x) for x in name.split("x")), (name, r.mesh_shape)
        for k in sorted(ref.metrics):
            np.testing.assert_array_equal(ref.metrics[k], r.metrics[k],
                                          err_msg=f"{name}:{k}")
        for lane in range(len(grid)):
            assert ref.variance_band(lane) == r.variance_band(lane), (
                name, lane)
    print("MESH-OK", runs["auto"].mesh_shape)
""")


@pytest.mark.slow
def test_mesh_shapes_bit_identical_on_forced_host_devices():
    env = dict(
        os.environ,
        XLA_FLAGS=("--xla_force_host_platform_device_count=4 "
                   + os.environ.get("XLA_FLAGS", "")),
        JAX_PLATFORMS="cpu",
    )
    for k in ("REPRO_SWEEP_DEVICES", "REPRO_SWEEP_MESH", "REPRO_SEED_SHARE"):
        env.pop(k, None)
    proc = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MESH-OK" in proc.stdout


# ---------------------------------------------------------------------------
# jax.distributed scaffolding (2 local processes, subprocess)
# ---------------------------------------------------------------------------

def test_distributed_disabled_is_single_host(monkeypatch):
    monkeypatch.delenv("REPRO_DIST_COORD", raising=False)
    assert partition.maybe_init_distributed() is False
    # coord set without the group size/rank is a config error, named
    monkeypatch.setenv("REPRO_DIST_COORD", "127.0.0.1:9999")
    monkeypatch.delenv("REPRO_DIST_NPROCS", raising=False)
    monkeypatch.delenv("REPRO_DIST_RANK", raising=False)
    with pytest.raises(ValueError, match="REPRO_DIST_NPROCS"):
        partition.maybe_init_distributed()
    monkeypatch.setenv("REPRO_DIST_NPROCS", "two")
    monkeypatch.setenv("REPRO_DIST_RANK", "0")
    with pytest.raises(ValueError, match="must be integers"):
        partition.maybe_init_distributed()


_DIST_SCRIPT = textwrap.dedent("""
    import sys
    from repro.nmp import partition
    assert partition.maybe_init_distributed() is True
    assert partition.maybe_init_distributed() is True   # idempotent
    import jax
    assert jax.process_count() == 2, jax.process_count()
    # each process contributes its 2 forced host devices to the global mesh
    assert jax.device_count() == 4, jax.device_count()
    assert jax.local_device_count() == 2
    devs = partition.sweep_devices()
    assert len(devs) == 4
    print(f"rank{jax.process_index()} DIST-OK", flush=True)
""")


@pytest.mark.slow
def test_distributed_init_two_local_processes(tmp_path):
    """Two local processes join one jax.distributed group and see a 4-device
    global platform (2 forced host devices each).  No cross-process
    computation is dispatched, so this exercises exactly what the
    scaffolding claims: process-group init, global device visibility, and
    graceful single-host degradation when the knobs are unset."""
    base = dict(
        os.environ,
        XLA_FLAGS=("--xla_force_host_platform_device_count=2 "
                   + os.environ.get("XLA_FLAGS", "")),
        JAX_PLATFORMS="cpu",
        REPRO_DIST_COORD="127.0.0.1:19731",
        REPRO_DIST_NPROCS="2",
    )
    for k in ("REPRO_SWEEP_DEVICES", "REPRO_SWEEP_MESH", "REPRO_DIST_RANK"):
        base.pop(k, None)
    procs = [subprocess.Popen([sys.executable, "-c", _DIST_SCRIPT],
                              env=dict(base, REPRO_DIST_RANK=str(r)),
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for r in range(2)]
    outs = [p.communicate(timeout=300) for p in procs]
    for r, (p, (out, err)) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank{r}: {err[-3000:]}"
        assert f"rank{r} DIST-OK" in out
