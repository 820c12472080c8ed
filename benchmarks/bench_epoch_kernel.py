"""Fused-epoch-kernel + async-pipeline benchmark: the PR 9 execute-layer
changes (fused epoch backend, async result landing, host-side agent
staging) against an emulated PR 8 configuration on the same grid.

Protocol (interleaved A/B, min of warm reps — benchmarks/common.py):

  A (PR 8 emulation): REPRO_SWEEP_LAND=sync, REPRO_STORE_STAGING=off,
     REPRO_EPOCH_BACKEND=jnp — synchronous group landing, per-cell device
     cold_start + jnp.stack agent batches, unfused jnp epoch stages.
  B (new defaults):   async landing (group k's host fetch/unfold overlaps
     group k+1's device step), preallocated numpy staging buffers with a
     cached cold-cell snapshot per (seed, agent_cfg), REPRO_EPOCH_BACKEND
     auto.

The grid is shaped to stress exactly what changed: lineage-tagged AIMM
lanes (agent staging + store write-backs on the landing path) across
several topologies plus a ragged baseline group (>= 4 compiled groups, so
async landing has device work to hide behind).  `auto` resolves the epoch
backend to the jnp path on every platform, so the A/B improvement here
measures the pipelining + staging work; the fused Pallas kernel is
recorded separately as *parity rows* (interpret-mode wall time +
bit-identity vs jnp) with no speedup claim — the TPU compiler refuses the
kernel (kernels/epoch_fused/kernel.py).

Also recorded: a store-stacking microbench (`_warm_agent_batch` on a
prewarmed store, staging buffers vs historical per-cell device stacking)
and a serial spot check.  Record lands in
``bench_out/BENCH_epoch_kernel.json`` (schema: benchmarks/README.md).
"""
from __future__ import annotations

import json
import os

from benchmarks.common import (FULL, ab_compare, emit, env_overrides,
                               metrics_equal, min_warm)

JSON_PATH = os.environ.get("BENCH_EPOCH_KERNEL_JSON",
                           "bench_out/BENCH_epoch_kernel.json")

APPS = ("KM", "PR", "SPMV") if FULL else ("KM", "PR")
TOPOLOGIES = ("mesh2d", "torus2d", "ring")
SEEDS = 8 if FULL else 4
N_OPS = 1024 if FULL else 512
EPISODES = 2
REPS = 7 if FULL else 5
TARGET_IMPROVEMENT = 1.15

# PR 8 execute layer emulated on today's engine: every knob the PR 9
# execute-layer work introduced, pinned to its historical behaviour.
ENV_BASELINE = {"REPRO_SWEEP_LAND": "sync", "REPRO_STORE_STAGING": "off",
                "REPRO_EPOCH_BACKEND": "jnp"}
ENV_NEW = {"REPRO_SWEEP_LAND": None, "REPRO_STORE_STAGING": None,
           "REPRO_EPOCH_BACKEND": None}


def _grid():
    """Lineage-heavy multi-group grid: one lineage-tagged AIMM cell per
    (app, topology) with a folded seed axis, plus a ragged S=1 baseline
    group per topology.  Topology variety splits the plan into one compiled
    program per (topology, agent-mode) group — the async landing path needs
    multiple groups to overlap."""
    from repro.nmp.scenarios import Scenario, seed_variants
    from repro.nmp.traces import make_trace

    grid = []
    traces = {app: make_trace(app, n_ops=N_OPS) for app in APPS}
    for topo in TOPOLOGIES:
        for app in APPS:
            grid += seed_variants(
                Scenario(name=f"{app}/{topo}/aimm", trace=traces[app],
                         mapper="aimm", episodes=EPISODES,
                         lineage=f"{app}-{topo}", topology=topo),
                tuple(range(SEEDS)))
        grid.append(Scenario(name=f"{APPS[0]}/{topo}/none",
                             trace=traces[APPS[0]], mapper="none",
                             topology=topo))
    return grid


def run():
    from repro.nmp import NMPConfig, partition
    from repro.nmp import sweep as sweep_mod
    from repro.nmp.engine import default_agent_cfg
    from repro.nmp.sweep import run_grid, run_grid_serial

    cfg = NMPConfig()
    grid = _grid()

    # -- main A/B: PR 8 emulation vs new defaults -----------------------
    ab = ab_compare(lambda: run_grid(grid), lambda: run_grid(grid),
                    reps=REPS, env_a=ENV_BASELINE, env_b=ENV_NEW)
    res_base, res_new = ab["last_a"], ab["last_b"]
    bit_identical = metrics_equal(res_base, res_new)
    improvement = ab["improvement"]

    # serial spot check: strided subset covering both mapper kinds
    idxs = sorted(set(list(range(0, len(grid), max(1, len(grid) // 6)))[:6]
                      + [len(grid) - 1]))
    serial = run_grid_serial([grid[i] for i in idxs])
    mismatches = sum(
        1 for j, i in enumerate(idxs)
        if serial[j]["cycles"] != res_new.episode_summary(i)["cycles"])

    # -- fused-kernel parity rows (interpret mode; no speedup claim) ----
    # A small sub-grid keeps the interpret-mode emulator affordable; each
    # backend is timed resident (min-of-warm) and checked bit-identical
    # against the jnp reference path.
    sub = [sc for sc in grid if sc.topology == TOPOLOGIES[0]
           and (sc.mapper == "none" or sc.seed < 2)]
    backends = {}
    ref = None
    for backend in ("jnp", "pallas_interpret"):
        with env_overrides(REPRO_EPOCH_BACKEND=backend, **{
                k: v for k, v in ENV_NEW.items()
                if k != "REPRO_EPOCH_BACKEND"}):
            res = run_grid(sub)
            warm_s, _ = min_warm(lambda: run_grid(sub), 3)
        row = {"warm_s": round(warm_s, 4)}
        if ref is None:
            ref = res
        else:
            row["bit_identical_vs_jnp"] = metrics_equal(ref, res)
        backends[backend] = row
        emit(f"epoch_kernel/backend_{backend}/warm_s", warm_s * 1e6,
             round(warm_s, 4))

    # -- store-stacking microbench --------------------------------------
    # `_warm_agent_batch` on a prewarmed store + the largest lineage group:
    # persistent staging buffers (checkout_host + in-place rows + one
    # device transfer per leaf) vs the historical per-cell device path
    # (checkout import + jnp.stack).  Both produce bit-identical batches
    # (tests/test_pallas_parity.py); only the host cost differs.
    import jax
    store = res_new.store
    group = max((g for g in res_new.plan.groups if g.lineage),
                key=lambda g: g.n_lanes * g.n_seeds)
    agent_cfg = default_agent_cfg(cfg)
    mesh = partition.build_mesh()
    staging = sweep_mod.AgentStaging()

    def stack_staged():
        jax.block_until_ready(sweep_mod._warm_agent_batch(
            group, group.n_lanes, store, agent_cfg, mesh=mesh,
            staging=staging))

    def stack_historical():
        with env_overrides(REPRO_STORE_STAGING="off"):
            jax.block_until_ready(sweep_mod._warm_agent_batch(
                group, group.n_lanes, store, agent_cfg, mesh=mesh))

    stack_staged(); stack_historical()        # warm both paths
    staged_s, _ = min_warm(stack_staged, REPS)
    hist_s, _ = min_warm(stack_historical, REPS)
    stack_improvement = hist_s / staged_s if staged_s else float("inf")

    cells = group.n_lanes * group.n_seeds
    tag = f"epoch_kernel/cells{len(grid)}_s{SEEDS}"
    emit(f"{tag}/warm_baseline_s", ab["a_s"] * 1e6, round(ab["a_s"], 3))
    emit(f"{tag}/warm_new_s", ab["b_s"] * 1e6, round(ab["b_s"], 3))
    emit(f"{tag}/improvement_vs_pr8", ab["b_s"] * 1e6,
         round(improvement, 3))
    emit(f"{tag}/bit_identical", ab["b_s"] * 1e6, bit_identical)
    emit(f"{tag}/metric_mismatches_vs_serial", ab["b_s"] * 1e6, mismatches)
    emit(f"{tag}/stacking_improvement", staged_s * 1e6,
         round(stack_improvement, 3))

    record = {
        "grid": {"cells": len(grid), "apps": list(APPS),
                 "topologies": list(TOPOLOGIES), "seeds": SEEDS,
                 "n_ops": N_OPS, "aimm_episodes": EPISODES, "full": FULL,
                 "groups": [(g.n_lanes, g.n_seeds, g.n_episodes)
                            for g in res_new.plan.groups]},
        "mesh": partition.mesh_desc(partition.build_mesh()),
        "ab": {
            "env_baseline": ENV_BASELINE,
            "env_new": {k: "<default>" for k in ENV_NEW},
            "reps": REPS,
            "warm_baseline_s": round(ab["a_s"], 4),
            "warm_new_s": round(ab["b_s"], 4),
            "warm_baseline_all": [round(w, 4) for w in ab["a_all"]],
            "warm_new_all": [round(w, 4) for w in ab["b_all"]],
            "improvement_vs_pr8": round(improvement, 3),
            "target_improvement": TARGET_IMPROVEMENT,
            "met_target": bool(improvement >= TARGET_IMPROVEMENT),
            "bit_identical": bool(bit_identical),
        },
        "serial_spot": {"lanes_checked": len(idxs),
                        "metric_mismatches": mismatches},
        "backends": backends,
        "store_stacking": {"cells": cells,
                           "staging_s": round(staged_s, 5),
                           "historical_s": round(hist_s, 5),
                           "improvement": round(stack_improvement, 3)},
    }
    os.makedirs(os.path.dirname(JSON_PATH) or ".", exist_ok=True)
    with open(JSON_PATH, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(f"# wrote {JSON_PATH}", flush=True)


if __name__ == "__main__":
    run()
