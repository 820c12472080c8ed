#!/usr/bin/env python3
"""Smoke run of the sweep engine and the mapping service on a TPU.

    python chip_smoke.py             # one chip: grid, exactness, service
    python chip_smoke.py --chips 4   # four chips: the sharded grid and the
                                     # service, each against one device

Everything runs in this one process through the public API (`run_grid`,
`run_episode`, `MappingServer`, `run_stream`).  The script refuses to run
anywhere but a TPU.  Each phase prints `<phase>: key=value ...` lines;
times are wall-clock on the host around work that ends on the host, from a
smoke run, not a benchmark.  Any failed check exits non-zero; on success
the last line is the JSON device record.

Phases (one chip):
  grid       the paper's Fig. 6 grid (9 apps x {bnmp, ldb, pei} x {none,
             tom, aimm}, 16384-op traces, 5 AIMM episodes: 81 lanes of
             128 epochs per episode) run cold and warm
  exactness  every engine golden (tests/test_engine_golden.py) rerun with
             run_episode and compared bit for bit; one none, one tom and
             three aimm lanes of the grid against run_grid_serial; every
             metric of every learned lane finite
  service    32 tenants x 3 phases through a 16-slot MappingServer until
             drained; no recompiles after the first tick; one tenant
             bit-identical to its solo stream
Phase (four chips): the grid with seeds (0, 1, 2) on all devices against
the same grid on one device, lane placement on every device, and the
service on all devices against one tenant's solo stream.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Paper-scale sizes (paper Table 1 / Fig. 6 protocol).
GRID_N_OPS = 16384
GRID_AIMM_EPISODES = 5
SERIAL_LANES = ("KM/bnmp/none/s0", "SPMV/pei/tom/s0", "PR/ldb/aimm/s0",
                "KM/pei/aimm/s0", "SPMV/bnmp/aimm/s0")
FLEET = dict(n_tenants=32, n_phases=3, n_ops_per_app=1024)
N_SLOTS = 16
STORE_CAPACITY = 32
SPOT_TENANT = "t017"


def require_tpu():
    """The device check comes first: no phase ever runs off the chip."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's first device is "
                 f"{dev.platform!r}); refusing to run")
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke: the repository's src/repro is not next to "
                 f"{Path(__file__).name}; refusing to run")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    return dev


def emit(phase: str, **kv) -> None:
    print(f"{phase}: " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def paper_grid(seeds=(0,)):
    from repro.nmp.scenarios import single_program_grid
    from repro.nmp.traces import APPS
    return single_program_grid(apps=APPS, techniques=("bnmp", "ldb", "pei"),
                               mappers=("none", "tom", "aimm"),
                               n_ops=GRID_N_OPS, seeds=seeds,
                               aimm_episodes=GRID_AIMM_EPISODES)


def timed_grid(grid, cfg):
    from repro.nmp.sweep import run_grid
    t0 = time.perf_counter()
    res = run_grid(grid, cfg)
    return res, time.perf_counter() - t0


def phase_grid(ctx):
    import jax
    import numpy as np
    from benchmarks.common import metrics_equal
    from repro.compile_cache import cache_hits
    cfg = ctx["cfg"]
    grid = paper_grid()
    hits0 = cache_hits()
    res, cold_s = timed_grid(grid, cfg)
    emit("grid", lanes=len(grid), cold_wall_s=f"{cold_s:.3f}",
         compile_cache_hits=cache_hits() - hits0)
    res2, warm_s = timed_grid(grid, cfg)
    assert metrics_equal(res, res2), "warm rerun differs from the cold run"
    epochs = float(np.sum(res.metrics["epochs"]))
    ops = float(np.sum(res.metrics["ops"]))
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    emit("grid", warm_wall_s=f"{warm_s:.3f}", epoch_steps=int(epochs),
         nmp_ops=int(ops), epoch_steps_per_s_warm=f"{epochs / warm_s:.1f}",
         nmp_ops_per_s_warm=f"{ops / warm_s:.1f}", peak_bytes_in_use=peak,
         devices=res.n_devices, note="chip smoke run, not a benchmark")
    ctx["grid"], ctx["grid_res"] = grid, res


def phase_exactness(ctx):
    import numpy as np
    from repro.nmp import make_trace
    from repro.nmp.engine import run_episode
    from repro.nmp.stats import summarize
    from repro.nmp.sweep import run_grid_serial
    from tests.test_engine_golden import GOLDEN
    cfg = ctx["cfg"]
    bad = []
    for key in sorted(GOLDEN):
        app, n_ops, tech, mapper, forced = key
        s = summarize(run_episode(make_trace(app, n_ops=n_ops), cfg, tech,
                                  mapper, seed=2, forced_action=forced))
        got = (s["cycles"], s["ops"], s["opc"])
        if got != GOLDEN[key]:
            bad.append(key)
            emit("exactness", golden_mismatch="/".join(map(str, key)),
                 got=got, want=GOLDEN[key])
    emit("exactness", golden_bit_identical=f"{len(GOLDEN) - len(bad)}"
         f"/{len(GOLDEN)}")

    grid, res = ctx["grid"], ctx["grid_res"]
    names = [sc.name for sc in grid]
    lanes = [names.index(n) for n in SERIAL_LANES]
    serial = run_grid_serial([grid[i] for i in lanes], cfg)
    diff = [n for n, i, s in zip(SERIAL_LANES, lanes, serial)
            if any(s[k] != v for k, v in res.episode_summary(i).items())]
    for n in diff:
        emit("exactness", serial_mismatch=n)
    emit("exactness", serial_lanes_bit_identical=f"{len(lanes) - len(diff)}"
         f"/{len(lanes)}", lanes=",".join(SERIAL_LANES))

    learned = [i for i, sc in enumerate(grid) if sc.mapper == "aimm"]
    nonfinite = [k for k, v in res.metrics.items()
                 if np.issubdtype(v.dtype, np.floating)
                 and not np.isfinite(v[learned]).all()]
    emit("exactness", learned_lanes=len(learned),
         nonfinite_metrics=",".join(nonfinite) or "none")
    assert not bad, f"{len(bad)} golden entries differ"
    assert not diff, f"batched != serial on {diff}"
    assert not nonfinite, f"non-finite learned-lane metrics {nonfinite}"


def run_service(cfg, spot: str):
    """Drain the tenant fleet through one server; returns (stats, spot
    tenant bit-identical to its solo stream)."""
    import numpy as np
    from repro.nmp.continual import run_stream
    from repro.nmp.scenarios import tenant_fleet
    from repro.nmp.serving import MappingServer, solo_stream
    fleet = tenant_fleet(**FLEET)
    srv = MappingServer(cfg, n_slots=N_SLOTS, store_capacity=STORE_CAPACITY)
    for tid, stream in fleet.items():
        srv.submit(tid, stream)
    t0 = time.perf_counter()
    srv.run()
    wall = time.perf_counter() - t0
    stats = srv.stats()
    solo = run_stream(solo_stream(spot, fleet[spot]), cfg)
    identical = all(
        np.array_equal(srv.tenant_metrics(spot, pi)[k], want[k][0])
        for pi in range(FLEET["n_phases"])
        for want in [solo.phases[pi].metrics] for k in want)
    return stats, identical, wall


def check_service(stats, identical, wall, spot):
    emit("service", tenants_done=f"{stats['tenants_done']}"
         f"/{FLEET['n_tenants']}", ticks=stats["ticks"],
         devices=stats["n_devices"], drain_wall_s=f"{wall:.3f}",
         compile_s=f"{stats['compile_s']:.3f}",
         steady_epochs_per_s=stats["steady_epochs_per_sec"],
         phase_latency_p50_s=stats["phase_latency_p50_s"],
         phase_latency_p99_s=stats["phase_latency_p99_s"],
         recompiles_after_first_tick=stats["recompiles_after_first_tick"],
         spot_tenant=spot, spot_bit_identical_to_solo=identical)
    assert stats["tenants_done"] == FLEET["n_tenants"], "fleet not drained"
    assert stats["recompiles_after_first_tick"] == 0, "service recompiled"
    assert identical, f"tenant {spot} differs from its solo stream"


def phase_service(ctx):
    check_service(*run_service(ctx["cfg"], SPOT_TENANT), SPOT_TENANT)


def phase_four_chips(ctx):
    import jax
    from benchmarks.common import env_overrides, metrics_equal
    from repro.nmp import partition, plan as plan_mod, sweep
    cfg = ctx["cfg"]
    n = len(jax.devices())
    assert n == 4, f"--chips 4 needs 4 devices, found {n}"
    grid = paper_grid(seeds=(0, 1, 2))
    res4, wall4 = timed_grid(grid, cfg)
    with env_overrides(REPRO_SWEEP_DEVICES="1"):
        res1, wall1 = timed_grid(grid, cfg)
    same = metrics_equal(res4, res1)

    # Lane placement: dispatch the heaviest group through the same
    # partition/execute calls run_grid makes and read the output shardings.
    plan = plan_mod.plan_grid(grid, cfg)
    devs = partition.sweep_devices()
    mesh = partition.build_mesh(devs, partition.auto_mesh_shape(
        len(devs), [(g.n_lanes, g.n_seeds, g.n_episodes)
                    for g in plan.groups]))
    gi = plan_mod.packed_group_order(plan, partition.mesh_lane_dim(mesh),
                                     partition.mesh_seed_dim(mesh))[0]
    group = plan.groups[gi]
    batch, _ = sweep.prepare_group_batch(plan, group, cfg, mesh)
    s_pad = int(batch["ep_seed"].shape[1])
    out, _, _ = sweep.dispatch_sweep(
        batch, partition.replicate(plan_mod.plan_tom_candidates(plan, cfg),
                                   mesh),
        cfg, ctx["spec"], ctx["agent_cfg"], plan.n_epochs, group.n_episodes,
        plan.ring_len, sweep.executed_flags(group, s_pad))
    cyc = jax.block_until_ready(out["cycles"])
    shard_devs = {s.device.id for s in cyc.addressable_shards}
    shard_idx = {str(s.index) for s in cyc.addressable_shards}
    emit("four_chips", cells=len(grid), lanes=res4.plan.n_lanes,
         mesh=partition.mesh_desc(mesh), run_grid_mesh=res4.mesh_shape,
         run_grid_devices=res4.n_devices, wall_4dev_s=f"{wall4:.3f}",
         wall_1dev_s=f"{wall1:.3f}", bit_identical_to_1dev=same,
         output_sharding=cyc.sharding.spec, shard_devices=sorted(shard_devs),
         distinct_shards=len(shard_idx))
    assert res4.n_devices == 4 and res1.n_devices == 1
    assert same, "4-device grid differs from the 1-device grid"
    assert len(shard_devs) == 4 and len(shard_idx) == 4, \
        "lanes did not land on all four devices"
    check_service(*run_service(cfg, SPOT_TENANT), SPOT_TENANT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every one-chip phase; 4: only the four-chip "
                         "phase")
    args = ap.parse_args()
    dev = require_tpu()

    from repro.compile_cache import cache_hits, enable_compile_cache
    cache_dir = enable_compile_cache()
    entries = (len(list(Path(cache_dir).iterdir()))
               if Path(cache_dir).is_dir() else 0)
    emit("setup", device_kind=repr(dev.device_kind), compile_cache=cache_dir,
         cache_entries_at_start=entries,
         epoch_backend=os.environ.get("REPRO_EPOCH_BACKEND", "auto"))

    import jax
    from repro.configs.aimm_nmp import PAPER_4X4
    from repro.kernels.epoch_fused import resolve_backend
    from repro.nmp.engine import default_agent_cfg, state_spec_for
    assert resolve_backend() == "jnp", "the chip default epoch core is jnp"
    ctx = {"cfg": PAPER_4X4, "spec": state_spec_for(PAPER_4X4),
           "agent_cfg": default_agent_cfg(PAPER_4X4)}

    phases = ([("four_chips", phase_four_chips)] if args.chips == 4 else
              [("grid", phase_grid), ("exactness", phase_exactness),
               ("service", phase_service)])
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn(ctx)
            status = "passed"
        except Exception:
            traceback.print_exc()
            failed.append(name)
            status = "FAILED"
        emit(name, status=status, phase_wall_s=f"{time.perf_counter() - t0:.3f}")
        if name == "grid" and status != "passed":
            break                    # the exactness phase reuses the grid
    emit("summary", failed=",".join(failed) or "none",
         compile_cache_hits=cache_hits())
    if failed:
        return 1
    devices = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
