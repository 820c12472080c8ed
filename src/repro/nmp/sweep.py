"""Execute layer of the sweep pipeline: one compiled program per lane group.

`run_grid` is a three-layer pipeline:

  plan      (nmp.plan)      : normalize scenarios into a declarative
                              `GridPlan` — shared padding envelope, lanes
                              grouped by DQN-liveness and cube topology
                              (one program per topology group; the routing
                              tensors are trace-time constants), seeds
                              folded into a per-lane seed axis, lanes
                              cost-ordered for shard packing;
  partition (nmp.partition) : build a 2-D (lanes × seeds) device mesh —
                              shape auto-factored from the plan's padded
                              cell counts (`auto_mesh_shape`) or forced via
                              REPRO_SWEEP_MESH — pad each group to
                              mesh-divisible lane/seed counts and shard both
                              axes (`NamedSharding`); degrades to a plain
                              transfer on one device;
  execute   (this module)   : jit one program per lane group — episode
                              chaining as `lax.scan`, the epoch scan outside
                              the lane vmap, and the folded seed axis as an
                              inner vmap, so S seed replicas of a lane share
                              one copy of its trace arrays and every lane
                              reports mean±std variance bands for free.
                              Groups are *dispatched* heaviest-first
                              (`plan.packed_group_order`) with the next
                              group's host batch built while the previous
                              one runs on device, and the previous group's
                              results fetched/unfolded on a background
                              thread (REPRO_SWEEP_LAND=async, the default)
                              so landings overlap the in-flight device step
                              too.  Warm agent batches are stacked through
                              reusable host staging buffers
                              (REPRO_STORE_STAGING=on, the default; see
                              AgentStaging) instead of per-cell device
                              imports.  Both knobs are bit-identical to
                              their historical paths.

Hot-path layout: the epoch `lax.scan` sits *outside* the (lane, seed) vmaps
(scan-of-vmap, not vmap-of-scan), so the agent invocation inside one epoch is
a genuine scalar `lax.cond` on "any lane invokes" — epochs where every AIMM
lane is between invocations skip the whole DQN machinery at run time (and TOM
candidate scoring is gated the same way on "any lane profiles").  The input
batch is donated to the compiled sweep (`donate_argnames`) and per-epoch
metric timelines are stored at slim dtypes (`valid_t` as uint16).

2-D mesh layout: the env/metric grid inside the program is (L, S, ...) with
L sharded over the mesh's lane axis and S over its seed axis — a (lane,
seed) cell never crosses a device, so per-cell results are bit-identical for
every mesh shape (4x1, 2x2, 1x4, or no mesh at all).  The agent batch stays
*flat* lane-major (L*S, ...): a reshape of a P(lanes, seeds)-sharded (L, S)
array to (L*S,) is exactly GSPMD's dimension-merge P((lanes, seeds))
sharding, so flattening costs no resharding and the whole DQN machinery is
layout-oblivious.  When the executed seed width exceeds 1 the epoch body
hoists the seed-invariant half of the cost model out of the inner seed vmap
(`BodyFlags.share_seed_inv` -> engine.SharedEpoch): window fetches, validity
masks, row-buffer stamp races, PEI thresholds and page-touch counts are
computed once per lane and broadcast across the S replicas.

Agent lifecycle: cold-start lanes are born and die inside the compiled
program (the historical path, bit-identical by construction); lanes that
declare a `Scenario.lineage` tag compile into a separate warm-capable
program whose initial agent batch is an input and whose final agent batch is
an output, threaded through a `continual.PolicyStore` so one DQN can live
across run_grid calls, program switches and process restarts (see
nmp.continual).

Exactness: technique/mapper/forced-action are traced `TraceCtx` selectors and
every engine update is gated on `has_ops` (see engine._epoch_sim/_epoch_apply),
so each (lane, seed) cell's `cycles` / `ops_done` / final OPC are bit-identical
to a serial `run_episode` / `run_program` of the same scenario — whether the
lane axis is sharded over devices or not, and however seeds are folded
(tests/test_sweep_equivalence.py, tests/test_plan_partition.py).
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import agent as agent_mod
from repro.nmp import partition
from repro.nmp import plan as plan_mod
from repro.nmp import spans
from repro.nmp.config import NMPConfig
from repro.nmp.engine import (NO_TARGET, TraceCtx, _init_env,
                              default_agent_cfg, scan_epochs, state_spec_for)
from repro.nmp.plan import GridPlan, group_flags, needs_agent, plan_grid
from repro.nmp.scenarios import Scenario
from repro.nmp.stats import energy_breakdown, energy_nj, resample_opc

LAND_KNOB = "REPRO_SWEEP_LAND"
LAND_MODES = ("async", "sync")
STAGING_KNOB = "REPRO_STORE_STAGING"
STAGING_MODES = ("on", "off")


def _env_choice(knob: str, default: str, choices: tuple[str, ...]) -> str:
    val = os.environ.get(knob, default)
    if val not in choices:
        raise ValueError(f"{knob}={val!r} is not a valid mode; expected one "
                         f"of {choices}")
    return val


def land_mode() -> str:
    """How `run_grid` lands dispatched group results (REPRO_SWEEP_LAND):
    `async` (default) fetches/unfolds group k on a background thread while
    group k+1 runs on device; `sync` is the historical in-loop landing."""
    return _env_choice(LAND_KNOB, "async", LAND_MODES)


def staging_enabled() -> bool:
    """Whether the warm agent batch is built through reusable host staging
    buffers (REPRO_STORE_STAGING, default on) instead of the historical
    per-cell device stacking.  Both paths are bit-identical."""
    return _env_choice(STAGING_KNOB, "on", STAGING_MODES) == "on"


# Fail fast on typo'd knobs at import.
land_mode()
staging_enabled()


@partial(jax.jit,
         static_argnames=("cfg", "spec", "agent_cfg", "n_epochs", "n_episodes",
                          "ring_len", "flags", "want_agent"),
         donate_argnames=("batch",))
def _run_sweep(batch, tom_cands, cfg, spec, agent_cfg, n_epochs, n_episodes,
               ring_len, flags, warm_agent=None, want_agent=False):
    """Scan over episodes; inside, the batched epoch scan runs every
    (lane, seed) cell in lockstep (nested (lane, seed) vmap of the epoch
    body, scalar any-lane-invokes agent cond).  The env is re-initialized per
    episode while the agent chains through.  `batch["ep_seed"]` is
    (L, S, E); trace arrays stay per-lane (L, ...) and are shared across the
    seed axis.

    Agent lifecycle: by default every (lane, seed) cell cold-starts its DQN
    inside the program (the exact historical path).  Lineage groups pass the
    initial agent batch in as `warm_agent` (flat (L*S,) cells, warm-started
    from a PolicyStore or cold-started on a fresh lineage) and set
    `want_agent` to get the final agent batch back out for the store."""
    trace = {k: batch[k] for k in ("dest", "src1", "src2")}
    L, S, _E = batch["ep_seed"].shape
    base_ctx = TraceCtx(
        n_ops=batch["n_ops"], n_pages=batch["n_pages"],
        t_ring=batch["t_ring"], pei_idx=batch["pei_idx"],
        technique=batch["technique"], mapper=batch["mapper"],
        forced_action=batch["forced_action"],
        explore=jnp.zeros_like(batch["ep_explore"][:, 0]))
    init_envs = jax.vmap(jax.vmap(
        lambda pt, s: _init_env(pt, cfg, spec, s, ring_len),
        in_axes=(None, 0)))                               # (L, S) grid of envs
    if warm_agent is not None:
        agent0 = warm_agent
    else:
        agent0 = (jax.vmap(lambda s: agent_mod.cold_start(s, agent_cfg))(
            batch["ep_seed"][:, :, 0].reshape(L * S))
            if flags.has_agent else None)
    env0 = init_envs(batch["page_table"], batch["ep_seed"][:, :, 0])

    def episode(carry, x):
        agent, _ = carry
        seeds, explore = x                        # (L, S) / (L,)
        ctx = base_ctx._replace(explore=explore)
        env = init_envs(batch["page_table"], seeds)
        env, agent2, ms = scan_epochs(trace, batch["rw"], env, agent,
                                      tom_cands, ctx, cfg, spec, agent_cfg,
                                      n_epochs, flags)
        out = {
            "cycles": env.cycles, "ops": env.ops_done,
            "hops_sum": env.hops_sum, "util_sum": env.util_sum,
            "epochs": env.epochs, "migrations": env.mig_count,
            "pages_migrated": env.mig_page_mask.sum(axis=-1),
            "access_total": env.access_total,
            "access_on_migrated": env.access_on_migrated,
            "energy": env.energy,
            # per-epoch timelines, stored slim: ms leaves are (n_epochs, L, S)
            "opc_t": jnp.moveaxis(ms["opc"], 0, -1),
            "valid_t": jnp.moveaxis(ms["valid"].astype(jnp.uint16), 0, -1),
            "invoke_t": jnp.moveaxis(ms["invoke"].astype(jnp.uint16), 0, -1),
        }
        if flags.any_aimm:
            # what an AIMM lane did at each epoch, for a reference that
            # replays it (0 / NO_TARGET wherever no action applied)
            out["action_t"] = jnp.moveaxis(ms["action"].astype(jnp.uint8),
                                           0, -1)
            out["target_t"] = jnp.moveaxis(ms["target"].astype(jnp.uint8),
                                           0, -1)
        if flags.has_agent:
            # scanned epochs in which the any-lane-invokes agent cond fired
            fires = jnp.sum(jnp.any(ms["invoke"] > 0, axis=(1, 2)),
                            dtype=jnp.int32)
            out["agent_fires"] = jnp.broadcast_to(fires, (L, S))
        return ((agent2 if flags.has_agent else agent), env), out

    xs = (jnp.moveaxis(batch["ep_seed"], -1, 0),          # (E, L, S)
          batch["ep_explore"].T)                          # (E, L)
    (agent_fin, env_fin), outs = jax.lax.scan(episode, (agent0, env0), xs,
                                              length=n_episodes)
    # outs leaves are (E, L, S, ...); present them cell-major.
    outs = {k: jnp.moveaxis(v, 0, 2) for k, v in outs.items()}
    return outs, env_fin, (agent_fin if want_agent else None)


@dataclasses.dataclass
class SweepResult:
    scenarios: list[Scenario]
    cfg: NMPConfig
    metrics: dict[str, np.ndarray]   # (B, E) scalars; energy (B, E, EN_N);
                                     # opc_t/valid_t/invoke_t (B, E, n_epochs),
                                     # and action_t/target_t (uint8) when a
                                     # lane is AIMM
    final_env: Any                   # EnvState stacked over the lane axis
    n_episodes: int                  # common (padded) episode count E
    wall_s: float                    # build + compile + run wall time
    plan: GridPlan | None = None     # the executed plan (seed folding, groups)
    n_devices: int = 1               # mesh width the sweep ran on
    mesh_shape: tuple[int, int] = (1, 1)   # (lane, seed) device mesh dims
    store: Any = None                # the PolicyStore holding the grid's
                                     # final agent lineages (None when no
                                     # lane declared a lineage)
    counters: dict = dataclasses.field(default_factory=dict)
    # over the learned (live-DQN) groups: `agent_epochs` scanned epochs,
    # `agent_fires` of them in which the batch's DQN step ran, and
    # `agent_invocations`, the sum of invoke_t over learned cells

    def episode_summary(self, lane: int, episode: int | None = None) -> dict:
        """Per-(lane, episode) summary with the same keys as stats.summarize.

        `episode` defaults to the scenario's last real episode (its greedy
        eval episode when `eval_episode` is set)."""
        sc = self.scenarios[lane]
        e = sc.total_episodes - 1 if episode is None else episode
        m = self.metrics
        cycles = max(float(m["cycles"][lane, e]), 1.0)
        ops = float(m["ops"][lane, e])
        return {
            "cycles": cycles,
            "ops": ops,
            "opc": ops / cycles,
            "mean_hops": float(m["hops_sum"][lane, e]) / max(ops, 1.0),
            "compute_util": (float(m["util_sum"][lane, e])
                             / max(float(m["epochs"][lane, e]), 1.0)),
            "migrations": float(m["migrations"][lane, e]),
            "frac_pages_migrated": (float(m["pages_migrated"][lane, e])
                                    / sc.trace.n_pages),
            "frac_access_migrated": (float(m["access_on_migrated"][lane, e])
                                     / max(float(m["access_total"][lane, e]),
                                           1.0)),
            "energy_nj": energy_nj(m["energy"][lane, e]),
            "energy_breakdown": energy_breakdown(m["energy"][lane, e]),
        }

    def summary(self, lane: int) -> dict:
        return self.episode_summary(lane)

    def opc_timeline(self, lane: int, episode: int | None = None,
                     samples: int = 64) -> np.ndarray:
        sc = self.scenarios[lane]
        e = sc.total_episodes - 1 if episode is None else episode
        return resample_opc(self.metrics["opc_t"][lane, e],
                            self.metrics["valid_t"][lane, e], samples)

    def invocations(self, lane: int, episode: int | None = None) -> int:
        """Agent invocations in one episode (all episodes when None) — the
        paper's natural x-axis for convergence ("invocations to threshold
        OPC", see benchmarks/bench_continual.py)."""
        sc = self.scenarios[lane]
        inv = self.metrics["invoke_t"][lane]
        if episode is not None:
            return int(inv[episode].sum())
        return int(inv[:sc.total_episodes].sum())

    # ---- variance bands over the folded seed axis ----

    def seed_group(self, lane: int) -> list[int]:
        """Scenario indices of every seed replica folded into `lane`'s lane."""
        if self.plan is None:
            return [lane]
        return list(self.plan.seed_group(lane))

    def variance_band(self, lane: int, episode: int | None = None,
                      keys: Sequence[str] = ("opc", "cycles",
                                             "energy_nj")) -> dict:
        """mean±std of per-seed episode summaries across `lane`'s seed group.

        Returns {"seeds": [...], "n": S, "<key>_mean": ..., "<key>_std": ...}
        — the variance-band record every figure gets for free from the folded
        seed axis."""
        members = self.seed_group(lane)
        sums = [self.episode_summary(i, episode) for i in members]
        band: dict[str, Any] = {
            "seeds": [self.scenarios[i].seed for i in members],
            "n": len(members),
        }
        for k in keys:
            vals = np.asarray([s[k] for s in sums], np.float64)
            band[f"{k}_mean"] = float(vals.mean())
            band[f"{k}_std"] = float(vals.std())
        return band

    def opc_timeline_band(self, lane: int, episode: int | None = None,
                          samples: int = 64) -> tuple[np.ndarray, np.ndarray]:
        """(mean, std) resampled OPC timelines across `lane`'s seed group."""
        tls = np.stack([self.opc_timeline(i, episode, samples)
                        for i in self.seed_group(lane)])
        return tls.mean(axis=0), tls.std(axis=0)


class AgentStaging:
    """Reusable host-side staging for the warm agent batch.

    The historical stacking path builds the batch from scratch every tick:
    one host->device import per warm cell, one `cold_start` per fresh cell,
    then an on-device `jnp.stack` per leaf — all garbage one tick later.
    At fleet scale (the serving layer re-stacks every resident slot every
    tick) that is hundreds of small transfers per tick.  This class keeps

      * one preallocated numpy buffer per agent leaf, shaped
        (n_cells, *leaf) — rows are filled in place from the store's host
        snapshots, so a steady-state tick pays ONE device transfer per
        *leaf* (via `partition.shard_agent_batch`) instead of one per cell;
      * a bounded cache of cold-start snapshots keyed by (seed, agent_cfg),
        so a fresh lineage's cold cell is computed once, not every tick.

    Buffers are (re)allocated whenever the cell count or leaf envelope
    changes and reused otherwise; `device_put`/jit copy out of them at
    dispatch, so refilling next tick is safe.  The stacked values are
    bit-identical to the historical path's."""

    _COLD_CACHE_MAX = 128        # cold cells are only needed for *fresh*
                                 # tags, so this never grows in steady state

    def __init__(self):
        self._bufs: list[np.ndarray] | None = None
        self._treedef = None
        self._cold: dict = {}

    def cold_cell(self, seed: int, agent_cfg):
        """Host snapshot of `agent_mod.cold_start(seed, agent_cfg)`."""
        key = (int(seed), agent_cfg)
        if key not in self._cold:
            if len(self._cold) >= self._COLD_CACHE_MAX:
                self._cold.pop(next(iter(self._cold)))
            self._cold[key] = agent_mod.export_agent(
                agent_mod.cold_start(int(seed), agent_cfg))
        return self._cold[key]

    def stack(self, cells):
        """Stack host-side cell pytrees into the reused (n_cells, ...)
        buffers; returns the stacked pytree (numpy leaves)."""
        leaves0, treedef = jax.tree_util.tree_flatten(cells[0])
        fit = (self._bufs is not None and self._treedef == treedef
               and len(self._bufs) == len(leaves0)
               and self._bufs[0].shape[0] == len(cells)
               and all(b.shape[1:] == np.shape(l) and b.dtype == l.dtype
                       for b, l in zip(self._bufs, leaves0)))
        if not fit:
            self._bufs = [np.empty((len(cells),) + np.shape(l),
                                   np.asarray(l).dtype) for l in leaves0]
            self._treedef = treedef
        for i, cell in enumerate(cells):
            for buf, leaf in zip(self._bufs, jax.tree_util.tree_leaves(cell)):
                buf[i] = leaf
        return jax.tree_util.tree_unflatten(treedef, self._bufs)


def _warm_agent_batch(group, n_lanes_padded: int, store, agent_cfg,
                      n_seeds: int | None = None, mesh=None, staging=None):
    """Initial agent batch for a lineage group: flat (L*S,) cells, lane-major.

    A cell whose lineage tag is in the store warm-starts from the stored
    agent (with the scenario-boundary handoff applied); a fresh tag
    cold-starts the lineage with the cell's own seed.  `n_seeds` is the
    *executed* seed width (the group's, padded up to the mesh seed dim by
    repeating seed slot 0 — mirroring `partition.pad_seed_axis`);
    device-divisibility padding lanes repeat lane 0's cells, mirroring
    `partition.pad_group_batch`.  With a mesh the stacked cells are placed
    on the merged (lanes, seeds) sharding up front.

    `staging` is an optional `AgentStaging` whose host buffers persist
    across calls (the serving layer holds one per server); by default a
    throwaway one is used when REPRO_STORE_STAGING is on, and the
    historical per-cell device stacking when it is off.  All paths produce
    bit-identical batches."""
    S = group.n_seeds if n_seeds is None else n_seeds
    if staging is None and staging_enabled():
        staging = AgentStaging()
    cells = []
    for lane in group.lanes:
        tag = lane.scenario.lineage
        # one checkout per tag; seed replicas reuse the read-only cell and
        # the stacking below gives each its own copy
        warm_in_store = store is not None and tag in store
        if staging is not None:
            warm = store.checkout_host(tag) if warm_in_store else None
        else:
            warm = store.checkout(tag) if warm_in_store else None
        seeds = lane.seeds + (lane.seeds[0],) * (S - group.n_seeds)
        for seed in seeds:
            if warm is not None:
                cells.append(warm)
            elif staging is not None:
                cells.append(staging.cold_cell(int(seed), agent_cfg))
            else:
                cells.append(agent_mod.cold_start(int(seed), agent_cfg))
    lane0 = cells[:S]
    for _ in range(n_lanes_padded - group.n_lanes):
        cells.extend(lane0)
    if staging is not None:
        stacked = staging.stack(cells)
    else:
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *cells)
    return partition.shard_agent_batch(stacked, mesh)


def prepare_group_batch(plan: GridPlan, group, group_cfg: NMPConfig, mesh,
                        n_lanes: int | None = None, host_cache=None,
                        ids: dict | None = None):
    """Host-side build + device placement of one group's input batch.

    `n_lanes` forces the padded lane count (the serving layer's fixed slot
    programs); by default the group is padded to the smallest
    mesh-divisible lane count, and the folded seed axis to the smallest
    mesh-divisible seed width (`partition.padded_seed_count`; padding slots
    re-simulate seed slot 0 and are dropped).  `host_cache` is threaded to
    `plan.build_group_batch` for per-lane host-array reuse across calls.
    Returns (device batch, padded lane count) — read the executed seed width
    off `batch["ep_seed"].shape[1]` (shape metadata stays readable after the
    batch is donated).  The host->device transfer happens here, so a caller
    can overlap it with a previously dispatched compiled call (double
    buffering).  `ids` (`call`, `group`) tag the `build` and `place` host
    spans (`nmp.spans`)."""
    ids = ids or {}
    n_lanes_padded = (partition.padded_lane_count(group.n_lanes, mesh)
                      if n_lanes is None else n_lanes)
    if n_lanes_padded < group.n_lanes:
        raise ValueError(f"n_lanes={n_lanes_padded} < group lane count "
                         f"{group.n_lanes}")
    if n_lanes_padded != partition.padded_lane_count(n_lanes_padded, mesh):
        raise ValueError(f"n_lanes={n_lanes_padded} is not divisible by the "
                         "device mesh width")
    n_seeds_padded = partition.padded_seed_count(group.n_seeds, mesh)
    with spans.span("build", **ids, lanes=group.n_lanes,
                    lanes_padded=n_lanes_padded, seeds_padded=n_seeds_padded):
        batch_np = plan_mod.build_group_batch(plan, group, group_cfg,
                                              host_cache=host_cache)
        batch_np = partition.pad_seed_axis(batch_np, n_seeds_padded)
        batch_np = partition.pad_group_batch(batch_np, n_lanes_padded)
    with spans.span("place", **ids, h2d_bytes=spans.nbytes(batch_np)):
        batch = partition.shard_group_batch(batch_np, mesh)
    return batch, n_lanes_padded


def executed_flags(group, n_seeds: int):
    """The BodyFlags a group actually compiles with for an executed seed
    width of `n_seeds`: mesh seed-padding can widen a width-1 group's seed
    axis, in which case the seed-invariant sharing pays even though the plan
    compiled it out — and a width-1 execution always compiles it out."""
    share = n_seeds > 1 and plan_mod.seed_share_enabled()
    if group.flags.share_seed_inv == share:
        return group.flags
    return group.flags._replace(share_seed_inv=share)


def mesh_scope(batch):
    """The context a sweep program is traced in: the abstract mesh of the
    devices its batch is split over, when there are several.  The program
    reads it to keep the agent's contractions off the order-fixed kernel,
    which GSPMD cannot split (`core.dqn`)."""
    sharding = getattr(batch["ep_seed"], "sharding", None)
    mesh = getattr(sharding, "mesh", None)
    if mesh is None or mesh.size <= 1:
        return contextlib.nullcontext()
    return jax.sharding.use_abstract_mesh(mesh.abstract_mesh)


def dispatch_sweep(batch, tom_cands, group_cfg: NMPConfig, spec, agent_cfg,
                   n_epochs: int, n_episodes: int, ring_len: int, flags,
                   warm_agent=None, want_agent: bool = False,
                   ids: dict | None = None):
    """Dispatch the compiled sweep for one prepared group batch.

    The call is asynchronous: the returned (outs, final env, final agent)
    leaves are unmaterialized jax arrays — block (`jax.block_until_ready`)
    when the values are needed, and build the *next* batch in between to
    hide its host->device transfer behind the running program.  `ids`
    (`call`, `group`) tag the `dispatch` host span (`nmp.spans`)."""
    with (warnings.catch_warnings(), spans.span("dispatch", **(ids or {})),
          mesh_scope(batch)):
        # int trace/ctx buffers have no same-shaped outputs to reuse;
        # their donation being unusable is expected, not a leak.
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return _run_sweep(batch, tom_cands, group_cfg, spec, agent_cfg,
                          n_epochs, n_episodes, ring_len, flags,
                          warm_agent=warm_agent, want_agent=want_agent)


def lane_finite_mask(out: dict, agent_fin, n_lanes: int,
                     n_seeds: int = 1) -> np.ndarray:
    """Per-lane divergence guard: True where every float metric of the lane
    AND every float param leaf of its final agent cells is finite.

    One batched `isfinite` reduction per completed tick, evaluated at host
    sync — never per epoch.  The whole check is ONE jitted program (fused
    reductions; compiles once per resident shape set, cached separately from
    the sweep programs), so the steady-state cost is a single tiny device
    call over already-materialized outputs.  `out` leaves are
    (L_padded, S, ...) metric arrays; `agent_fin` leaves (when given) are
    flat (L_padded*S, ...) cells.  Only the first `n_lanes` lanes are
    reported (padding lanes repeat lane 0 and are dropped by callers)."""
    lanes_padded = None
    floats = []
    for v in out.values():
        if jnp.issubdtype(v.dtype, jnp.floating):
            floats.append(v)
            lanes_padded = v.shape[0]
    if agent_fin is not None:
        for leaf in jax.tree.leaves(agent_fin.params):
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                floats.append(leaf)
                if lanes_padded is None:
                    lanes_padded = leaf.shape[0] // n_seeds
    if not floats:
        return np.ones(n_lanes, bool)
    return np.asarray(_finite_mask_prog(floats, lanes_padded))[:n_lanes]


@partial(jax.jit, static_argnames=("lanes_padded",))
def _finite_mask_prog(floats, lanes_padded: int):
    # every leaf is lane-major: (L_padded, S, ...) metrics and (L_padded*S,
    # ...) agent cells both collapse to (lanes_padded, -1)
    ok = jnp.ones((lanes_padded,), bool)
    for v in floats:
        ok = ok & jnp.isfinite(v).reshape(lanes_padded, -1).all(axis=1)
    return ok


def compiled_sweep_programs() -> int:
    """Number of distinct compiled sweep programs resident in the jit cache.

    The serving layer's steady-state guarantee is that this stays constant
    across service ticks once the slot programs are warm."""
    return int(_run_sweep._cache_size())


def run_grid(scenarios: Sequence[Scenario], cfg: NMPConfig = NMPConfig(),
             agent_cfg=None, store=None) -> SweepResult:
    """Run every scenario cell of a grid through the plan -> partition ->
    execute pipeline: one batched, jitted program per lane group, the folded
    seed axis vmapped inside each lane, the lane axis sharded over the device
    mesh when more than one device is visible.

    `store` is a `continual.PolicyStore` carrying agent lineages across
    run_grid calls: lanes whose `Scenario.lineage` tag it holds warm-start
    from the stored agent, fresh tags cold-start, and every tag's final
    agent is written back (the store is updated in place and also returned
    as `SweepResult.store`).  With no lineage lanes the store is untouched
    and the compiled programs are exactly the historical cold-start ones.

    Returns a SweepResult whose per-cell `cycles`/`ops`/`opc` match the serial
    `run_episode`/`run_program` protocol bit-for-bit (see module docstring).
    """
    call = spans.next_call()
    with spans.span("run_grid", call=call) as root:
        return _run_grid(list(scenarios), cfg, agent_cfg, store, call, root)


def _run_grid(scenarios, cfg, agent_cfg, store, call, root):
    """`run_grid`'s body inside its root host span `root`, each phase in a
    span of its own (`nmp.spans`)."""
    t0 = time.time()
    with spans.span("plan", call=call):
        plan = plan_grid(scenarios, cfg)
        spec = state_spec_for(cfg)
        agent_cfg = agent_cfg or default_agent_cfg(cfg)
        devices = partition.sweep_devices()
        shape = (partition.sweep_mesh_shape(len(devices))
                 or partition.auto_mesh_shape(
                     len(devices), [(g.n_lanes, g.n_seeds, g.n_episodes)
                                    for g in plan.groups]))
        mesh = partition.build_mesh(devices, shape)
        tom_cands = partition.replicate(
            plan_mod.plan_tom_candidates(plan, cfg), mesh)
        if store is None and plan.lineage_tags():
            from repro.nmp.continual import PolicyStore
            store = PolicyStore()

        # Mixed-topology grids: the stacked final env needs one link-space
        # width, so per-group pending link loads are padded to the widest
        # topology's link count before stacking (padding links carry zero
        # load).
        from repro.nmp.topology import get_topology
        n_links_max = max(
            get_topology(dataclasses.replace(cfg, topology=t)).n_links
            for t in dict.fromkeys(plan.topologies))
    root.set_metadata(lanes=plan.n_lanes, groups=len(plan.groups))

    outs: list = [None] * len(scenarios)
    envs: list = [None] * len(scenarios)
    learned: list = []               # per learned group: its counters
    staging = AgentStaging() if staging_enabled() else None
    # The store is touched from two threads under async landing: warm
    # checkouts in launch() (main thread) vs lineage write-backs in land()
    # (worker).  A tag never spans groups, so there is no semantic race —
    # the lock only keeps the registry's dict/LRU bookkeeping atomic.
    store_lock = threading.Lock()

    def launch(gi):
        """Host batch build + async dispatch of one group's program."""
        group = plan.groups[gi]
        ids = {"call": call, "group": gi}
        group_cfg = dataclasses.replace(cfg, topology=group.topology)
        batch, n_lanes_padded = prepare_group_batch(plan, group, group_cfg,
                                                    mesh, ids=ids)
        s_pad = int(batch["ep_seed"].shape[1])
        if group.lineage:
            with store_lock, spans.span("place", **ids) as place:
                warm = _warm_agent_batch(group, n_lanes_padded, store,
                                         agent_cfg, n_seeds=s_pad, mesh=mesh,
                                         staging=staging)
                place.set_metadata(h2d_bytes=spans.nbytes(warm))
        else:
            warm = None
        out, env_fin, agent_fin = dispatch_sweep(
            batch, tom_cands, group_cfg, spec, agent_cfg, plan.n_epochs,
            group.n_episodes, plan.ring_len, executed_flags(group, s_pad),
            warm_agent=warm, want_agent=group.lineage, ids=ids)
        return ids, group, group_cfg, s_pad, out, env_fin, agent_fin

    def land(state):
        """Block on a dispatched group, fetch to host, unfold its lanes."""
        ids, group, group_cfg, s_pad, out, env_fin, agent_fin = state
        with spans.span("wait", **ids):
            out = jax.block_until_ready(out)
        with spans.span("fetch", **ids,
                        d2h_bytes=spans.nbytes((out, env_fin))):
            out = partition.host_fetch(out)
            env_fin = partition.host_fetch(env_fin)
        out = dict(out)
        fires = out.pop("agent_fires", None)
        with spans.span("unfold", **ids, lanes=group.n_lanes) as unfold:
            pad_l = n_links_max - get_topology(group_cfg).n_links
            if pad_l:
                env_fin = env_fin._replace(pending_mig_loads=np.pad(
                    env_fin.pending_mig_loads, [(0, 0)] * 2 + [(0, pad_l)]))
            pad_e = plan.n_episodes - group.n_episodes
            for li, lane in enumerate(group.lanes):
                cells = {}               # seed slot -> unfolded metric dict
                for i, si in zip(lane.indices, lane.slots):
                    if si not in cells:
                        cells[si] = (
                            {k: np.pad(np.asarray(v[li, si]),
                                       [(0, pad_e)] + [(0, 0)]
                                       * (v[li, si].ndim - 1))
                             for k, v in out.items()},
                            jax.tree.map(
                                lambda a, li=li, si=si: np.asarray(a[li, si]),
                                env_fin))
                    outs[i], envs[i] = cells[si]
            if fires is not None:
                counts = {
                    "agent_epochs": plan.n_epochs * group.n_episodes,
                    "agent_fires": int(fires[0, 0].sum()),
                    "agent_invocations": sum(
                        int(outs[i]["invoke_t"][
                            :scenarios[i].total_episodes].sum())
                        for lane in group.lanes for i in lane.indices)}
                unfold.set_metadata(**counts)
                learned.append(counts)
            if group.lineage:
                # Hand every tag's final agent back to the store.  When
                # several cells share a tag (seed replicas, repeated tags),
                # the lineage continues from the first cell of the last lane
                # declaring it.
                with spans.span("fetch", **ids,
                                d2h_bytes=spans.nbytes(agent_fin)):
                    agent_fin = partition.host_fetch(agent_fin)
                with store_lock:
                    for li, lane in enumerate(group.lanes):
                        cell = jax.tree.map(
                            lambda a, li=li, s=lane.slots[0]:
                                np.asarray(a[li * s_pad + s]),
                            agent_fin)
                        store.put(lane.scenario.lineage, cell,
                                  scenario=lane.scenario.name)

    # Heaviest group first; one group in flight while the next group's host
    # batch is built, and — under async landing (REPRO_SWEEP_LAND, the
    # default) — the *previous* group's results fetched and unfolded on a
    # background thread while the in-flight group runs on device, so the
    # result drain never sits between one dispatch and the next build.
    # One worker + submission order keeps landings (and store write-backs)
    # in dispatch order; lanes are unfolded into `outs`/`envs` by scenario
    # index, so `SweepResult` ordering is identical either way.  (A tag
    # never spans groups, so warm checkouts in launch() can't race the
    # lineage write-back in land().)
    pool = (ThreadPoolExecutor(max_workers=1, thread_name_prefix="sweep-land")
            if land_mode() == "async" else None)
    try:
        landings = []
        pending = None
        for gi in plan_mod.packed_group_order(plan,
                                              partition.mesh_lane_dim(mesh),
                                              partition.mesh_seed_dim(mesh)):
            launched = launch(gi)
            if pending is not None:
                if pool is not None:
                    landings.append(pool.submit(land, pending))
                else:
                    land(pending)
            pending = launched
        if pending is not None:
            if pool is not None:
                landings.append(pool.submit(land, pending))
            else:
                land(pending)
        for fut in landings:
            fut.result()             # join in order; exceptions propagate
    finally:
        if pool is not None:
            pool.shutdown(wait=True)

    with spans.span("stack", call=call):
        metrics = {k: np.stack([_timeline(o, k) for o in outs])
                   for k in dict.fromkeys(k for o in outs for k in o)}
        final_env = jax.tree.map(lambda *xs: np.stack(xs), *envs)
        desc = partition.mesh_desc(mesh)
        return SweepResult(scenarios=scenarios, cfg=cfg, metrics=metrics,
                           final_env=final_env, n_episodes=plan.n_episodes,
                           wall_s=time.time() - t0, plan=plan,
                           n_devices=desc["n_devices"],
                           mesh_shape=tuple(desc["shape"]),
                           store=store,
                           counters={k: sum(c[k] for c in learned)
                                     for k in ("agent_epochs", "agent_fires",
                                               "agent_invocations")})


def _timeline(out: dict, key: str) -> np.ndarray:
    """A cell's `key` statistic; the AIMM-only action/target timelines of
    a cell from a group without AIMM lanes are filled in on the host (no
    action, NO_TARGET), so its program neither computes nor fetches them."""
    if key in out:
        return out[key]
    fill = {"action_t": 0, "target_t": NO_TARGET}[key]
    return np.full(out["invoke_t"].shape, fill, np.uint8)


def run_grid_serial(scenarios: Sequence[Scenario],
                    cfg: NMPConfig = NMPConfig()) -> list[dict]:
    """Reference serial loop over the same grid (one run_episode/run_program
    per lane). Used by the equivalence tests and the benchmark comparison.
    An AIMM lane's summary also holds its last episode's `action_t` and
    `target_t` timelines (uint8, as `run_grid` lands them)."""
    from repro.nmp.engine import run_episode, run_program
    from repro.nmp.stats import summarize
    out = []
    for sc in scenarios:
        sc_cfg = (dataclasses.replace(cfg, topology=sc.topology)
                  if sc.topology is not None else cfg)
        if needs_agent(sc):
            results = run_program(sc.trace, sc_cfg, sc.technique, "aimm",
                                  episodes=sc.episodes, seed=sc.seed,
                                  page_table=sc.page_table)
            if sc.eval_episode:
                results.append(run_episode(
                    sc.trace, sc_cfg, sc.technique, "aimm",
                    agent=results[-1].agent, seed=sc.seed, explore=False,
                    page_table=sc.page_table))
            res = results[-1]
        else:
            res = run_episode(sc.trace, sc_cfg, sc.technique, sc.mapper,
                              seed=sc.seed, page_table=sc.page_table,
                              forced_action=sc.forced_action)
        summary = summarize(res)
        if sc.mapper == "aimm":
            summary["action_t"] = np.asarray(res.metrics["action"], np.uint8)
            summary["target_t"] = np.asarray(res.metrics["target"], np.uint8)
        out.append(summary)
    return out
