"""Plan layer of the sweep pipeline: declarative normalization of a grid.

`plan_grid` turns a flat list of `scenarios.Scenario` cells into a
`GridPlan` — the complete, backend-agnostic description of how the grid will
execute:

  * **envelope**: the shared spatial envelope (op count, page count, epoch
    count, OPC-ring length) every lane is padded to, so per-lane metrics and
    the stacked final env have one shape;
  * **seed folding**: scenarios identical up to their `seed` collapse into
    one `LanePlan` with a seed axis — the execute layer vmaps that axis
    inside the lane, so S seed replicas share a single copy of the trace
    arrays and every lane gets mean±std variance bands for free.  Lanes
    whose results provably cannot depend on the seed (deterministic
    mappers, see `seed_invariant`) collapse to a width-1 seed axis: one
    simulated cell serves every replica;
  * **lane grouping**: lanes are grouped by DQN-liveness (`needs_agent`),
    agent-lineage mode (`lane_lineage`: warm-capable lanes whose agent
    batch is threaded in/out of the program vs plain cold-start lanes) and
    cube topology (`scenario_topology`: interconnects have different link
    spaces and routing tensors, so a mixed-topology grid compiles one
    program per topology group), with per-group `engine.BodyFlags`
    recording which machinery (AIMM actions, TOM scoring, PEI thresholding)
    any lane of the group uses, so unused features compile out.  A
    single-topology mixed grid compiles at most three programs — one per
    agent-mode group — exactly the historical layout.

`build_group_batch` materializes one group's numpy input batch (trace arrays
per lane, episode seed schedules per (lane, seed)); the partition layer
(`nmp.partition`) then pads + shards it over a device mesh and the execute
layer (`nmp.sweep`) runs it.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Sequence

import numpy as np

from repro.nmp import baselines
from repro.nmp.config import NMPConfig
from repro.nmp.engine import (BodyFlags, make_ctx_host, pad_trace_ops_host,
                              pei_top_k, phase_ring_len, serial_epochs)
from repro.nmp.paging import default_alloc
from repro.nmp.scenarios import Scenario


def needs_agent(sc: Scenario) -> bool:
    """A lane carries a live DQN iff it is a learned-policy AIMM cell."""
    return sc.mapper == "aimm" and sc.forced_action < 0


def scenario_topology(sc: Scenario, cfg: NMPConfig) -> str:
    """Effective cube interconnect of a lane: the scenario's own
    `topology` tag, falling back to the sweep config's."""
    return sc.topology if sc.topology is not None else cfg.topology


def lane_lineage(sc: Scenario) -> str | None:
    """The PolicyStore tag of a lane's agent lineage, or None for a plain
    cold-start lane.  Only learned-policy AIMM lanes carry an agent, so a
    lineage tag on any other cell is inert and normalized away here."""
    return sc.lineage if needs_agent(sc) else None


_ENV_SEED_SHARE = "REPRO_SEED_SHARE"


def seed_share_enabled() -> bool:
    """Whether seed-invariant work sharing (engine.SharedEpoch hoisted out of
    the seed vmap) is enabled.  On by default; REPRO_SEED_SHARE=off forces
    the historical recompute-per-replica path (the A/B baseline in
    benchmarks/bench_fleet.py).  Bit-identical either way."""
    raw = os.environ.get(_ENV_SEED_SHARE, "on").strip().lower()
    if raw in ("", "on", "1"):
        return True
    if raw in ("off", "0"):
        return False
    raise ValueError(f"{_ENV_SEED_SHARE}={raw!r}: expected 'on' or 'off'")


def seed_invariant(sc: Scenario) -> bool:
    """True when the scenario's results cannot depend on its seed.

    The seed enters the engine only through the env RNG (and the DQN init),
    and the env RNG is consumed exclusively by AIMM lanes (random-neighbor
    action targets, ε-greedy exploration).  Deterministic mappers therefore
    produce bit-identical metrics for every seed, and the plan collapses
    their folded seed axis to width 1 — one simulated cell serves all seed
    replicas instead of re-simulating identical work per seed."""
    return sc.mapper != "aimm"




@dataclasses.dataclass(frozen=True)
class LanePlan:
    """One folded lane: a representative scenario plus its seed axis.

    `seeds` holds the simulated seed-axis values, padded to the group's
    common width S by repeating the first seed (padding slots are simulated
    and dropped).  `indices[k]` is the original grid index of the lane's
    k-th folded scenario and `slots[k]` the seed-axis slot its results come
    from — for a seed-invariant lane every scenario reads slot 0 of a
    width-1 axis."""
    scenario: Scenario
    seeds: tuple[int, ...]
    indices: tuple[int, ...]
    slots: tuple[int, ...]

    @property
    def n_seeds(self) -> int:
        return len(self.seeds)


@dataclasses.dataclass(frozen=True)
class GroupPlan:
    """One compiled program: lanes sharing an agent mode, a lineage mode, a
    seed-axis width and an episode count.

    `lineage=True` marks the warm-capable program: its initial agent batch is
    an *input* (warm-started from a PolicyStore or cold-started on a fresh
    lineage) and its final agent batch an output.  Lineage-free lanes compile
    the exact historical program — agents born and dropped inside the jit —
    so grids without lineages stay bit-identical to pre-lifecycle builds."""
    lanes: tuple[LanePlan, ...]
    has_agent: bool
    flags: BodyFlags
    n_episodes: int              # per-group padded episode count
    n_seeds: int                 # common (padded) seed-axis width S
    lineage: bool = False        # agent batch threaded in/out of the program
    topology: str = "mesh2d"     # cube interconnect every lane of the group
                                 # simulates (the execute layer runs the
                                 # group under cfg resolved to it)

    @property
    def n_lanes(self) -> int:
        return len(self.lanes)


@dataclasses.dataclass(frozen=True)
class GridPlan:
    """Declarative execution plan for a scenario grid (see module docstring)."""
    scenarios: tuple[Scenario, ...]
    groups: tuple[GroupPlan, ...]
    n_ops_max: int
    n_pages_max: int
    n_epochs: int
    ring_len: int
    n_episodes: int              # global padded episode count (presentation)
    agent_lineage: tuple[str | None, ...] = ()
                                 # per-scenario PolicyStore tag (grid order):
                                 # None = cold-start, shared tag = lanes in
                                 # one warm-start / shared-agent group
    topologies: tuple[str, ...] = ()
                                 # per-scenario effective interconnect (grid
                                 # order, cfg fallback resolved)

    @property
    def n_lanes(self) -> int:
        return sum(g.n_lanes for g in self.groups)

    def lineage_tags(self) -> tuple[str, ...]:
        """Distinct lineage tags the grid declares, in first-seen order."""
        return tuple(dict.fromkeys(t for t in self.agent_lineage
                                   if t is not None))

    def seed_group(self, index: int) -> tuple[int, ...]:
        """Original grid indices of every seed replica folded into the same
        lane as scenario `index` (always contains `index`)."""
        for g in self.groups:
            for lane in g.lanes:
                if index in lane.indices:
                    return lane.indices
        raise IndexError(index)


def lane_cost(lane: LanePlan) -> int:
    """Padded device cost proxy of one folded lane: real op count × episode
    schedule length × simulated seed width.  Drives the throughput-tuned
    shard packing (`_fold_lanes` ordering, `packed_group_order`)."""
    sc = lane.scenario
    return sc.trace.n_ops * sc.total_episodes * lane.n_seeds


def _fold_lanes(scenarios: Sequence[Scenario],
                idxs: Sequence[int]) -> list[LanePlan]:
    """Fold one group's scenarios by `fold_key`, then order lanes by
    descending padded cost (`lane_cost`), stably — first-seen order breaks
    ties.  Cost-descending order packs the ragged lanes across the mesh's
    lane shards so the per-device padding (every shard runs the group's
    common padded shapes) wastes the least work; arrival order used to put
    cheap lanes first and let one late expensive lane inflate the tail
    shard.

    Seed-invariant lanes (deterministic mappers — see `seed_invariant`)
    collapse their replicas onto a single simulated seed slot."""
    by_key: dict[tuple, list[int]] = {}
    for i in idxs:
        by_key.setdefault(scenarios[i].fold_key(), []).append(i)
    lanes = []
    for members in by_key.values():
        sc = scenarios[members[0]]
        if seed_invariant(sc):
            seeds = (sc.seed,)
            slots = (0,) * len(members)
        else:
            seeds = tuple(scenarios[i].seed for i in members)
            slots = tuple(range(len(members)))
        lanes.append(LanePlan(scenario=sc, seeds=seeds,
                              indices=tuple(members), slots=slots))
    lanes.sort(key=lambda lane: -lane_cost(lane))      # stable
    return lanes


def _pad_seed_axis(lanes: list[LanePlan]) -> tuple[list[LanePlan], int]:
    """Pad every lane's seed axis to the group max by repeating its first
    seed (padding slots re-simulate seeds[0]; their outputs are dropped)."""
    S = max(lane.n_seeds for lane in lanes)
    return [dataclasses.replace(
        lane, seeds=lane.seeds + (lane.seeds[0],) * (S - lane.n_seeds))
        for lane in lanes], S


def group_flags(group: Sequence[Scenario], cfg: NMPConfig,
                has_agent: bool) -> BodyFlags:
    """Static body flags for one sweep group: the OR over its lanes' needs."""
    pei_k = max((pei_top_k(sc.trace.n_pages, cfg) for sc in group
                 if sc.technique == "pei"), default=0)
    return BodyFlags(
        has_agent=has_agent,
        any_aimm=any(sc.mapper == "aimm" for sc in group),
        any_tom=any(sc.mapper == "tom" for sc in group),
        pei_k=pei_k,
    )


def _pad_to(n: int, d: int) -> int:
    return ((max(n, 1) + d - 1) // d) * d


def group_padded_cells(group: GroupPlan, lane_dim: int = 1,
                       seed_dim: int = 1) -> int:
    """Executed (lane, seed, episode) cell count of one group on a
    (lane_dim, seed_dim) device mesh, padding included."""
    return (_pad_to(group.n_lanes, lane_dim) * _pad_to(group.n_seeds, seed_dim)
            * group.n_episodes)


def packed_group_order(plan: GridPlan, lane_dim: int = 1,
                       seed_dim: int = 1) -> list[int]:
    """Execution order of a plan's groups: heaviest padded device cost
    first, stable.  Dispatching the big programs first overlaps their device
    execution with the host-side batch build of the cheap tail groups
    (run_grid pipelines prepare against compute), and plan.groups itself
    keeps the historical declaration order — only execution is reordered."""
    return sorted(range(len(plan.groups)),
                  key=lambda gi: -group_padded_cells(plan.groups[gi],
                                                     lane_dim, seed_dim))


def padding_waste(plan: GridPlan, lane_dim: int = 1,
                  seed_dim: int = 1) -> float:
    """Fraction of executed (lane, seed, episode) cells that are padding on
    a (lane_dim, seed_dim) mesh — the quantity `auto_mesh_shape` minimizes
    and BENCH_fleet.json records."""
    useful = sum(g.n_lanes * g.n_seeds * g.n_episodes for g in plan.groups)
    executed = sum(group_padded_cells(g, lane_dim, seed_dim)
                   for g in plan.groups)
    return 1.0 - useful / executed if executed else 0.0


@dataclasses.dataclass(frozen=True)
class Envelope:
    """The padded spatial/temporal envelope a grid's programs compile to.

    Normally derived from the scenarios themselves (`plan_envelope`); the
    serving layer (nmp.serving) instead *forces* one fixed envelope across
    every service tick, so the resident compiled programs' static shapes —
    and therefore the jit cache — never change as tenants come and go."""
    n_ops_max: int
    n_pages_max: int
    n_epochs: int
    ring_len: int
    n_episodes: int

    def dominates(self, other: "Envelope") -> bool:
        return (self.n_ops_max >= other.n_ops_max
                and self.n_pages_max >= other.n_pages_max
                and self.n_epochs >= other.n_epochs
                and self.ring_len >= other.ring_len
                and self.n_episodes >= other.n_episodes)


def plan_envelope(scenarios: Sequence[Scenario], cfg: NMPConfig) -> Envelope:
    """The minimal envelope covering every scenario of a grid."""
    if not scenarios:
        raise ValueError("empty scenario grid: plan_envelope needs at least "
                         "one scenario")
    return Envelope(
        n_ops_max=max(sc.trace.n_ops for sc in scenarios),
        n_pages_max=max(sc.trace.n_pages for sc in scenarios),
        n_epochs=max(serial_epochs(sc.trace.n_ops, cfg) for sc in scenarios),
        ring_len=max(phase_ring_len(sc.trace, cfg) for sc in scenarios),
        n_episodes=max(sc.total_episodes for sc in scenarios))


def plan_grid(scenarios: Sequence[Scenario], cfg: NMPConfig,
              envelope: Envelope | None = None) -> GridPlan:
    scenarios = tuple(scenarios)
    if not scenarios:
        raise ValueError(
            "empty scenario grid: run_grid/run_stream need at least one "
            "scenario per phase (got an empty sequence)")
    from repro.nmp.topology import validate_topology
    eff_topo = tuple(scenario_topology(sc, cfg) for sc in scenarios)
    for t in dict.fromkeys(eff_topo):
        validate_topology(t)
    # A lineage tag spanning topologies would compile into separate
    # per-topology programs whose final agents overwrite each other in the
    # PolicyStore (last group wins) — refuse it like the ragged-episode case
    # instead of corrupting the lineage (run per-topology phases as separate
    # run_grid calls, or use distinct tags).
    tag_topos: dict[str, set] = {}
    for i, sc in enumerate(scenarios):
        if lane_lineage(sc) is not None:
            tag_topos.setdefault(sc.lineage, set()).add(eff_topo[i])
    for tag, topos in tag_topos.items():
        if len(topos) > 1:
            raise ValueError(
                f"lineage {tag!r} spans topologies {sorted(topos)}; a tag's "
                "lanes must share one interconnect per grid (use distinct "
                "tags or separate run_grid calls)")

    # The spatial envelope (ops/pages/epochs/ring) is shared across both
    # agent-mode groups so the merged final_env and per-epoch timelines
    # stack; episode counts and seed widths are padded per group —
    # deterministic lanes must not simulate the AIMM lanes' longer training
    # schedules.  A forced `envelope` (the serving layer's fixed-shape
    # resident programs) replaces the derived one; it must dominate it, so
    # padding stays exact.
    derived = plan_envelope(scenarios, cfg)
    if envelope is not None:
        if not envelope.dominates(derived):
            raise ValueError(
                f"forced envelope {envelope} does not cover the grid's own "
                f"envelope {derived}; every scenario must fit the fixed "
                "shapes")
        env = envelope
    else:
        env = derived
    n_ops_max, n_pages_max = env.n_ops_max, env.n_pages_max
    n_epochs, ring_len = env.n_epochs, env.ring_len
    n_episodes = env.n_episodes

    # Group order: cold agent lanes first (the exact historical program),
    # then warm-capable lineage lanes, then deterministic lanes — grids
    # without lineages keep the historical two-group layout untouched.
    # Within an agent mode, lanes split further by cube topology (first-seen
    # order): interconnects differ in link count and routing tensors, so
    # each topology group compiles its own program; a single-topology grid
    # keeps the exact historical grouping.
    groups = []
    for has_agent, lineage in ((True, False), (True, True), (False, False)):
        mode_idxs = [i for i, sc in enumerate(scenarios)
                     if needs_agent(sc) == has_agent
                     and (lane_lineage(sc) is not None) == (has_agent
                                                            and lineage)]
        for topo in dict.fromkeys(eff_topo[i] for i in mode_idxs):
            idxs = [i for i in mode_idxs if eff_topo[i] == topo]
            lanes, n_seeds = _pad_seed_axis(_fold_lanes(scenarios, idxs))
            members = [scenarios[i] for i in idxs]
            group_eps = (envelope.n_episodes if envelope is not None
                         else max(sc.total_episodes for sc in members))
            if lineage:
                # Fail bad tags at plan time, not in the post-simulation
                # write-back (continual.check_tag enforces the same rule at
                # PolicyStore.put).
                from repro.nmp.continual import check_tag
                for sc in members:
                    check_tag(sc.lineage)
                # A padding episode would keep training a lineage's agent
                # past its scenario's schedule and hand the extra training to
                # the next phase — refuse ragged episode counts instead of
                # corrupting the lineage (run ragged phases as separate
                # run_grid calls).
                ragged = {sc.total_episodes for sc in members}
                if len(ragged) > 1:
                    raise ValueError(
                        "lineage lanes must share one episode count per grid "
                        f"(got {sorted(ragged)}); split ragged phases into "
                        "separate run_grid calls")
                if envelope is not None and ragged != {group_eps}:
                    raise ValueError(
                        f"lineage lanes run {sorted(ragged)} episodes but the "
                        f"forced envelope fixes {group_eps}; padding episodes "
                        "would keep training the lineage past its schedule")
            # Seed-invariant work sharing pays (and compiles in) only when
            # the simulated seed axis is wider than 1; the execute layer may
            # re-widen this after mesh padding (sweep.run_grid).
            flags = group_flags(members, cfg, has_agent)._replace(
                share_seed_inv=n_seeds > 1 and seed_share_enabled())
            groups.append(GroupPlan(
                lanes=tuple(lanes), has_agent=has_agent,
                flags=flags,
                n_episodes=group_eps,
                n_seeds=n_seeds, lineage=lineage, topology=topo))
    return GridPlan(scenarios=scenarios, groups=tuple(groups),
                    n_ops_max=n_ops_max, n_pages_max=n_pages_max,
                    n_epochs=n_epochs, ring_len=ring_len,
                    n_episodes=n_episodes,
                    agent_lineage=tuple(lane_lineage(sc) for sc in scenarios),
                    topologies=eff_topo)


def episode_schedule(sc: Scenario, seed: int,
                     n_episodes: int) -> tuple[np.ndarray, np.ndarray]:
    """(seeds, explore) per episode for one (lane, seed) cell, padded to the
    group episode count.

    Training episodes use seed, seed+1, ... (the run_program protocol); the
    optional eval episode replays the base seed with exploration off. Padding
    episodes continue the seed sequence and are simply not reported."""
    seeds = [seed + e for e in range(sc.episodes)]
    explore = [True] * sc.episodes
    if sc.eval_episode:
        seeds.append(seed)
        explore.append(False)
    while len(seeds) < n_episodes:
        seeds.append(seed + len(seeds))
        explore.append(True)
    return (np.asarray(seeds, np.int32), np.asarray(explore, bool))


def build_group_batch(plan: GridPlan, group: GroupPlan, cfg: NMPConfig,
                      host_cache: dict | None = None) -> dict[str, np.ndarray]:
    """Materialize one group's input batch as numpy arrays.

    Host NumPy only: no device array is made or read back (on a chip host
    each small synchronous transfer costs a fraction of a millisecond, and
    a lane would need about twenty).

    Trace/ctx/page-table entries carry the lane axis (L, ...); the episode
    seed schedule carries the folded seed axis as (L, S, E) with the
    per-lane exploration schedule at (L, E) — seed replicas of a lane share
    the schedule *shape* by construction (fold_key includes episodes and
    eval_episode).

    `host_cache` (optional, caller-owned dict) memoizes the per-lane arrays
    across calls, keyed on everything that shapes them (fold key, envelope,
    episode count, seed axis, config).  The serving layer passes a
    per-server cache so each tick's host batch build reuses the padded trace
    ops / page tables / seed schedules of resident tenants instead of
    re-padding them every tick — only lanes new to the slot map are built."""
    lanes = []
    for lane in group.lanes:
        sc = lane.scenario
        key = (sc.fold_key(), plan.n_ops_max, plan.n_pages_max,
               group.n_episodes, lane.seeds, cfg)
        if host_cache is not None and key in host_cache:
            lanes.append(host_cache[key])
            continue
        tr = sc.trace
        ops = pad_trace_ops_host(tr, plan.n_ops_max, cfg)
        pt = (np.asarray(sc.page_table, np.int32) if sc.page_table is not None
              else default_alloc(tr.n_pages, cfg))
        # pad the page table/RW flags with never-referenced filler pages that
        # follow the default interleave, so every entry is a legal cube id
        pad_pages = np.arange(tr.n_pages, plan.n_pages_max) % cfg.n_cubes
        pt = np.concatenate([pt, pad_pages.astype(np.int32)])
        rw = np.concatenate([tr.read_write,
                             np.zeros(plan.n_pages_max - tr.n_pages, bool)])
        ctx = make_ctx_host(tr, cfg, sc.technique, sc.mapper,
                            sc.forced_action)
        scheds = [episode_schedule(sc, seed, group.n_episodes)
                  for seed in lane.seeds]
        built = {
            **ops, "page_table": pt, "rw": rw,
            "n_ops": ctx.n_ops, "n_pages": ctx.n_pages,
            "t_ring": ctx.t_ring, "pei_idx": ctx.pei_idx,
            "technique": ctx.technique, "mapper": ctx.mapper,
            "forced_action": ctx.forced_action,
            "ep_seed": np.stack([s for s, _ in scheds]),       # (S, E)
            "ep_explore": scheds[0][1],                        # (E,)
        }
        if host_cache is not None:
            host_cache[key] = built
        lanes.append(built)
    return {k: np.stack([ln[k] for ln in lanes]) for k in lanes[0]}


def plan_tom_candidates(plan: GridPlan, cfg: NMPConfig):
    """TOM candidate tables for the plan's page envelope (shared, replicated
    across devices by the partition layer)."""
    return baselines.tom_candidates(plan.n_pages_max, cfg)
