"""Trace-driven, epoch-based NMP timing engine.

The entire simulate -> observe -> act -> learn loop is a single `jax.lax.scan`
(one step per agent invocation epoch), so an AIMM run is one compiled XLA
program: the continual-learning agent literally trains inside the simulator.

Epoch model (the cost model every lane runs):

  window   : the next `window_sizes[interval_level]` ops of the trace
  schedule : technique (BNMP/LDB/PEI) picks a compute cube per op, then the
             AIMM compute-remap table overrides per-page
  route    : packets s1->c, s2->c, c->d over the topology's precomputed
             routes (nmp.topology: hop matrix + route-link incidence tensor,
             built host-side per interconnect — XY on the paper's mesh,
             minimal routes on torus/ring/dragonfly); per-link flit loads
             are one gather + einsum, never per-epoch route construction
  time     : cycles = mc_inject + max(compute, link, dram serialization)
             + mean latency + NMP-table overflow stalls + migration stalls
  feedback : OPC = ops/cycles; reward = sign(dOPC); state vector from
             system EMAs + hot-page info cache entry (paper Fig. 3)

Hot-path structure (this is the optimized cost model the benchmarks measure;
see benchmarks/README.md "The engine hot path"):

  * Every epoch is split into `_epoch_sim` (cost model, reward, state vector
    -- everything that does not depend on the agent's action) and
    `_epoch_apply` (action application + state commit).  Between the two, the
    full agent invocation -- replay push, minibatch TD step, Adam update,
    target sync, eps-greedy act -- runs under `jax.lax.cond` on "any lane
    invokes this epoch", so epochs between invocations (stride 2..4 at higher
    interval levels) skip the DQN machinery entirely instead of computing it
    and masking the result.  TOM's profiling-phase candidate scoring is gated
    the same way (`lax.cond` on "any lane is in a profiling phase", see
    `_tom_window_scores`), so the 8 commit-phase windows of every TOM period
    skip the K-candidate scoring.
  * The PEI hot-page threshold is a `lax.top_k` order statistic over a static
    envelope of the hottest pages (`BodyFlags.pei_k`), not an O(P log P) sort
    of every page's access EMA; it is compiled in only when the program/grid
    actually contains PEI lanes.
  * The row-buffer distinct-page count is an O(W) scatter-stamp: each access
    stamps its page with the epoch tag (`at[].max`), a page is "distinct"
    exactly when its stamp equals the current tag.  No per-epoch sort.
  * `BodyFlags` records which features (AIMM action machinery, TOM candidate
    scoring, PEI thresholding, a live DQN) any lane of the compiled program
    uses; unused features are statically skipped, which keeps a plain
    technique-comparison grid close to baseline cost.

Batching model (plan/partition/execute pipeline, see nmp.plan / nmp.partition
/ nmp.sweep): every per-trace quantity that used to be a Python static -- op
count, OPC-ring length, PEI hot-page sort index, technique, mapper, forced
action, exploration flag -- is carried as a traced `TraceCtx` scalar instead,
and every state update is gated on `has_ops`, so epochs past the end of a
(padded) trace are exact no-ops.  The epoch body itself is written per-lane
and `jax.vmap`ed over a (lane, seed) grid (the serial runner is the same
body on a 1 x 1 grid), with the epoch scan *outside* the vmap so the
any-lane-invokes `lax.cond` is a genuine scalar branch; seed replicas of a
lane ride the inner seed-axis vmap, which shares the lane's trace arrays.
The serial runner keeps the grid's layout rather than a plain lane vmap
because on the TPU a different layout rounds the learned agent's state
features differently.  That makes one compiled program
valid for a whole stacked grid of scenarios -- shardable over a device mesh
along the lane axis -- and keeps the batched engine bit-identical to serial
runs (tests/test_sweep_equivalence.py, tests/test_engine_golden.py,
tests/test_plan_partition.py).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import actions as act_mod
from repro.core import agent as agent_mod
from repro.core.actions import (DEFAULT, FAR_COMPUTE, FAR_DATA, INC_INTERVAL,
                                DEC_INTERVAL, NEAR_COMPUTE, NEAR_DATA,
                                SOURCE_COMPUTE, N_ACTIONS)
from repro.core.agent import AgentConfig, AgentState
from repro.core.dqn import DQNConfig
from repro.core.reward import compute_reward
from repro.core.state import StateSpec, build_state
from repro.nmp import baselines
from repro.nmp import epoch_stages
from repro.nmp.config import NMPConfig
from repro.nmp.migration import migration_cost
from repro.nmp.paging import (PageInfoCache, default_alloc, init_page_cache,
                              lookup_or_insert, push_hist)
from repro.nmp.topology import get_topology
from repro.nmp.traces import Trace

MAPPERS = ("none", "tom", "aimm")
MAPPER_ID = {m: i for i, m in enumerate(MAPPERS)}
TECH_ID = {t: i for i, t in enumerate(baselines.TECHNIQUES)}

# Energy counter layout (see stats.py).
EN_PAGE_CACHE, EN_NMP_BUF, EN_MIG_Q, EN_MDMA, EN_WEIGHT, EN_REPLAY, \
    EN_STATE_BUF, EN_NET_BIT_HOPS, EN_MEM_BITS, EN_N = range(10)

# `target` timeline value of an epoch that applied no data or compute remap
# (a remap's target is a cube id 0..C-1, or C for "source mode").
NO_TARGET = 255

# TOM control period: K profiling windows (one per candidate) + this many
# commit windows running the winner (shared by _epoch_sim's phase arithmetic
# and the driver's profiling-phase cond gate).
TOM_COMMIT_WINDOWS = 8


class TraceCtx(NamedTuple):
    """Per-scenario runtime context: everything that used to be a compile-time
    static but must vary across the lanes of a batched sweep."""
    n_ops: jnp.ndarray          # () i32 real op count (trace arrays may be padded)
    n_pages: jnp.ndarray        # () i32 real page count (tables may be padded)
    t_ring: jnp.ndarray         # () i32 effective OPC phase-ring length
    pei_idx: jnp.ndarray        # () i32 hot-threshold index into the ascending
                                #        sort of the *real* pages' access EMAs
    technique: jnp.ndarray      # () i32 index into baselines.TECHNIQUES
    mapper: jnp.ndarray         # () i32 index into MAPPERS
    forced_action: jnp.ndarray  # () i32 scripted action, -1 = learned policy
    explore: jnp.ndarray        # () bool ε-greedy exploration on/off


class BodyFlags(NamedTuple):
    """Static feature flags of one compiled epoch body.

    Derived from what the lanes of a program actually use (serial runs: the
    single lane; sweeps: the OR over a group's lanes).  A feature that no lane
    uses is skipped at trace time, not masked at run time, so e.g. a pure
    technique-comparison grid never builds the AIMM action machinery and a
    grid without PEI lanes never computes the hot-page threshold.  `pei_k` is
    the top_k envelope for the PEI threshold order statistic (0 = no PEI
    lanes).

    `share_seed_inv` switches the epoch driver's folded-seed path to compute
    the seed-invariant half of the cost model (`SharedEpoch`: op windows,
    valid masks, row-buffer stamps, PEI thresholds, page-touch counts) once
    per lane and broadcast it across the S seed replicas instead of
    recomputing it S times.  Bit-identical either way; compiled out (flag
    False) when the executed seed axis is width 1."""
    has_agent: bool = False     # a live DQN (aimm lanes with a learned policy)
    any_aimm: bool = False      # hot-page selection / action application
    any_tom: bool = False       # TOM candidate scoring + commit
    pei_k: int = 0              # static top_k width for the PEI threshold
    share_seed_inv: bool = False  # hoist seed-invariant work out of seed vmap


def pei_hot_index(n_pages: int, cfg: NMPConfig) -> int:
    """Sort index of the PEI hot-page threshold among the real pages.

    Matches the historical static indexing `sorted[int(P*(1-frac)) - 1]`
    (including Python negative-index wraparound for tiny P).
    """
    return (int(n_pages * (1 - cfg.pei_hot_frac)) - 1) % n_pages


def pei_top_k(n_pages: int, cfg: NMPConfig) -> int:
    """top_k width needed to read the PEI threshold as the m-th largest EMA."""
    return n_pages - pei_hot_index(n_pages, cfg)


def episode_flags(trace: Trace, cfg: NMPConfig, technique: str, mapper: str,
                  forced_action: int = -1) -> BodyFlags:
    """Static body flags for one serial episode."""
    return BodyFlags(
        has_agent=mapper == "aimm" and forced_action < 0,
        any_aimm=mapper == "aimm",
        any_tom=mapper == "tom",
        pei_k=pei_top_k(trace.n_pages, cfg) if technique == "pei" else 0,
    )


def serial_epochs(n_ops: int, cfg: NMPConfig) -> int:
    """Number of epoch-scan steps needed to consume `n_ops` (exactly; the
    historical +1 all-padding epoch was a no-op by construction)."""
    return int(np.ceil(n_ops / cfg.epoch_ops))


def phase_ring_len(trace: Trace, cfg: NMPConfig) -> int:
    """Length of the same-phase OPC reference ring for one trace."""
    iter_ops = trace.iter_ops or trace.n_ops
    n_epochs = serial_epochs(trace.n_ops, cfg)
    return int(np.clip(iter_ops // cfg.epoch_ops, 1, n_epochs + 1))


def make_ctx_host(trace: Trace, cfg: NMPConfig, technique: str, mapper: str,
                  forced_action: int = -1, explore: bool = True) -> TraceCtx:
    """`TraceCtx` of numpy scalars (np.int32 / np.bool_), built on the host
    alone: the batched sweep stacks these into its input batch without a
    device round trip per field."""
    assert mapper in MAPPERS and technique in baselines.TECHNIQUES
    return TraceCtx(
        n_ops=np.int32(trace.n_ops),
        n_pages=np.int32(trace.n_pages),
        t_ring=np.int32(phase_ring_len(trace, cfg)),
        pei_idx=np.int32(pei_hot_index(trace.n_pages, cfg)),
        technique=np.int32(TECH_ID[technique]),
        mapper=np.int32(MAPPER_ID[mapper]),
        forced_action=np.int32(forced_action),
        explore=np.bool_(explore),
    )


def make_ctx(trace: Trace, cfg: NMPConfig, technique: str, mapper: str,
             forced_action: int = -1, explore: bool = True) -> TraceCtx:
    """`make_ctx_host` as device arrays (the serial runner's context)."""
    return jax.tree.map(jnp.asarray, make_ctx_host(
        trace, cfg, technique, mapper, forced_action, explore))


class EnvState(NamedTuple):
    page_to_cube: jnp.ndarray      # (P,) i32 data mapping
    compute_remap: jnp.ndarray     # (P,) i32, -1 = none
    op_ptr: jnp.ndarray            # () i32
    interval_level: jnp.ndarray    # () i32 (stride-1 epochs between invocations)
    since_invoke: jnp.ndarray      # () i32 epochs since last agent invocation
    span_sum: jnp.ndarray          # () f32 OPC sum of current action tenure
    span_n: jnp.ndarray            # () f32
    prev_span_mean: jnp.ndarray    # () f32 (-1 = none yet)
    opc_ring: jnp.ndarray          # (T,) f32 per-phase OPC one iteration ago
    ref_sum: jnp.ndarray           # () f32 same-phase reference sum for tenure
    ref_n: jnp.ndarray             # () f32
    page_access_ema: jnp.ndarray   # (P,) f32
    rb_stamp: jnp.ndarray          # (P+1,) i32 epoch tag of the page's last
                                   #  access (row-buffer distinct-count stamp;
                                   #  row P is the invalid-access sink)
    nmp_occ: jnp.ndarray           # (C,) f32
    rb_hit: jnp.ndarray            # (C,) f32
    mc_queue: jnp.ndarray          # (M,) f32
    global_act_hist: jnp.ndarray   # (Hg,) i32
    cache: PageInfoCache
    pending_mig_loads: jnp.ndarray  # (L,) f32
    pending_mig_stall: jnp.ndarray  # () f32
    prev_state_vec: jnp.ndarray    # (S,) f32
    prev_action: jnp.ndarray       # () i32
    recent_pages: jnp.ndarray      # (R,) i32 pages acted on recently (-1 empty)
    remap_age: jnp.ndarray         # (P,) i32 epochs since compute remap set
    rng: jax.Array
    # TOM state
    tom_scores: jnp.ndarray        # (K,) f32
    tom_active: jnp.ndarray        # () i32 candidate idx in use (-1 = default)
    # cumulative stats
    cycles: jnp.ndarray
    ops_done: jnp.ndarray
    hops_sum: jnp.ndarray
    util_sum: jnp.ndarray
    epochs: jnp.ndarray
    mig_count: jnp.ndarray
    mig_page_mask: jnp.ndarray     # (P,) f32
    access_total: jnp.ndarray
    access_on_migrated: jnp.ndarray
    energy: jnp.ndarray            # (EN_N,) f64-ish counters (f32)


class EpisodeResult(NamedTuple):
    env: EnvState
    agent: AgentState | None
    metrics: dict[str, jnp.ndarray]   # per-epoch stacked


def _init_env(page_table: jnp.ndarray, cfg: NMPConfig, spec: StateSpec,
              seed, t_ring: int = 1) -> EnvState:
    """Fresh env state. `page_table` fixes P (possibly padded); `seed` may be a
    traced scalar (episode scans re-init inside jit); `t_ring` is the static
    ring buffer size (>= every lane's effective TraceCtx.t_ring)."""
    page_table = jnp.asarray(page_table, jnp.int32)
    P = page_table.shape[0]
    C, M = cfg.n_cubes, cfg.n_mcs
    L = get_topology(cfg).n_links
    return EnvState(
        page_to_cube=page_table,
        compute_remap=jnp.full((P,), -1, jnp.int32),
        op_ptr=jnp.zeros((), jnp.int32),
        interval_level=jnp.zeros((), jnp.int32),    # invoke every epoch initially
        since_invoke=jnp.zeros((), jnp.int32),
        span_sum=jnp.zeros(()),
        span_n=jnp.zeros(()),
        prev_span_mean=jnp.full((), -1.0),
        opc_ring=jnp.zeros((t_ring,)),
        ref_sum=jnp.zeros(()),
        ref_n=jnp.zeros(()),
        page_access_ema=jnp.zeros((P,)),
        rb_stamp=jnp.zeros((P + 1,), jnp.int32),
        nmp_occ=jnp.zeros((C,)),
        rb_hit=jnp.full((C,), 0.5),
        mc_queue=jnp.zeros((M,)),
        global_act_hist=jnp.zeros((spec.global_act_hist,), jnp.int32),
        cache=init_page_cache(cfg, spec.hop_hist, spec.lat_hist,
                              spec.mig_hist, spec.act_hist),
        pending_mig_loads=jnp.zeros((L,)),
        pending_mig_stall=jnp.zeros(()),
        prev_state_vec=jnp.zeros((spec.dim,)),
        prev_action=jnp.zeros((), jnp.int32),
        recent_pages=jnp.full((max(cfg.recent_ring, 1),), -1, jnp.int32),
        remap_age=jnp.zeros((P,), jnp.int32),
        rng=jax.random.PRNGKey(seed),
        tom_scores=jnp.zeros((6,)),
        tom_active=jnp.full((), -1, jnp.int32),
        cycles=jnp.zeros(()),
        ops_done=jnp.zeros(()),
        hops_sum=jnp.zeros(()),
        util_sum=jnp.zeros(()),
        epochs=jnp.zeros(()),
        mig_count=jnp.zeros(()),
        mig_page_mask=jnp.zeros((P,)),
        access_total=jnp.zeros(()),
        access_on_migrated=jnp.zeros(()),
        energy=jnp.zeros((EN_N,)),
    )


class EpochMid(NamedTuple):
    """Intermediate results handed from `_epoch_sim` to `_epoch_apply` (and to
    the agent invocation in between).  Everything here is per-lane; the epoch
    driver vmaps the halves and keeps the agent `lax.cond` un-vmapped."""
    valid: jnp.ndarray         # (W,) f32
    w_valid: jnp.ndarray       # () f32
    has_ops: jnp.ndarray       # () bool
    invoke: jnp.ndarray        # () bool
    dest: jnp.ndarray          # (W,) i32
    src1: jnp.ndarray          # (W,) i32
    src2: jnp.ndarray          # (W,) i32
    cycles: jnp.ndarray        # () f32
    opc: jnp.ndarray           # () f32
    span_sum: jnp.ndarray
    span_n: jnp.ndarray
    cur_mean: jnp.ndarray
    ref_sum: jnp.ndarray
    ref_n: jnp.ndarray
    opc_ring: jnp.ndarray
    reward: jnp.ndarray
    hops_total: jnp.ndarray
    mean_hops: jnp.ndarray
    util: jnp.ndarray
    nmp_occ: jnp.ndarray
    rb_hit: jnp.ndarray
    mc_queue: jnp.ndarray
    page_ema: jnp.ndarray
    rb_stamp: jnp.ndarray
    cache: PageInfoCache
    ent: jnp.ndarray
    hot_page: jnp.ndarray
    touches_hot: jnp.ndarray
    ccube_hot: jnp.ndarray
    svec: jnp.ndarray
    k_nbr: jax.Array
    env_rng: jax.Array
    tom_scores: jnp.ndarray
    tom_active: jnp.ndarray
    mig_stall_tom: jnp.ndarray
    migrated_tom: jnp.ndarray
    energy: jnp.ndarray        # action-independent counters already added


# ---------------------------------------------------------------------------
# One epoch: cost model (action-independent half)
# ---------------------------------------------------------------------------

def _fetch_window(env: EnvState, trace: dict, ctx: TraceCtx,
                  cfg: NMPConfig):
    """This epoch's op window: (dest, src1, src2, valid) sliced at `op_ptr`
    from the (pre-padded) trace arrays.  The single definition of the window
    fetch + validity mask, shared by `_epoch_sim` and the TOM profiling
    scorer so the two can never drift apart."""
    W = cfg.w_max
    window = jnp.asarray(cfg.epoch_ops, jnp.int32)
    sl = lambda a: jax.lax.dynamic_slice(a, (env.op_ptr,), (W,))
    dest, src1, src2 = sl(trace["dest"]), sl(trace["src1"]), sl(trace["src2"])
    idx = jnp.arange(W)
    valid = ((idx < window)
             & (env.op_ptr + idx < ctx.n_ops)).astype(jnp.float32)
    return dest, src1, src2, valid


class SharedEpoch(NamedTuple):
    """The seed-invariant half of one lane's epoch: every quantity below
    depends only on the op stream position (`op_ptr`/`epochs`), the trace
    arrays, and trace-derived accumulators (`page_access_ema`, `rb_stamp`)
    that evolve identically across seed replicas — never on the data
    mapping, routing, timing, or RNG, which are seed-dependent.  Under
    `BodyFlags.share_seed_inv` the epoch driver computes one SharedEpoch per
    lane and broadcasts it across the folded seed axis (inner vmap
    `in_axes=None`), so S replicas share one window fetch, one row-buffer
    stamp scatter, one PEI top_k and one touch-count scatter instead of S."""
    dest: jnp.ndarray          # (W,) i32 op window destination pages
    src1: jnp.ndarray          # (W,) i32
    src2: jnp.ndarray          # (W,) i32
    valid: jnp.ndarray         # (W,) f32 window validity mask
    w_valid: jnp.ndarray       # () f32
    has_ops: jnp.ndarray       # () bool
    rb_stamp: jnp.ndarray      # (P+1,) i32 updated row-buffer stamps
    rb_winner: jnp.ndarray     # (3W,) bool first-touch-of-epoch indicators
    page_ema: jnp.ndarray      # (P,) f32 updated access EMA (PEI programs)
    pei_hot1: jnp.ndarray | None  # (W,) bool src1 above the PEI threshold
    pei_hot2: jnp.ndarray | None  # (W,) bool
    touch_cnt: jnp.ndarray | None  # (P,) f32 window touch counts (AIMM)
    tom_scores: jnp.ndarray | None  # (K,) f32 TOM candidate scores (TOM)


def _shared_epoch(env: EnvState, trace: dict, ctx: TraceCtx, cfg: NMPConfig,
                  flags: BodyFlags,
                  tom_scores_all: jnp.ndarray | None = None) -> SharedEpoch:
    """Compute the seed-invariant epoch quantities from one lane's env (any
    seed replica — seed slot 0 by convention).  The stage math lives in
    `epoch_stages.shared_stage`."""
    dest, src1, src2, valid = _fetch_window(env, trace, ctx, cfg)
    w_valid = valid.sum()
    has_ops = w_valid > 0

    parts = epoch_stages.shared_stage(
        dest, src1, src2, valid, env.epochs, env.rb_stamp,
        env.page_access_ema, ctx.n_pages, ctx.pei_idx,
        pei_k=flags.pei_k, aimm=flags.any_aimm)
    # Only the PEI threshold reads the access EMA; without PEI lanes the
    # decay + triple scatter is compiled out and the EMA rides unchanged.
    page_ema = (parts.page_ema if parts.page_ema is not None
                else env.page_access_ema)
    return SharedEpoch(dest=dest, src1=src1, src2=src2, valid=valid,
                       w_valid=w_valid, has_ops=has_ops,
                       rb_stamp=parts.rb_stamp, rb_winner=parts.rb_winner,
                       page_ema=page_ema, pei_hot1=parts.pei_hot1,
                       pei_hot2=parts.pei_hot2, touch_cnt=parts.touch_cnt,
                       tom_scores=tom_scores_all)


def _epoch_sim(env: EnvState, trace: dict, tom_cands: jnp.ndarray,
               ctx: TraceCtx, cfg: NMPConfig, spec: StateSpec,
               agent_cfg: AgentConfig, flags: BodyFlags,
               tom_scores_all: jnp.ndarray | None = None,
               shared: SharedEpoch | None = None) -> EpochMid:
    """Everything up to (but excluding) the agent's action: window fetch,
    scheduling, routing, timing, reward bookkeeping, hot-page selection and
    the state vector.  Runs per-lane (vmapped by the epoch driver).

    `tom_scores_all` is the (K,) candidate-score vector for this lane's
    window, computed by the epoch driver under its profiling-phase `lax.cond`
    (zeros when no lane is profiling — the per-lane select below never reads
    them in that case).

    `shared` carries the precomputed seed-invariant half (see SharedEpoch)
    when the driver hoists it out of the seed vmap; None (serial runs,
    S==1 programs) computes it inline — same ops, bit-identical."""
    P = env.page_to_cube.shape[0]
    C = cfg.n_cubes
    topo = get_topology(cfg)     # host-side tensors, trace-time constants
    is_tom = ctx.mapper == MAPPER_ID["tom"]
    is_aimm = ctx.mapper == MAPPER_ID["aimm"]

    # ---- seed-invariant half: window fetch, stamps, thresholds, counts ----
    if shared is None:
        shared = _shared_epoch(env, trace, ctx, cfg, flags, tom_scores_all)
    dest, src1, src2, valid = (shared.dest, shared.src1, shared.src2,
                               shared.valid)
    w_valid = shared.w_valid
    has_ops = shared.has_ops

    # ---- data mapping (TOM may override the page table) ----
    if flags.any_tom:
        eff_table = jnp.where(is_tom & (env.tom_active >= 0),
                              tom_cands[jnp.maximum(env.tom_active, 0)],
                              env.page_to_cube)
    else:
        eff_table = env.page_to_cube
    # ---- schedule + route + per-cube counts (`epoch_stages.route_stage`):
    # effective-table gathers, technique scheduling (PEI hot-source
    # placement, AIMM compute-remap override), per-link flit loads, hop
    # counts, and the per-cube compute/access/row-buffer-distinct/MC-queue
    # counts.  Counts and route weights are exact small integers in f32, so
    # every reduction is bit-exact regardless of accumulation order.
    rparts = epoch_stages.route_stage(
        dest, src1, src2, valid, shared.rb_winner, shared.pei_hot1,
        shared.pei_hot2, eff_table, env.compute_remap, ctx.technique,
        is_aimm, env.pending_mig_loads, jnp.asarray(topo.route_links),
        jnp.asarray(topo.hops), jnp.asarray(topo.nearest_mc),
        pei=flags.pei_k > 0, aimm=flags.any_aimm, n_mcs=cfg.n_mcs,
        packet_flits=cfg.packet_flits)
    ccube, loads, hops_op = rparts.ccube, rparts.loads, rparts.hops_op
    ops_c, acc_c, distinct_c, mcq = (rparts.ops_c, rparts.acc_c,
                                     rparts.distinct_c, rparts.mcq)
    hops_total = jnp.sum(hops_op * valid)
    mean_hops = hops_total / jnp.maximum(w_valid, 1.0)

    # ---- per-cube compute load & NMP-table occupancy ----
    table_excess = jnp.maximum(ops_c - cfg.nmp_table_size, 0.0).sum()
    compute_serial = jnp.max(ops_c) * cfg.t_op / cfg.cube_issue_rate
    eff_cubes = jnp.square(ops_c.sum()) / jnp.maximum(jnp.sum(ops_c ** 2), 1.0)
    util = eff_cubes / C

    # ---- row-buffer model: distinct (cube,page) pairs accessed per cube ----
    # A page maps to exactly one cube, so distinct pairs == distinct pages.
    # O(W) scatter-stamp (shared half): stamp each accessed page with this
    # epoch's tag; an access is its page's first touch of the epoch iff it
    # won the stamp race (`rb_winner`).  Only the scatter-add of winner
    # indicators by the seed-dependent compute cube stays per-seed.
    rb_stamp = shared.rb_stamp
    hit_c = jnp.where(acc_c > 0, 1.0 - distinct_c / jnp.maximum(acc_c, 1.0), 0.5)
    lat_c = hit_c * cfg.t_dram_hit + (1 - hit_c) * cfg.t_dram_miss
    dram_serial = jnp.max(acc_c * lat_c) / (cfg.n_vaults * 4.0)

    # ---- epoch cycles & OPC ----
    mc_inject = w_valid / (cfg.n_mcs * cfg.mc_issue_rate)
    # Hottest-link serialization with superlinear queuing amplification: a link
    # loaded far above the network average queues disproportionately (3-stage
    # routers, token flow control), so imbalance costs more than linearly.
    mean_load = jnp.sum(loads) / loads.shape[0]
    imbalance = jnp.max(loads) / jnp.maximum(mean_load, 1.0)
    link_serial = jnp.max(loads) * (1.0 + (cfg.congestion_alpha - 1.0)
                                    * jnp.clip((imbalance - 1.0) / 4.0, 0.0, 1.0))
    mean_lat = (mean_hops * cfg.t_router + cfg.packet_flits
                + jnp.sum(acc_c * lat_c) / jnp.maximum(acc_c.sum(), 1.0))
    # agent invocation cadence: the interval actions control how many epochs an
    # action's tenure lasts (paper intervals {100,125,167,250} cycles, modeled
    # as {1,2,3,4} fixed-size epochs between invocations).
    stride = env.interval_level + 1
    invoke = (env.since_invoke + 1 >= stride) & has_ops
    agent_overhead = jnp.where(is_aimm & invoke, cfg.t_agent, 0.0)
    cycles = (agent_overhead + mc_inject
              + jnp.maximum(jnp.maximum(compute_serial, link_serial), dram_serial)
              + mean_lat + table_excess * cfg.t_op + env.pending_mig_stall)
    cycles = jnp.where(has_ops, cycles, 0.0)
    opc = jnp.where(has_ops, w_valid / jnp.maximum(cycles, 1.0), 0.0)
    # The performance monitor accumulates OPC over the current action's tenure.
    # Reward for the previous action (paper: +-1 on performance improvement or
    # degradation): compare the tenure-mean OPC against the *same trace phase
    # one kernel iteration ago* (like-for-like; content-controlled), falling
    # back to the previous tenure's mean while the phase ring is still filling.
    span_sum = env.span_sum + opc
    span_n = env.span_n + jnp.where(has_ops, 1.0, 0.0)
    cur_mean = span_sum / jnp.maximum(span_n, 1.0)
    slot = env.epochs.astype(jnp.int32) % ctx.t_ring
    ring_ready = (env.epochs >= ctx.t_ring) & has_ops
    ref_sum = env.ref_sum + jnp.where(ring_ready, env.opc_ring[slot], 0.0)
    ref_n = env.ref_n + jnp.where(ring_ready, 1.0, 0.0)
    ref_mean = ref_sum / jnp.maximum(ref_n, 1.0)
    use_ring = ref_n >= span_n - 0.5
    r_ring = compute_reward(cur_mean, ref_mean, deadband=0.01)
    r_prev = jnp.where(env.prev_span_mean >= 0.0,
                       compute_reward(cur_mean, env.prev_span_mean,
                                      deadband=0.01), 0.0)
    reward = jnp.where(invoke,
                       jnp.where(use_ring & (ref_n > 0), r_ring, r_prev), 0.0)
    opc_ring = jnp.where(has_ops, env.opc_ring.at[slot].set(opc), env.opc_ring)

    # ---- EMAs / system info ----
    d = 0.7
    nmp_occ = d * env.nmp_occ + (1 - d) * ops_c
    rb_hit = d * env.rb_hit + (1 - d) * hit_c
    mc_queue = d * env.mc_queue + (1 - d) * mcq
    page_ema = shared.page_ema          # updated in the shared half (PEI only)

    # ---- hot page + page-info cache update (AIMM lanes only) ----
    # The MCs take turns feeding the agent page info (§5.1 round-robin); pages
    # acted on in the last few invocations are skipped so invocations cover the
    # hot set instead of hammering one page.
    if flags.any_aimm:
        with jax.named_scope("aimm.select"):
            touch_cnt = shared.touch_cnt
            recently = jnp.zeros((P,)).at[env.recent_pages].set(
                (env.recent_pages >= 0).astype(jnp.float32))
            hot_page = jnp.argmax(touch_cnt * (1.0 - recently)).astype(
                jnp.int32)
            touches_hot = touch_cnt[hot_page]
            is_hot_op = ((dest == hot_page) | (src1 == hot_page)
                         | (src2 == hot_page)) & (valid > 0)
            first_hot = jnp.argmax(is_hot_op)
            ccube_hot = ccube[first_hot]
            hops_hot = hops_op[first_hot]

            cache, ent = lookup_or_insert(env.cache, hot_page)
            cache = cache._replace(
                freq=cache.freq.at[ent].add(1.0),
                accesses=cache.accesses.at[ent].add(touches_hot),
                hop_hist=push_hist(cache.hop_hist, ent, hops_hot),
                lat_hist=push_hist(cache.lat_hist, ent, mean_lat),
            )
            env_rng, _k_agent, k_nbr = jax.random.split(env.rng, 3)

            # state vector (paper Fig. 3)
            page_rate = touches_hot / jnp.maximum(3.0 * w_valid, 1.0)
            mig_per_acc = (cache.migrations[ent]
                           / jnp.maximum(cache.accesses[ent], 1.0))
            svec = build_state(
                spec, nmp_occ, rb_hit, mc_queue, env.global_act_hist,
                env.interval_level, page_rate, mig_per_acc,
                cache.hop_hist[ent], cache.lat_hist[ent],
                cache.mig_hist[ent], cache.act_hist[ent],
                eff_table[hot_page], ccube_hot,
                occ_norm=float(cfg.nmp_table_size),
            )
    else:
        cache, ent = env.cache, jnp.zeros((), jnp.int32)
        hot_page = jnp.zeros((), jnp.int32)
        touches_hot = jnp.zeros(())
        ccube_hot = jnp.zeros((), jnp.int32)
        svec = jnp.zeros((spec.dim,))
        env_rng, k_nbr = env.rng, env.rng

    # ---- TOM control (profiling + commit are action-independent) ----
    if flags.any_tom:
        K = tom_cands.shape[0]
        period = K + TOM_COMMIT_WINDOWS
        phase = (env.epochs.astype(jnp.int32)) % period
        page_live = (jnp.arange(P) < ctx.n_pages).astype(jnp.float32)

        # profiling: candidate `phase` was scored on this window by the epoch
        # driver (under lax.cond on "any lane profiles" — see _epoch_batched);
        # outside profiling phases the scores are unused and may be zeros.
        scores_all = shared.tom_scores
        tom_scores = jnp.where(is_tom & (phase < K),
                               env.tom_scores.at[jnp.clip(phase, 0, K - 1)].set(
                                   scores_all[jnp.clip(phase, 0, K - 1)]),
                               env.tom_scores)
        commit = is_tom & (phase == K)
        best = jnp.argmax(tom_scores).astype(jnp.int32)
        prev_map = jnp.where(env.tom_active >= 0,
                             tom_cands[jnp.maximum(env.tom_active, 0)],
                             env.page_to_cube)
        changed = jnp.sum((tom_cands[best] != prev_map).astype(jnp.float32)
                          * page_live)
        tom_active = jnp.where(commit, best, env.tom_active)
        # remap data movement: amortized one-time link traffic + stall
        mig_stall_tom = jnp.where(commit,
                                  changed * cfg.page_flits / (topo.n_links * 8.0),
                                  0.0)
        migrated_tom = jnp.where(commit, changed, 0.0)
    else:
        tom_scores, tom_active = env.tom_scores, env.tom_active
        mig_stall_tom = jnp.zeros(())
        migrated_tom = jnp.zeros(())

    # ---- energy counters (action-independent part) ----
    en = env.energy
    en = en.at[EN_MEM_BITS].add(w_valid * 3 * cfg.packet_bytes * 8)
    en = en.at[EN_PAGE_CACHE].add(2 * w_valid)
    en = en.at[EN_NMP_BUF].add(2 * w_valid)
    if flags.any_aimm:
        inv = (invoke & is_aimm).astype(jnp.float32)
        if flags.has_agent:
            # One inference + one minibatch (fwd/bwd) per *invocation*: the
            # DQN machinery is invocation-gated, so weight/replay traffic is
            # charged only when the agent actually fires.
            bs = agent_cfg.dqn.batch_size
            en = en.at[EN_WEIGHT].add((1 + 3 * bs) * inv)
            en = en.at[EN_REPLAY].add((1 + bs) * inv)
        en = en.at[EN_STATE_BUF].add(2.0 * inv)

    return EpochMid(
        valid=valid, w_valid=w_valid, has_ops=has_ops, invoke=invoke,
        dest=dest, src1=src1, src2=src2,
        cycles=cycles, opc=opc,
        span_sum=span_sum, span_n=span_n, cur_mean=cur_mean,
        ref_sum=ref_sum, ref_n=ref_n, opc_ring=opc_ring, reward=reward,
        hops_total=hops_total, mean_hops=mean_hops, util=util,
        nmp_occ=nmp_occ, rb_hit=rb_hit, mc_queue=mc_queue,
        page_ema=page_ema, rb_stamp=rb_stamp,
        cache=cache, ent=ent,
        hot_page=hot_page, touches_hot=touches_hot, ccube_hot=ccube_hot,
        svec=svec, k_nbr=k_nbr, env_rng=env_rng,
        tom_scores=tom_scores, tom_active=tom_active,
        mig_stall_tom=mig_stall_tom, migrated_tom=migrated_tom,
        energy=en,
    )


def _tom_window_scores(env: EnvState, trace: dict, tom_cands: jnp.ndarray,
                       ctx: TraceCtx, cfg: NMPConfig) -> jnp.ndarray:
    """Co-location scores of every TOM candidate mapping on this lane's
    current window: the expensive profiling-phase work, split out of
    `_epoch_sim` so the epoch driver can gate it under `lax.cond` on "any
    lane is in a profiling phase" (the same shape as the DQN invocation
    gate).  Recomputes the window fetch (`_fetch_window`, three slices + the
    mask) — cheap next to scoring K candidates (`epoch_stages.tom_stage`)."""
    dest, src1, src2, valid = _fetch_window(env, trace, ctx, cfg)
    return epoch_stages.tom_stage(dest, src1, src2, valid, tom_cands,
                                  cfg.n_cubes)


# ---------------------------------------------------------------------------
# One epoch: action application + state commit
# ---------------------------------------------------------------------------

def _epoch_apply(env: EnvState, mid: EpochMid, action: jnp.ndarray,
                 rw_pages: jnp.ndarray, ctx: TraceCtx, cfg: NMPConfig,
                 flags: BodyFlags):
    """Apply the chosen action and assemble the next env state + metrics.
    Runs per-lane (vmapped by the epoch driver)."""
    C = cfg.n_cubes
    is_tom = ctx.mapper == MAPPER_ID["tom"]
    is_aimm = ctx.mapper == MAPPER_ID["aimm"]
    invoke, has_ops = mid.invoke, mid.has_ops
    window = jnp.asarray(cfg.epoch_ops, jnp.int32)
    cache = mid.cache
    en = mid.energy

    if flags.any_aimm:
        with jax.named_scope("aimm.apply"):
            # --- apply action (no-op unless an aimm lane invokes) ---
            topo = get_topology(cfg)
            hot_page = mid.hot_page
            nbr = act_mod.random_neighbor(mid.k_nbr, mid.ccube_hot,
                                          jnp.asarray(topo.nbr),
                                          jnp.asarray(topo.nbr_valid))
            diag = act_mod.far_target(mid.ccube_hot, jnp.asarray(topo.far))
            is_data = (action == NEAR_DATA) | (action == FAR_DATA)
            is_comp = ((action == NEAR_COMPUTE) | (action == FAR_COMPUTE)
                       | (action == SOURCE_COMPUTE))
            data_tgt = jnp.where(action == NEAR_DATA, nbr, diag)
            comp_tgt = jnp.where(action == NEAR_COMPUTE, nbr,
                                 jnp.where(action == FAR_COMPUTE, diag,
                                           jnp.asarray(C, jnp.int32)))
            acted = invoke & is_aimm
            target = jnp.where(acted & is_data, data_tgt,
                               jnp.where(acted & is_comp, comp_tgt,
                                         jnp.int32(NO_TARGET)))

            old_cube = env.page_to_cube[hot_page]
            mig_latency, mig_stall_aimm, mig_loads_aimm = migration_cost(
                old_cube, data_tgt, rw_pages[hot_page], mid.touches_hot, cfg)
            moved = is_data & (data_tgt != old_cube) & invoke & is_aimm
            migrated_aimm = moved.astype(jnp.float32)
            page_to_cube = env.page_to_cube.at[hot_page].set(
                jnp.where(moved, data_tgt, old_cube).astype(jnp.int32))
            mig_latency = jnp.where(moved, mig_latency, 0.0)
            mig_stall_aimm = jnp.where(moved, mig_stall_aimm, 0.0)
            mig_loads_aimm = jnp.where(moved, mig_loads_aimm, 0.0)

            # DEFAULT on the selected page restores its default mapping
            # (clears the compute-remap entry): the agent's undo for stale
            # remaps.
            entry = jnp.where(is_comp, comp_tgt,
                              jnp.where(action == DEFAULT,
                                        jnp.asarray(-1, jnp.int32),
                                        env.compute_remap[hot_page]))
            compute_remap = env.compute_remap.at[hot_page].set(
                jnp.where(invoke & is_aimm, entry,
                          env.compute_remap[hot_page]).astype(jnp.int32))
            # Finite compute-remap table: entries expire after remap_ttl epochs
            # (LRU-style eviction under table pressure), which bounds the
            # damage of a stale remap.
            remap_age = jnp.where(compute_remap >= 0, env.remap_age + 1, 0)
            expired = remap_age > cfg.remap_ttl
            compute_remap = jnp.where(expired, -1, compute_remap)
            remap_age = jnp.where(expired, 0, remap_age)
            remap_age = jnp.where(is_aimm, remap_age, env.remap_age)
            interval_level = jnp.where(invoke & is_aimm,
                                       act_mod.adjust_interval(
                                           env.interval_level, action),
                                       env.interval_level)

            cache = cache._replace(
                migrations=cache.migrations.at[mid.ent].add(migrated_aimm),
                mig_hist=jnp.where(moved,
                                   push_hist(cache.mig_hist, mid.ent,
                                             mig_latency),
                                   cache.mig_hist),
                act_hist=jnp.where(invoke & is_aimm,
                                   push_hist(cache.act_hist, mid.ent,
                                             action.astype(jnp.float32)),
                                   cache.act_hist),
            )
            gah = jnp.where(invoke & is_aimm,
                            jnp.concatenate([env.global_act_hist[1:],
                                             action[None]]),
                            env.global_act_hist)
            recent_pages = jnp.where(invoke & is_aimm,
                                     jnp.concatenate([env.recent_pages[1:],
                                                      hot_page[None]]),
                                     env.recent_pages)
            prev_state_vec = jnp.where(invoke & is_aimm, mid.svec,
                                       env.prev_state_vec)
            prev_action = jnp.where(invoke, action,
                                    env.prev_action).astype(jnp.int32)

            # ---- accesses on migrated pages (Fig. 10 stat) ----
            mig_mask = jnp.where(is_aimm,
                                 env.mig_page_mask.at[hot_page].set(
                                     jnp.maximum(env.mig_page_mask[hot_page],
                                                 migrated_aimm)),
                                 env.mig_page_mask)
            acc_mig = (jnp.sum(mig_mask[mid.dest] * mid.valid)
                       + jnp.sum(mig_mask[mid.src1] * mid.valid)
                       + jnp.sum(mig_mask[mid.src2] * mid.valid))

            aimm_f = is_aimm.astype(jnp.float32)
            en = en.at[EN_MIG_Q].add(2 * migrated_aimm * aimm_f)
            en = en.at[EN_MDMA].add(migrated_aimm * cfg.page_flits * aimm_f)
    else:
        page_to_cube = env.page_to_cube
        compute_remap = env.compute_remap
        remap_age = env.remap_age
        interval_level = env.interval_level
        gah = env.global_act_hist
        recent_pages = env.recent_pages
        prev_state_vec = env.prev_state_vec
        prev_action = env.prev_action
        mig_mask = env.mig_page_mask
        acc_mig = jnp.zeros(())
        migrated_aimm = jnp.zeros(())
        mig_stall_aimm = jnp.zeros(())
        mig_loads_aimm = jnp.zeros_like(env.pending_mig_loads)
        target = None

    # ---- combine mapper outputs ----
    mig_stall = jnp.where(is_aimm, mig_stall_aimm,
                          jnp.where(is_tom, mid.mig_stall_tom, 0.0))
    mig_loads = jnp.where(is_aimm, mig_loads_aimm,
                          jnp.zeros_like(env.pending_mig_loads))
    migrated = jnp.where(is_aimm, migrated_aimm,
                         jnp.where(is_tom, mid.migrated_tom, 0.0))

    en = en.at[EN_NET_BIT_HOPS].add(mid.hops_total * cfg.packet_bytes * 8
                                    + migrated * cfg.page_bytes * 8 * 2)

    cand_env = EnvState(
        page_to_cube=page_to_cube,
        compute_remap=compute_remap,
        op_ptr=env.op_ptr + window,
        interval_level=interval_level,
        since_invoke=jnp.where(invoke, 0,
                               env.since_invoke + 1).astype(jnp.int32),
        span_sum=jnp.where(invoke, 0.0, mid.span_sum),
        span_n=jnp.where(invoke, 0.0, mid.span_n),
        prev_span_mean=jnp.where(invoke, mid.cur_mean, env.prev_span_mean),
        opc_ring=mid.opc_ring,
        ref_sum=jnp.where(invoke, 0.0, mid.ref_sum),
        ref_n=jnp.where(invoke, 0.0, mid.ref_n),
        page_access_ema=mid.page_ema,
        rb_stamp=mid.rb_stamp,
        nmp_occ=mid.nmp_occ,
        rb_hit=mid.rb_hit,
        mc_queue=mid.mc_queue,
        global_act_hist=gah,
        cache=cache,
        pending_mig_loads=mig_loads,
        pending_mig_stall=mig_stall,
        prev_state_vec=prev_state_vec,
        prev_action=prev_action,
        recent_pages=recent_pages,
        remap_age=remap_age,
        rng=mid.env_rng,
        tom_scores=mid.tom_scores,
        tom_active=mid.tom_active,
        cycles=env.cycles + mid.cycles,
        ops_done=env.ops_done + mid.w_valid,
        hops_sum=env.hops_sum + mid.hops_total,
        util_sum=env.util_sum + mid.util,
        epochs=env.epochs + 1.0,
        mig_count=env.mig_count + jnp.where(is_aimm, migrated_aimm, 0.0),
        mig_page_mask=mig_mask,
        access_total=env.access_total + 3 * mid.w_valid,
        access_on_migrated=env.access_on_migrated + acc_mig,
        energy=en,
    )
    # Gate the entire state transition on has_ops: once the (possibly padded)
    # trace is exhausted, every subsequent epoch is an exact no-op, so batched
    # lanes of different lengths stay bit-identical to their serial runs.
    new_env = jax.tree.map(lambda n, o: jnp.where(has_ops, n, o), cand_env, env)
    metrics = {
        "opc": mid.opc, "cycles": mid.cycles, "reward": mid.reward,
        "action": jnp.where(has_ops, action, jnp.zeros((), jnp.int32)),
        "mean_hops": jnp.where(has_ops, mid.mean_hops, 0.0),
        "util": jnp.where(has_ops, mid.util, 0.0),
        "invoke": invoke.astype(jnp.float32), "valid": mid.w_valid,
    }
    if target is not None:
        metrics["target"] = target       # NO_TARGET where no remap applied
    return new_env, metrics


# ---------------------------------------------------------------------------
# One epoch: invocation-gated agent step
# ---------------------------------------------------------------------------

def _sel(mask: jnp.ndarray, new, old):
    """Per-lane select over an agent pytree (mask: (B,) bool)."""
    def one(n, o):
        m = mask.reshape(mask.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree.map(one, new, old)


def _invoke_agent(agent: AgentState, svec: jnp.ndarray, reward: jnp.ndarray,
                  invoke: jnp.ndarray, prev_svec: jnp.ndarray,
                  prev_action: jnp.ndarray, explore: jnp.ndarray,
                  commit: jnp.ndarray, prev_ok: jnp.ndarray,
                  agent_cfg: AgentConfig, agent_gate: str):
    """Batched continual-learning invocation (Fig. 4-2 flow): the completed
    transition (s_{t-1}, a_{t-1}, r_{t-1}, s_t) enters the replay buffer
    (one row written in place per committing cell), the DNN takes one
    minibatch TD step, and ε-greedy inference picks the next
    action.  Every argument carries one flat leading cell axis — the epoch
    driver flattens (lane, seed) grids down to it, so the agent machinery is
    written once for both layouts.

    The TD step sits behind its own nested `lax.cond` on "any committing lane
    has a ready replay buffer": until `min_replay` transitions have
    accumulated, a train step is an exact no-op (masked batch, zero grads
    onto zero Adam moments), so skipping it is bit-identical and the warm-up
    episodes never pay for the minibatch.  The sample RNG is drawn *outside*
    that cond (committing lanes always advance their stream), which is what
    makes the skip exact.  Lanes not committing keep their old agent
    bit-for-bit, so running this under the driver's any-lane-invokes cond
    equals the compute-then-mask reference path (tests/test_engine_golden.py).
    """
    with jax.named_scope("agent.observe"):
        ag = jax.vmap(lambda al, s, a, r, s2, m: agent_mod.observe(
            al, s, a, r, s2, where=m))(agent, prev_svec, prev_action, reward,
                                       svec, commit & prev_ok)
    keys = jax.vmap(jax.random.split)(ag.rng)          # (B, 2, key)
    ag = ag._replace(rng=jnp.where(commit[:, None], keys[:, 0], ag.rng))
    k_train = keys[:, 1]

    def do_train(a):
        trained = jax.vmap(lambda al, k: agent_mod.train_step(al, agent_cfg,
                                                              k))(a, k_train)
        return _sel(commit, trained, a)

    with jax.named_scope("agent.train"):
        ready = agent_mod.replay_ready(ag, agent_cfg)
        if agent_gate == "cond":
            ag = jax.lax.cond(jnp.any(commit & ready), do_train,
                              lambda a: a, ag)
        else:
            ag = do_train(ag)
    with jax.named_scope("agent.act"):
        action_g, acted = jax.vmap(
            lambda al, s, e: agent_mod.act(al, agent_cfg, s, e))(ag, svec,
                                                                 explore)
        ag = _sel(commit, acted, ag)
    action = jnp.where(invoke, action_g,
                       jnp.int32(DEFAULT)).astype(jnp.int32)
    return ag, action


# ---------------------------------------------------------------------------
# Epoch driver + episode runner
# ---------------------------------------------------------------------------

def _epoch_batched(env: EnvState, agent: AgentState | None, trace: dict,
                   rw_pages: jnp.ndarray, tom_cands: jnp.ndarray,
                   ctx: TraceCtx, cfg: NMPConfig, spec: StateSpec,
                   agent_cfg: AgentConfig, flags: BodyFlags,
                   agent_gate: str = "cond", tom_gate: str = "cond"):
    """One epoch over a (B, ...) batch of lanes.

    The env (and per-cell EpochMid/metrics) carry a
    (B, S) (lane, seed) grid while the trace / rw_pages / TraceCtx stay
    per-lane (B, ...): the cost-model halves are nested-vmapped with the
    trace axis unmapped over seeds, so S seed replicas of a lane share one
    copy of its (big) trace arrays.  The agent state is kept *flat* over
    B*S cells throughout — only the two cost-model halves need the 2-D view.

    The cost-model halves are vmapped per cell; the agent invocation between
    them is an un-vmapped `lax.cond` on "any lane invokes this epoch"
    (`agent_gate="masked"` forces the compute-every-epoch reference path used
    by the equality test).  TOM's profiling-phase candidate scoring is gated
    the same way: scored only under `lax.cond` on "any lane is in a
    profiling phase" (`tom_gate="masked"` forces the score-every-epoch
    reference path).

    With `flags.share_seed_inv` the seed-invariant half of
    the cost model is computed once per lane from the seed-0 env slice
    (`_shared_epoch`; every quantity in it evolves identically across seed
    replicas) and broadcast into the inner seed vmap with `in_axes=None` —
    S replicas share one window fetch / stamp scatter / PEI top_k, and TOM's
    profiling scorer runs per lane instead of per cell."""
    share = flags.share_seed_inv
    env0 = jax.tree.map(lambda a: a[:, 0], env) if share else None

    if flags.any_tom:
        K = tom_cands.shape[0]

        def scores_fn(e, t, c):
            return _tom_window_scores(e, t, tom_cands, c, cfg)

        score_env = env0 if share else env
        vscores = (jax.vmap(scores_fn) if share else
                   jax.vmap(jax.vmap(scores_fn, in_axes=(0, None, None))))
        phase = (score_env.epochs.astype(jnp.int32)
                 % (K + TOM_COMMIT_WINDOWS))             # (B,) / (B, S)
        is_tom_b = ctx.mapper == MAPPER_ID["tom"]
        n_ops_b = ctx.n_ops
        if not share:
            is_tom_b, n_ops_b = is_tom_b[:, None], n_ops_b[:, None]
        profiling = is_tom_b & (phase < K) & (score_env.op_ptr < n_ops_b)
        if tom_gate == "cond":
            tom_scores_all = jax.lax.cond(
                jnp.any(profiling),
                lambda: vscores(score_env, trace, ctx),
                lambda: jnp.zeros(phase.shape + (K,)))
        else:
            tom_scores_all = vscores(score_env, trace, ctx)
    else:
        tom_scores_all = None

    def sim_fn(e, t, c, ts):
        return _epoch_sim(e, t, tom_cands, c, cfg, spec, agent_cfg, flags, ts)

    if share:
        shared = jax.vmap(
            lambda e, t, c, ts: _shared_epoch(e, t, c, cfg, flags, ts))(
                env0, trace, ctx, tom_scores_all)

        def sim_sh(e, t, c, sh):
            return _epoch_sim(e, t, tom_cands, c, cfg, spec, agent_cfg,
                              flags, shared=sh)

        sim = jax.vmap(jax.vmap(sim_sh, in_axes=(0, None, None, None)))(
            env, trace, ctx, shared)
    else:
        sim = jax.vmap(jax.vmap(sim_fn, in_axes=(0, None, None, 0)))(
            env, trace, ctx, tom_scores_all)
    B, S = sim.invoke.shape
    flat = lambda a: a.reshape((B * S,) + a.shape[2:])
    rep = lambda a: jnp.repeat(a, S, axis=0)             # per-lane -> per-cell

    is_aimm = rep(ctx.mapper == MAPPER_ID["aimm"])       # flat (B*S,)
    forced = rep(ctx.forced_action)
    invoke_f = flat(sim.invoke)
    scripted = jnp.where(invoke_f, forced, jnp.int32(DEFAULT)).astype(jnp.int32)
    if flags.has_agent:
        prev_ok = flat(env.prev_span_mean) >= 0.0
        commit = invoke_f & is_aimm & (forced < 0)

        def fire(ag):
            return _invoke_agent(ag, flat(sim.svec), flat(sim.reward),
                                 invoke_f, flat(env.prev_state_vec),
                                 flat(env.prev_action), rep(ctx.explore),
                                 commit, prev_ok, agent_cfg, agent_gate)

        def hold(ag):
            return ag, jnp.full_like(scripted, DEFAULT)

        if agent_gate == "cond":
            agent, learned = jax.lax.cond(jnp.any(sim.invoke), fire, hold,
                                          agent)
        else:
            agent, learned = fire(agent)
        action = jnp.where(forced >= 0, scripted, learned)
    else:
        action = scripted
    action = jnp.where(is_aimm, action, jnp.zeros_like(action))

    def apply_fn(e, m, a, r, c):
        return _epoch_apply(e, m, a, r, c, cfg, flags)

    env, metrics = jax.vmap(
        jax.vmap(apply_fn, in_axes=(0, 0, 0, None, None)))(
            env, sim, action.reshape(B, S), rw_pages, ctx)
    return env, agent, metrics


def scan_epochs(trace, rw_pages, env, agent, tom_cands, ctx, cfg, spec,
                agent_cfg, n_epochs, flags, agent_gate="cond",
                tom_gate="cond"):
    """Un-jitted batched epoch scan shared by the serial and sweep runners.
    Trace, rw_pages and ctx carry a leading lane axis (B,), the env a
    (B, S) seed grid and the agent flat (B*S,) cells (see _epoch_batched);
    metrics come back as (n_epochs, B, S)."""
    def body(carry, _):
        env, agent = carry
        env, agent, m = _epoch_batched(env, agent, trace, rw_pages, tom_cands,
                                       ctx, cfg, spec, agent_cfg, flags,
                                       agent_gate, tom_gate)
        return (env, agent), m

    (env, agent), ms = jax.lax.scan(body, (env, agent), None, length=n_epochs)
    return env, agent, ms


@partial(jax.jit, static_argnames=("cfg", "spec", "agent_cfg", "n_epochs",
                                   "flags", "agent_gate", "tom_gate"),
         donate_argnames=("env", "agent"))
def _run_scan(trace, rw_pages, env, agent, tom_cands, ctx, cfg, spec,
              agent_cfg, n_epochs, flags, agent_gate, tom_gate="cond"):
    # env/agent are donated: the scan carry is the same pytree of shapes, so
    # XLA reuses the input buffers for the carry instead of allocating a
    # second stacked-env footprint (the callers build both args fresh).
    return scan_epochs(trace, rw_pages, env, agent, tom_cands, ctx, cfg, spec,
                       agent_cfg, n_epochs, flags, agent_gate, tom_gate)


def state_spec_for(cfg: NMPConfig) -> StateSpec:
    """State layout for a config: cube/MC counts plus the page-info-cache
    history depths (configurable via NMPConfig; the paper's Fig. 3 defaults
    leave the historical layout untouched)."""
    return StateSpec(n_cubes=cfg.n_cubes, n_mcs=cfg.n_mcs,
                     hop_hist=cfg.hop_hist, lat_hist=cfg.lat_hist,
                     mig_hist=cfg.mig_hist, act_hist=cfg.act_hist)


def default_agent_cfg(cfg: NMPConfig) -> AgentConfig:
    """Default AIMM hyperparameters.

    gamma=0: the tenure reward already integrates the action's effect over its
    own horizon (like-for-like vs the previous kernel iteration), so mapping
    control is contextual-bandit-shaped, and bootstrapping with a large gamma
    only adds TD noise at the few hundred invocations an episode makes.
    """
    spec = state_spec_for(cfg)
    return AgentConfig(dqn=DQNConfig(state_dim=spec.dim, n_actions=N_ACTIONS,
                                     gamma=0.0))


def pad_trace_ops_host(trace: Trace, n_total: int,
                       cfg: NMPConfig) -> dict[str, np.ndarray]:
    """Trace op arrays padded to `n_total + w_max` (dict of numpy arrays)."""
    pad = n_total - trace.n_ops + cfg.w_max
    return {k: np.concatenate([v, np.zeros(pad, v.dtype)])
            for k, v in trace.as_dict().items() if k != "program_id"}


def pad_trace_ops(trace: Trace, n_total: int, cfg: NMPConfig) -> dict:
    """`pad_trace_ops_host` as device arrays (dict of jnp arrays)."""
    return {k: jnp.asarray(v)
            for k, v in pad_trace_ops_host(trace, n_total, cfg).items()}


def _batch1(tree):
    """Add a leading batch axis of 1 to every leaf."""
    return jax.tree.map(lambda a: jnp.asarray(a)[None], tree)


def run_episode(trace: Trace, cfg: NMPConfig = NMPConfig(),
                technique: str = "bnmp", mapper: str = "none",
                agent: AgentState | None = None,
                agent_cfg: AgentConfig | None = None,
                seed: int = 0, page_table: np.ndarray | None = None,
                explore: bool = True, forced_action: int = -1,
                agent_gate: str = "cond",
                tom_gate: str = "cond") -> EpisodeResult:
    """Run one episode (= one pass over the trace) and return final stats.

    `agent` persists across episodes (continual learning); pass the returned
    agent back in to keep training. Env state is reset each episode, matching
    the paper's protocol ("simulation states are cleared except the DNN").
    Cross-scenario persistence (warm starts, program-switch streams,
    checkpointing) lives one layer up in `nmp.continual.PolicyStore` — the
    engine only ever sees an AgentState in, an AgentState out.

    This serial runner is the batched engine on a 1 x 1 (lane, seed) grid,
    so its numbers are bit-identical to the same lane inside a
    `sweep.run_grid` batch by construction.
    """
    assert mapper in MAPPERS and technique in baselines.TECHNIQUES
    spec = state_spec_for(cfg)
    agent_cfg = agent_cfg or default_agent_cfg(cfg)
    flags = episode_flags(trace, cfg, technique, mapper, forced_action)
    if flags.has_agent and agent is None:
        # Fresh lineage: the canonical cold-start convention shared with the
        # sweep's in-jit lane init and the continual layer's fresh tags.
        agent = agent_mod.cold_start(seed, agent_cfg)
    n_epochs = serial_epochs(trace.n_ops, cfg)

    tr = _batch1(pad_trace_ops(trace, trace.n_ops, cfg))
    rw = _batch1(jnp.asarray(trace.read_write))
    pt = page_table if page_table is not None else default_alloc(trace.n_pages, cfg)
    env = _batch1(_batch1(_init_env(pt, cfg, spec, seed,
                                    phase_ring_len(trace, cfg))))   # 1 x 1 grid
    tom_cands = baselines.tom_candidates(trace.n_pages, cfg)
    ctx = _batch1(make_ctx(trace, cfg, technique, mapper, forced_action,
                           explore))

    env, agent_out, ms = _run_scan(tr, rw, env,
                                   _batch1(agent) if flags.has_agent else None,
                                   tom_cands, ctx, cfg, spec, agent_cfg,
                                   n_epochs, flags, agent_gate, tom_gate)
    env = jax.tree.map(lambda a: a[0, 0], env)
    ms = {k: v[:, 0, 0] for k, v in ms.items()}
    if flags.has_agent:
        agent_out = jax.tree.map(lambda a: a[0], agent_out)
    else:
        agent_out = agent
    return EpisodeResult(env, agent_out, ms)


def run_program(trace: Trace, cfg: NMPConfig = NMPConfig(),
                technique: str = "bnmp", mapper: str = "none",
                episodes: int = 5, seed: int = 0,
                page_table: np.ndarray | None = None,
                agent_cfg: AgentConfig | None = None,
                agent: AgentState | None = None) -> list[EpisodeResult]:
    """Paper §6.1 protocol: run the application episode `episodes` times,
    clearing simulation state between runs but keeping the DNN.

    This is the serial reference runner; `sweep.run_grid` executes the same
    protocol (episode chaining inside one compiled scan) for whole grids.
    """
    results = []
    for e in range(episodes):
        res = run_episode(trace, cfg, technique, mapper, agent=agent,
                          agent_cfg=agent_cfg, seed=seed + e,
                          page_table=page_table)
        agent = res.agent
        results.append(res)
    return results
