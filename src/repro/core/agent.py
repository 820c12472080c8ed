"""Continual ε-greedy Q-learning agent (paper §4.3, §5.2).

The agent is a NamedTuple of arrays (scan-compatible). Per invocation:

  act      : ε-greedy action from the online dueling network
  observe  : append (s, a, r, s') to the replay ring buffer
  train    : one minibatch TD step (Adam), with periodic target-network sync

"Continual learning" per the paper: the DNN persists across episode resets —
only the environment state is cleared between runs (see nmp.engine.run_program).
The engine invokes the whole observe -> train -> act pipeline only on
invocation epochs (under `jax.lax.cond`); epochs between invocations carry
the agent through untouched.

Lifecycle API (the continual layer, nmp.continual, builds on these):

  cold_start     : the canonical fresh-agent convention (PRNGKey(seed + 1))
  hand_off       : scenario-boundary handoff — per-scenario counters reset,
                   lifetime state (DNN, replay, global_step) carries over
  export_agent / import_agent : host-side numpy snapshot <-> AgentState
  agent_template : RNG-free AgentState skeleton (checkpoint restore target)

`AgentState.global_step` counts env interactions over the agent's whole
lifetime and is never reset by `hand_off`; the ε-greedy schedule keys on it,
so exploration decays across scenario/program switches instead of restarting
at every boundary.  For a cold-started agent `global_step == step` until the
first handoff, so single-scenario behavior is unchanged.
"""
from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dqn
from repro.core.dqn import DQNConfig
from repro.core.replay import ReplayBuffer, init_replay, push, sample
from repro.train.optimizer import adamw

PyTree = Any


class AgentState(NamedTuple):
    params: PyTree
    target_params: PyTree
    opt_state: PyTree
    replay: ReplayBuffer
    step: jnp.ndarray          # env interactions in the current scenario
    train_steps: jnp.ndarray   # gradient updates taken (lifetime)
    rng: jax.Array
    loss_ema: jnp.ndarray
    global_step: jnp.ndarray   # lifetime env interactions (never reset)


class AgentConfig(NamedTuple):
    dqn: DQNConfig
    replay_capacity: int = 4096
    eps_start: float = 0.3
    eps_end: float = 0.02
    eps_decay: int = 120       # interactions to decay over
    train_every: int = 1       # train each invocation (continual)
    min_replay: int = 32


def optimizer(cfg: AgentConfig):
    """Adam with the gradient clip's global norm summed in the Q-network's
    fixed order (`dqn.tree_sum`), so a TD step's bits do not depend on how
    many cells are batched with it."""
    return adamw(cfg.dqn.lr, grad_clip=cfg.dqn.grad_clip,
                 leaf_sum=lambda g: dqn.tree_sum(g.reshape(-1), 0))


def init_agent(rng: jax.Array, cfg: AgentConfig) -> AgentState:
    k1, k2 = jax.random.split(rng)
    params = dqn.init_params(k1, cfg.dqn)
    opt = optimizer(cfg)
    return AgentState(
        params=params,
        target_params=jax.tree.map(jnp.copy, params),
        opt_state=opt.init(params),
        replay=init_replay(cfg.replay_capacity, cfg.dqn.state_dim),
        step=jnp.zeros((), jnp.int32),
        train_steps=jnp.zeros((), jnp.int32),
        rng=k2,
        loss_ema=jnp.zeros(()),
        global_step=jnp.zeros((), jnp.int32),
    )


@partial(jax.jit, static_argnums=1)
def cold_start(seed, cfg: AgentConfig) -> AgentState:
    """The engine's fresh-agent convention: one agent per scenario seed,
    keyed off PRNGKey(seed + 1).  `seed` may be a traced scalar (the sweep
    cold-starts whole lanes inside jit).  Always compiled: op-by-op
    execution rounded the initial weights differently from the sweep's
    compiled cold start on the TPU, so serial and batched lanes diverged."""
    return init_agent(jax.random.PRNGKey(seed + 1), cfg)


def hand_off(agent: AgentState) -> AgentState:
    """Scenario-boundary handoff (program switch, co-runner churn): the agent
    continues its lifetime — DNN weights, target net, Adam moments, replay
    buffer, RNG stream and `global_step` all carry over — while the
    per-scenario interaction counter resets.  ε-greedy exploration keys on
    `global_step`, so it keeps decaying across the boundary."""
    return agent._replace(step=jnp.zeros((), jnp.int32))


def export_agent(agent: AgentState) -> AgentState:
    """Host-side numpy snapshot of an agent (same pytree structure).  The
    snapshot is detached from any device/mesh, so it can be stored, compared
    or checkpointed regardless of where the agent ran."""
    return jax.tree.map(lambda a: np.asarray(jax.device_get(a)), agent)


def import_agent(snapshot: AgentState) -> AgentState:
    """Re-materialize an exported snapshot as device arrays (dtypes kept)."""
    return jax.tree.map(jnp.asarray, snapshot)


def agent_template(cfg: AgentConfig) -> AgentState:
    """RNG-free AgentState skeleton: every leaf has the shape/dtype of a real
    agent but zero contents (params via `dqn.zeros_params`).  Checkpoint
    restore targets are built from this, so a fresh process can restore an
    agent without replaying the init RNG."""
    params = dqn.zeros_params(cfg.dqn)
    opt = optimizer(cfg)
    return AgentState(
        params=params,
        target_params=jax.tree.map(jnp.copy, params),
        opt_state=opt.init(params),
        replay=init_replay(cfg.replay_capacity, cfg.dqn.state_dim),
        step=jnp.zeros((), jnp.int32),
        train_steps=jnp.zeros((), jnp.int32),
        rng=jax.random.PRNGKey(0),
        loss_ema=jnp.zeros(()),
        global_step=jnp.zeros((), jnp.int32),
    )


def epsilon(cfg: AgentConfig, step: jnp.ndarray) -> jnp.ndarray:
    frac = jnp.exp(-step.astype(jnp.float32) / cfg.eps_decay)
    return cfg.eps_end + (cfg.eps_start - cfg.eps_end) * frac


def act(agent: AgentState, cfg: AgentConfig, state_vec: jnp.ndarray,
        explore: bool | jnp.ndarray = True) -> tuple[jnp.ndarray, AgentState]:
    """ε-greedy action selection; returns (action, new agent state).

    `explore` may be a traced boolean (batched sweeps flip exploration per
    episode inside one compiled program); RNG consumption is identical either
    way, so greedy evaluation stays reproducible against static calls.
    """
    rng, k_eps, k_act = jax.random.split(agent.rng, 3)
    q = dqn.q_values(agent.params, state_vec, cfg.dqn)
    greedy = jnp.argmax(q).astype(jnp.int32)
    # ε decays over the agent's *lifetime* (global_step survives scenario
    # handoffs); for a cold-started agent global_step == step, so cold
    # first-episode behavior matches the historical per-scenario schedule.
    eps = epsilon(cfg, agent.global_step)
    rand_a = jax.random.randint(k_act, (), 0, cfg.dqn.n_actions)
    take_rand = jnp.asarray(explore) & (jax.random.uniform(k_eps) < eps)
    action = jnp.where(take_rand, rand_a, greedy)
    return action, agent._replace(rng=rng, step=agent.step + 1,
                                  global_step=agent.global_step + 1)


def observe(agent: AgentState, s, a, r, s2, done=0.0,
            where=True) -> AgentState:
    """Append (s, a, r, s2, done) to the replay ring where `where` holds;
    elsewhere the agent is returned unchanged (`replay.push`)."""
    return agent._replace(replay=push(agent.replay, s, a, r, s2, done,
                                      where))


def replay_ready(agent: AgentState, cfg: AgentConfig) -> jnp.ndarray:
    """True once the replay buffer holds enough samples for a real TD step.

    Monotone in time; while False, `train_step` is an exact no-op (masked
    batch, zero grads onto zero Adam moments, no step count), which is what
    lets the engine skip the whole minibatch under `lax.cond` until some lane
    is ready.
    """
    return agent.replay.size >= cfg.min_replay


def train(agent: AgentState, cfg: AgentConfig) -> AgentState:
    """One TD minibatch step; no-op (via masking) until replay has min_replay."""
    rng, k = jax.random.split(agent.rng)
    return train_step(agent._replace(rng=rng), cfg, k)


def train_step(agent: AgentState, cfg: AgentConfig,
               rng: jax.Array) -> AgentState:
    """`train` with the minibatch RNG drawn by the caller (`agent.rng` is not
    consumed here, so the engine can advance the stream unconditionally and
    gate the expensive TD step itself behind `lax.cond`)."""
    opt = optimizer(cfg)
    batch = sample(agent.replay, rng, cfg.dqn.batch_size)
    ready = (agent.replay.size >= cfg.min_replay).astype(jnp.float32)
    batch = dict(batch, w=batch["w"] * ready)

    loss, grads = jax.value_and_grad(dqn.td_loss)(
        agent.params, agent.target_params, batch, cfg.dqn)
    # Zero the update entirely when not ready (grads of masked loss are 0 anyway,
    # but Adam moments should not accumulate noise).
    grads = jax.tree.map(lambda g: g * ready, grads)
    new_params, new_opt = opt.update(grads, agent.opt_state, agent.params,
                                     agent.train_steps)
    train_steps = agent.train_steps + jnp.asarray(ready, jnp.int32)

    # Periodic hard target sync.
    sync = (train_steps % cfg.dqn.target_sync == 0) & (train_steps > 0)
    new_target = jax.tree.map(
        lambda t, p: jnp.where(sync, p, t), agent.target_params, new_params)

    return agent._replace(
        params=new_params,
        target_params=new_target,
        opt_state=new_opt,
        train_steps=train_steps,
        loss_ema=0.99 * agent.loss_ema + 0.01 * loss,
    )


def step_agent(agent: AgentState, cfg: AgentConfig, prev_s, prev_a, reward,
               new_s) -> tuple[jnp.ndarray, AgentState]:
    """Full continual-learning invocation: observe -> train -> act.

    This is the hardware flow of Fig. 4-2: the incoming (state, reward) pair
    plus the buffered (prev state, prev action) form a replay sample; the agent
    then infers the next action for the new state.
    """
    agent = observe(agent, prev_s, prev_a, reward, new_s)
    agent = train(agent, cfg)
    return act(agent, cfg, new_s)
