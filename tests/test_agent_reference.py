"""The program's agent (`core/dqn.py`, `core/agent.py`) against the plain
NumPy reference (`chipbench/reference_agent.py`) at the widths of the
benchmark's AIMM configuration: state 106, hidden 128x128, 8 actions,
batch 64, on seeded random weights and states.

The program sums in a fixed binary tree and the reference in NumPy's
order, so float32 results agree to rounding, not to the bit.  Each
tolerance below says what it allows.  A bfloat16 forward pass, the
nearest precision below the float32 the agent states, has to fail them.
The file needs no accelerator and runs as it is on one."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import reference_agent as ref  # noqa: E402
from repro.core import agent as agent_mod  # noqa: E402
from repro.core import dqn, replay  # noqa: E402
from repro.nmp.config import NMPConfig  # noqa: E402
from repro.nmp.engine import default_agent_cfg, state_spec_for  # noqa: E402
from repro.train.optimizer import global_norm  # noqa: E402

CONFIG = json.loads((ROOT / "chipbench" / "configs"
                     / "paper_4x4_aimm.json").read_text())
AGENT = CONFIG["agent"]
NMP = NMPConfig(**CONFIG["nmp_config"])
ACFG = default_agent_cfg(NMP)

# Q-values and the loss: a sum of 128 float32 products per unit, in another
# order, differs by a few ulps of the largest term: 1e-5 of the largest |Q|.
Q_TOL = 1e-5
# Gradients, the clipped norm and Adam's first moment: sums of 64 rows of
# products of two such numbers; 1e-4 of each leaf's largest entry.
GRAD_TOL = 1e-4
# Adam's step divides m by sqrt(v) + 1e-8, so where a gradient entry is
# within rounding of zero its step can take any size up to lr whichever
# side computes it: the step is checked on the program's own moments.
# There its bias correction 1 - 0.999^t cancels: one ulp of 0.999^t
# (6e-8) is 6e-5 of it at t = 1, and a power computed to a few ulps (the
# TPU's) moves a step of at most lr by about 1e-4 of lr (8.9e-8 seen on a
# v5e); 3e-4 of the learning rate leaves room for eight ulps.
STEP_TOL = 3e-4 * AGENT["lr"]
# epsilon: one float32 exp, which the TPU computes to a few tens of ulps
# (about 1.5e-6 seen on a v5e), against NumPy's.
EPS_TOL = 1e-5


def _params(seed: int):
    return agent_mod.export_agent(
        agent_mod.cold_start(seed, ACFG)).params


def _states(seed: int, n: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, 1.0, (n, AGENT["state_dim"])).astype(np.float32)


def _batch(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    n = AGENT["batch_size"]
    return {"s": _states(seed, n), "s2": _states(seed + 1, n),
            "a": rng.integers(0, AGENT["n_actions"], n).astype(np.int32),
            "r": rng.choice([-1.0, 0.0, 1.0], n).astype(np.float32),
            "done": np.zeros(n, np.float32),
            "w": np.ones(n, np.float32)}


def _close(got, want, tol_of_max) -> float:
    """The largest gap over the largest |want|, and whether it is within."""
    want = np.asarray(want, np.float64)
    gap = float(np.max(np.abs(np.asarray(got, np.float64) - want)))
    return gap / max(float(np.max(np.abs(want))), 1e-30), tol_of_max


def test_config_states_the_program_agent():
    d = ACFG.dqn
    assert state_spec_for(NMP).dim == AGENT["state_dim"] == d.state_dim
    assert (list(d.hidden), d.dueling, d.double, d.n_actions) == (
        AGENT["hidden"], AGENT["dueling"], AGENT["double"],
        AGENT["n_actions"])
    assert (d.gamma, d.lr, d.grad_clip, d.target_sync, d.batch_size) == (
        AGENT["gamma"], AGENT["lr"], AGENT["grad_clip"],
        AGENT["target_sync"], AGENT["batch_size"])
    assert (ACFG.replay_capacity, ACFG.min_replay, ACFG.eps_start,
            ACFG.eps_end, ACFG.eps_decay, ACFG.train_every) == (
        AGENT["replay_capacity"], AGENT["min_replay"], AGENT["eps_start"],
        AGENT["eps_end"], AGENT["eps_decay"], AGENT["train_every"])


@pytest.mark.parametrize("seed", [1, 123457])
def test_q_values(seed):
    params = _params(seed)
    s = _states(seed, AGENT["batch_size"])
    got = np.asarray(dqn.q_values(params, jnp.asarray(s), ACFG.dqn))
    rel, tol = _close(got, ref.q_values(params, s), Q_TOL)
    print(f"q_values rel gap {rel:.3e}")
    assert rel <= tol
    assert (np.argmax(got, -1) == np.argmax(ref.q_values(params, s), -1)
            ).mean() > 0.95


def test_bfloat16_forward_fails_the_tolerance():
    params = _params(5)
    s = _states(5, AGENT["batch_size"])
    got = np.asarray(dqn.q_values(params, jnp.asarray(s), ACFG.dqn))
    rel, tol = _close(got, ref.q_values(params, s, ml_dtypes.bfloat16),
                      Q_TOL)
    print(f"bfloat16 q_values rel gap {rel:.3e}")
    assert rel > 10 * tol


def _program_td_step(agent, batch):
    """One TD step of the program on a given minibatch, and its loss and
    gradient norm before the clip."""
    opt = agent_mod.optimizer(ACFG)
    b = {k: jnp.asarray(v) for k, v in batch.items()}
    loss, grads = jax.value_and_grad(dqn.td_loss)(
        agent.params, agent.target_params, b, ACFG.dqn)
    gnorm = global_norm(grads, lambda g: dqn.tree_sum(g.reshape(-1), 0))
    params, opt_state = opt.update(grads, agent.opt_state, agent.params,
                                   agent.train_steps)
    return loss, gnorm, params, opt_state


@pytest.mark.parametrize("steps", [0, 7])
def test_td_step(steps):
    agent = agent_mod.cold_start(steps + 3, ACFG)
    batch = _batch(steps + 40)
    m = jax.tree.map(np.asarray, agent.opt_state["m"])
    v = jax.tree.map(np.asarray, agent.opt_state["v"])
    params, target = agent.params, agent.target_params
    for k in range(steps):               # moments and weights that moved
        _, _, params, st = _program_td_step(
            agent._replace(params=params, train_steps=jnp.int32(k),
                           opt_state={"m": m, "v": v}), _batch(k))
        m, v = st["m"], st["v"]
    agent = agent._replace(params=params, opt_state={"m": m, "v": v},
                           train_steps=jnp.int32(steps))
    loss, gnorm, new_p, new_st = _program_td_step(agent, batch)
    host = lambda t: jax.tree.map(np.asarray, t)
    want = ref.td_step(host(agent.params), host(target), host(m), host(v),
                       steps, batch, {**AGENT})
    gaps = {"loss": _close(loss, want["loss"], Q_TOL),
            "grad_norm": _close(gnorm, want["grad_norm"], GRAD_TOL)}
    adam = ref.td_step(host(agent.params), host(target), host(m), host(v),
                       steps, batch, {**AGENT}, moments=(host(new_st["m"]),
                                                         host(new_st["v"])))
    for k in want["params"]:
        gaps[f"m.{k}"] = _close(new_st["m"][k], want["m"][k], GRAD_TOL)
        gaps[f"v.{k}"] = _close(new_st["v"][k], want["v"][k], 2 * GRAD_TOL)
        step_gap = float(np.max(np.abs(np.asarray(new_p[k], np.float64)
                                       - adam["params"][k])))
        gaps[f"p.{k}"] = (step_gap, STEP_TOL)
    print({k: f"{g:.2e}" for k, (g, _) in gaps.items()})
    assert all(g <= tol for g, tol in gaps.values()), gaps
    assert want["train_steps"] == steps + 1


def test_target_sync():
    sync = AGENT["target_sync"]
    agent = agent_mod.cold_start(9, ACFG)
    n = ACFG.min_replay
    filled = _batch(9)
    agent = agent._replace(replay=agent.replay._replace(
        s=agent.replay.s.at[:n].set(filled["s"][:n]),
        s2=agent.replay.s2.at[:n].set(filled["s2"][:n]),
        a=agent.replay.a.at[:n].set(filled["a"][:n]),
        r=agent.replay.r.at[:n].set(filled["r"][:n]),
        ptr=jnp.int32(n), size=jnp.int32(n)))
    host = lambda t: jax.tree.map(np.asarray, t)
    out = {}
    for steps in (sync - 2, sync - 1):
        a = agent._replace(train_steps=jnp.int32(steps))
        new = agent_mod.train_step(a, ACFG, jax.random.PRNGKey(steps))
        out[steps] = new
        want = ref.td_step(host(a.params), host(a.target_params),
                           host(a.opt_state["m"]), host(a.opt_state["v"]),
                           steps, host(replay.sample(
                               a.replay, jax.random.PRNGKey(steps),
                               AGENT["batch_size"])), {**AGENT})
        synced = steps + 1 == sync
        assert want["train_steps"] == int(new.train_steps) == steps + 1
        for k in want["params"]:
            copied = np.array_equal(np.asarray(new.target_params[k]),
                                    np.asarray(new.params[k]))
            assert copied == synced
            assert np.array_equal(want["target"][k], want["params"][k]) \
                == synced
    assert not np.array_equal(np.asarray(out[sync - 1].params["w0"]),
                              np.asarray(agent.params["w0"]))


@pytest.mark.parametrize("step", [0, 1, 60, 120, 1000])
def test_epsilon_schedule(step):
    got = float(agent_mod.epsilon(ACFG, jnp.int32(step)))
    assert got == pytest.approx(float(ref.epsilon(AGENT, step)), rel=EPS_TOL)


def test_replay_push_wraps():
    cap, dim = 5, AGENT["state_dim"]
    buf = replay.init_replay(cap, dim)
    ring = jax.tree.map(np.asarray, buf._asdict())
    rng = np.random.default_rng(3)
    for k in range(cap + 3):
        s, s2 = rng.normal(size=dim), rng.normal(size=dim)
        buf = replay.push(buf, s, k % 8, float(k), s2, 0.0)
        ring = ref.replay_push(ring, s, k % 8, float(k), s2, 0.0)
        got = jax.tree.map(np.asarray, buf._asdict())
        for key in ring:
            np.testing.assert_array_equal(got[key], ring[key], err_msg=key)
    assert int(buf.size) == cap and int(buf.ptr) == 3
