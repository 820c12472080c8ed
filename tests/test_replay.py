"""The replay ring's masked write (`replay.push(..., where)`), and the
compiled agent step it serves: one row per committing cell written in place,
with no whole-buffer copy or select left in the program."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import agent as agent_mod
from repro.core.actions import N_ACTIONS
from repro.core.agent import AgentConfig
from repro.core.dqn import DQNConfig
from repro.core.replay import init_replay, push
from repro.nmp import engine

CAP, DIM = 4, 3


def _row(k):
    """A distinct transition per k: (s, a, r, s2, done)."""
    return (jnp.full(DIM, k, jnp.float32), k % 5, float(k) + 0.5,
            jnp.full(DIM, -k, jnp.float32), float(k % 2))


def _ring_ref(buf, rows):
    """A plain NumPy ring write of every row in order, from `buf`."""
    out = {f: np.array(getattr(buf, f)) for f in buf._fields}
    for s, a, r, s2, done in rows:
        i = int(out["ptr"])
        out["s"][i], out["a"][i], out["r"][i] = s, a, r
        out["s2"][i], out["done"][i] = s2, done
        out["ptr"] = np.int32((i + 1) % CAP)
        out["size"] = np.int32(min(int(out["size"]) + 1, CAP))
    return out


def _assert_buf(buf, want: dict):
    for f in buf._fields:
        got = np.asarray(getattr(buf, f))
        assert got.dtype == want[f].dtype, f
        np.testing.assert_array_equal(got, want[f], err_msg=f)


def _filled(n):
    buf = init_replay(CAP, DIM)
    for k in range(n):
        buf = push(buf, *_row(k + 1))
    return buf


def _case_false_keeps_buffer():
    """where=False leaves every leaf bit-identical, ptr and size too, on an
    empty, a partly filled and a full ring."""
    for n in (0, 2, CAP + 1):
        buf = _filled(n)
        _assert_buf(push(buf, *_row(9), where=False), _ring_ref(buf, []))
        _assert_buf(push(buf, *_row(9), where=jnp.asarray(False)),
                    _ring_ref(buf, []))


def _case_true_is_plain_write():
    """where=True (default, Python or traced) is the plain ring write."""
    buf = _filled(2)
    want = _ring_ref(buf, [_row(7)])
    _assert_buf(push(buf, *_row(7)), want)
    _assert_buf(push(buf, *_row(7), where=True), want)
    _assert_buf(jax.jit(push)(buf, *_row(7), jnp.asarray(True)), want)


def _case_wraps_at_capacity():
    """Past capacity the oldest rows are overwritten and size stays at
    capacity; masked-off writes in between move nothing."""
    buf = init_replay(CAP, DIM)
    rows = []
    for k in range(2 * CAP + 1):
        keep = k % 3 != 1
        buf = push(buf, *_row(k), where=keep)
        rows += [_row(k)] if keep else []
    _assert_buf(buf, _ring_ref(init_replay(CAP, DIM), rows))
    assert int(buf.size) == CAP


def _case_vmap_mixed_mask():
    """Under vmap with a mixed mask each cell equals its own unbatched push
    (mask true) or its untouched buffer (mask false)."""
    bufs = [_filled(n) for n in (0, 1, CAP, CAP + 2, 3)]
    mask = jnp.asarray([True, False, True, False, True])
    rows = [_row(10 + c) for c in range(len(bufs))]
    stack = lambda xs: jax.tree.map(lambda *l: jnp.stack(
        [jnp.asarray(v) for v in l]), *xs)
    out = jax.vmap(push)(stack(bufs), *stack(rows), mask)
    for c, buf in enumerate(bufs):
        cell = jax.tree.map(lambda v: v[c], out)
        _assert_buf(cell, _ring_ref(buf, [rows[c]] if mask[c] else []))


@pytest.mark.parametrize("case", [_case_false_keeps_buffer,
                                  _case_true_is_plain_write,
                                  _case_wraps_at_capacity,
                                  _case_vmap_mixed_mask],
                         ids=["where_false_keeps_buffer",
                              "where_true_is_plain_write",
                              "wraps_at_capacity", "vmap_mixed_mask"])
def test_masked_push(case):
    case()


# ---------------------------------------------------------------------------
# The batched agent step: masked in-place write, exactly the masked select

CELLS, STATE, REPLAY, FIRES = 4, 10, 64, 12
ACFG = AgentConfig(dqn=DQNConfig(state_dim=STATE, n_actions=N_ACTIONS,
                                 hidden=(16, 16), batch_size=8),
                   replay_capacity=REPLAY, min_replay=2)


def _inputs(seed=0):
    """Per-fire inputs with data-dependent masks: invoke, commit and
    prev_ok mixed across cells, and some fires in which no cell invokes."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    invoke = jax.random.bernoulli(k[0], 0.6, (FIRES, CELLS))
    invoke = invoke.at[3].set(False)
    return dict(
        svec=jax.random.normal(k[1], (FIRES, CELLS, STATE)),
        reward=jax.random.normal(k[2], (FIRES, CELLS)),
        invoke=invoke,
        commit=invoke & jax.random.bernoulli(k[3], 0.8, (FIRES, CELLS)),
        prev_ok=jax.random.bernoulli(k[4], 0.7, (FIRES, CELLS)),
        prev_action=jax.random.randint(k[5], (FIRES, CELLS), 0, N_ACTIONS),
    )


def _scan(invoke_agent, agent, xs):
    """The sweep's shape of the step: a scan of epochs, the agent step
    under the any-cell-invokes cond."""
    def body(ag, x):
        def fire(a):
            return invoke_agent(a, x["svec"], x["reward"], x["invoke"],
                                -x["svec"], x["prev_action"],
                                jnp.ones(CELLS, bool), x["commit"],
                                x["prev_ok"], ACFG, "cond")

        def hold(a):
            return a, jnp.zeros(CELLS, jnp.int32)
        return jax.lax.cond(jnp.any(x["invoke"]), fire, hold, ag)
    return jax.lax.scan(body, agent, xs)


def _agents():
    return jax.vmap(lambda s: agent_mod.cold_start(s, ACFG))(
        jnp.arange(CELLS))


def _select_after_push(agent, svec, reward, invoke, prev_svec, prev_action,
                       explore, commit, prev_ok, agent_cfg, agent_gate):
    """The step written as a push into every cell followed by a per-cell
    select of whole agents: the compute-then-mask form the masked write
    must equal bit for bit."""
    pushed = jax.vmap(agent_mod.observe)(agent, prev_svec, prev_action,
                                         reward, svec)
    agent = engine._sel(commit & prev_ok, pushed, agent)
    return engine._invoke_agent(agent, svec, reward, invoke, prev_svec,
                                prev_action, explore, commit,
                                jnp.zeros_like(prev_ok), agent_cfg,
                                agent_gate)


def test_masked_write_equals_select_after_push():
    """Twelve fires with mixed masks: the final agents (replay, Q-network,
    Adam state, RNG) and every action equal the select-after-push form."""
    xs = _inputs()
    assert bool(jnp.any(xs["commit"] & xs["prev_ok"]))
    assert bool(jnp.any(xs["commit"] & ~xs["prev_ok"]))
    got = jax.jit(lambda a, x: _scan(engine._invoke_agent, a, x))(
        _agents(), xs)
    want = jax.jit(lambda a, x: _scan(_select_after_push, a, x))(
        _agents(), xs)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert int(got[0].replay.size.sum()) == int(
        (xs["commit"] & xs["prev_ok"]).sum())
    assert int(got[0].train_steps.sum()) > 0


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([\w\-]+)\(")


def _replay_ops(hlo: str) -> list[tuple[str, str]]:
    """(name, opcode) of every instruction, fused ones included, whose
    result is or holds the whole batched replay `f32[CELLS,REPLAY,STATE]`."""
    shape = f"f32[{CELLS},{REPLAY},{STATE}]"
    return [(m.group(1), m.group(3)) for m in map(_INSTR.match,
                                                  hlo.splitlines())
            if m and shape in m.group(2)]


def test_agent_step_writes_replay_in_place():
    """The compiled agent step (scan + any-cell cond, masks from the input)
    holds no copy and no select of the whole batched replay, fused or not:
    the push is a row write into the carried buffer."""
    hlo = jax.jit(lambda a, x: _scan(engine._invoke_agent, a, x),
                  donate_argnums=0       # as the engine donates its agents
                  ).lower(_agents(), _inputs()).compile().as_text()
    ops = _replay_ops(hlo)
    assert any(op == "scatter" for _, op in ops), ops
    assert [(n, op) for n, op in ops
            if op in ("copy", "copy-start", "select")
            or "select" in n or "copy" in n] == []
