"""Unit tests: dueling DQN + replay buffer + agent learning."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import agent as A
from repro.core import dqn
from repro.core.agent import AgentConfig, init_agent
from repro.core.dqn import DQNConfig
from repro.core.replay import init_replay, push, sample


def test_q_values_shapes():
    cfg = DQNConfig(state_dim=12, n_actions=5)
    params = dqn.init_params(jax.random.PRNGKey(0), cfg)
    q1 = dqn.q_values(params, jnp.zeros(12), cfg)
    qb = dqn.q_values(params, jnp.zeros((7, 12)), cfg)
    assert q1.shape == (5,) and qb.shape == (7, 5)
    assert jnp.isfinite(q1).all()


def test_dueling_identity():
    """Q = V + A - mean(A): mean over actions of (Q - V) must be ~0."""
    cfg = DQNConfig(state_dim=6, n_actions=4)
    params = dqn.init_params(jax.random.PRNGKey(1), cfg)
    s = jax.random.normal(jax.random.PRNGKey(2), (3, 6))
    q = dqn.q_values(params, s, cfg)
    x = jnp.maximum(s @ params["w0"] + params["b0"], 0)
    x = jnp.maximum(x @ params["w1"] + params["b1"], 0)
    v = x @ params["w_v"] + params["b_v"]
    np.testing.assert_allclose(np.asarray(jnp.mean(q - v, axis=-1)), 0.0,
                               atol=1e-5)


def test_replay_ring_semantics():
    buf = init_replay(4, 3)
    for i in range(6):
        buf = push(buf, jnp.full(3, i, jnp.float32), i, float(i),
                   jnp.zeros(3), 0.0)
    assert int(buf.size) == 4
    assert int(buf.ptr) == 2
    # oldest entries overwritten: buffer holds 2..5
    assert set(np.asarray(buf.a).tolist()) == {2, 3, 4, 5}


def test_replay_sample_masks_empty():
    buf = init_replay(8, 3)
    batch = sample(buf, jax.random.PRNGKey(0), 4)
    assert float(batch["w"].sum()) == 0.0
    buf = push(buf, jnp.ones(3), 1, 1.0, jnp.ones(3), 0.0)
    batch = sample(buf, jax.random.PRNGKey(0), 4)
    assert float(batch["w"].sum()) == 4.0


def test_agent_learns_contextual_bandit():
    cfg = AgentConfig(dqn=DQNConfig(state_dim=8, n_actions=8, gamma=0.0))
    ag = init_agent(jax.random.PRNGKey(0), cfg)
    key = jax.random.PRNGKey(1)

    def step(carry, _):
        ag, key, s_prev, a_prev, r_prev = carry
        key, k = jax.random.split(key)
        ctx = jax.random.bernoulli(k)
        s = jnp.where(ctx, jnp.ones(8), -jnp.ones(8))
        ag = A.observe(ag, s_prev, a_prev, r_prev, s)
        ag = A.train(ag, cfg)
        a, ag = A.act(ag, cfg, s)
        r = jnp.where(a == jnp.where(ctx, 5, 3), 1.0, -1.0)
        return (ag, key, s, a, r), r

    carry = (ag, key, jnp.zeros(8), jnp.zeros((), jnp.int32), jnp.zeros(()))
    carry, rews = jax.lax.scan(jax.jit(step), carry, None, length=500)
    late = np.asarray(rews)[-100:]
    assert late.mean() > 0.7, late.mean()


def test_train_step_noop_until_replay_ready():
    """Pre-`min_replay` the TD step must be an exact no-op (this is what lets
    the engine skip it under lax.cond)."""
    cfg = AgentConfig(dqn=DQNConfig(state_dim=4, n_actions=2), min_replay=8)
    ag = init_agent(jax.random.PRNGKey(0), cfg)
    ag = A.observe(ag, jnp.ones(4), 0, 1.0, jnp.ones(4))
    assert not bool(A.replay_ready(ag, cfg))
    out = A.train_step(ag, cfg, jax.random.PRNGKey(9))
    for a, b in zip(jax.tree.leaves(ag), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_target_sync_periodic():
    cfg = AgentConfig(dqn=DQNConfig(state_dim=4, n_actions=2, target_sync=4),
                      min_replay=1)
    ag = init_agent(jax.random.PRNGKey(0), cfg)
    ag = A.observe(ag, jnp.ones(4), 0, 1.0, jnp.ones(4))
    for i in range(3):
        ag = A.train(ag, cfg)
    # after 3 updates online != target
    d = sum(float(jnp.abs(a - b).sum()) for a, b in
            zip(jax.tree.leaves(ag.params), jax.tree.leaves(ag.target_params)))
    assert d > 0
    ag = A.train(ag, cfg)   # 4th -> sync
    d = sum(float(jnp.abs(a - b).sum()) for a, b in
            zip(jax.tree.leaves(ag.params), jax.tree.leaves(ag.target_params)))
    assert d == 0.0


def test_order_fixed_layers_match_xla_and_their_gradients():
    """`dense`, `dueling_head` and `tree_sum` compute what matmul, the
    dueling mean and sum compute (to rounding), gradients included: their
    custom VJPs must be the true derivatives."""
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(k[0], (5, 7))
    w = jax.random.normal(k[1], (7, 3))
    b = jax.random.normal(k[2], (3,))
    g = jax.random.normal(k[3], (5, 3))

    def ours(x, w, b):
        z = dqn.dense(x, w, b)
        return jnp.vdot(dqn.dueling_head(z[:, :1], z), g) + dqn.tree_sum(x, 0)[1]

    def ref(x, w, b):
        z = x @ w + b
        q = z[:, :1] + z - jnp.mean(z, axis=-1, keepdims=True)
        return jnp.vdot(q, g) + jnp.sum(x, 0)[1]

    np.testing.assert_allclose(ours(x, w, b), ref(x, w, b), rtol=1e-5)
    got = jax.grad(ours, argnums=(0, 1, 2))(x, w, b)
    want = jax.grad(ref, argnums=(0, 1, 2))(x, w, b)
    for a, e in zip(got, want):
        np.testing.assert_allclose(a, e, rtol=1e-5, atol=1e-6)


def test_td_step_bits_do_not_depend_on_batch_width():
    """A cell's TD step (loss, gradients, clip, Adam) gives the same bits
    vmapped alone and among other cells: the serial reference and the
    batched sweep must learn identical agents."""
    cfg = AgentConfig(dqn=DQNConfig(state_dim=10, n_actions=4), min_replay=4)

    def trained(seed):
        ag = A.cold_start(seed, cfg)
        for i in range(12):
            k = jax.random.fold_in(jax.random.PRNGKey(seed + 100), i)
            ks = jax.random.split(k, 3)
            ag = A.observe(ag, jax.random.normal(ks[0], (10,)), i % 4,
                           jax.random.normal(ks[1], ()),
                           jax.random.normal(ks[2], (10,)))
        return A.train_step(ag, cfg, jax.random.PRNGKey(seed + 7)).params

    f = jax.jit(jax.vmap(trained))
    wide = f(jnp.arange(8))
    for j in (0, 5):
        alone = f(jnp.arange(j, j + 1))
        for a, b in zip(jax.tree.leaves(wide), jax.tree.leaves(alone)):
            np.testing.assert_array_equal(np.asarray(a)[j], np.asarray(b)[0])


@pytest.mark.parametrize("width", [1, 2, 7, 21])
def test_act_q_bits_do_not_depend_on_batch_width(width):
    """The act path's Q-values (one state per cell, paper widths) give the
    same bits for a cell vmapped among `width` cells as among 27, with the
    weights entering the program as inputs, as the sweep's scan carry holds
    them."""
    from repro.configs.aimm_nmp import PAPER_4X4
    from repro.nmp.engine import default_agent_cfg
    cfg = default_agent_cfg(PAPER_4X4)
    seeds = jnp.arange(27)
    params = jax.vmap(lambda s: A.cold_start(s, cfg).params)(seeds)
    states = jax.random.normal(jax.random.PRNGKey(3),
                               (27, cfg.dqn.state_dim))
    f = jax.jit(jax.vmap(lambda p, s: dqn.q_values(p, s, cfg.dqn)))
    wide = np.asarray(f(params, states))
    for j in (0, 27 - width):
        cut = lambda t: jax.tree.map(lambda a: a[j:j + width], t)
        np.testing.assert_array_equal(np.asarray(f(cut(params), cut(states))),
                                      wide[j:j + width])


@pytest.mark.parametrize("leaf_sum", ["jnp", "tree"])
def test_adamw_clip_bounds_global_norm(leaf_sum):
    """`adamw`'s clip scales gradients to `grad_clip` global norm whichever
    per-leaf sum it is given (the agent passes `dqn.tree_sum`)."""
    from repro.train.optimizer import adamw, global_norm
    fn = jnp.sum if leaf_sum == "jnp" else (
        lambda g: dqn.tree_sum(g.reshape(-1), 0))
    k = jax.random.split(jax.random.PRNGKey(4), 2)
    grads = {"w": 10.0 * jax.random.normal(k[0], (37, 5)),
             "b": 10.0 * jax.random.normal(k[1], (5,))}
    np.testing.assert_allclose(global_norm(grads, fn), global_norm(grads),
                               rtol=1e-6)
    params = jax.tree.map(jnp.zeros_like, grads)
    opt = adamw(1.0, grad_clip=0.5, leaf_sum=fn)
    _, st = opt.update(grads, opt.init(params), params, jnp.int32(0))
    # Adam's first moment holds (1 - b1) * clipped grads.
    np.testing.assert_allclose(global_norm(st["m"]), 0.1 * 0.5, rtol=1e-5)
    unclipped = adamw(1.0).update(grads, opt.init(params), params,
                                  jnp.int32(0))[1]
    np.testing.assert_allclose(global_norm(unclipped["m"]),
                               0.1 * global_norm(grads), rtol=1e-5)


def _kernel_matmul(a, b, jnp_path):
    """`dqn._matmul` sent through the order-fixed kernel in interpret mode,
    as a TPU program on one device runs it."""
    from repro.kernels.order_fixed_dense.kernel import order_fixed_matmul
    return order_fixed_matmul(a, b, interpret=True)


@pytest.mark.parametrize("rows", [1, 64])
@pytest.mark.parametrize("k,n", [(106, 128), (128, 128), (128, 1), (128, 8)])
def test_order_fixed_kernel_equals_dense(monkeypatch, rows, k, n):
    """The kernel gives `dense`'s bits in the forward pass, `dx` and `dW` at
    the agent's widths (the paper's 106-wide state exercises every padded
    level of the tree: 106 -> 53 -> 54 -> 27 -> 28 -> 14 -> 7 -> 8), signed
    zeros included.  `dense` runs op by op: a jitted CPU program fuses each
    product into the add that takes it, which rounds once, not twice."""
    ks = jax.random.split(jax.random.PRNGKey(rows * 1000 + k + n), 4)
    x = np.array(jax.random.normal(ks[0], (rows, k)))
    w = np.array(jax.random.normal(ks[1], (k, n)))
    b = np.asarray(jax.random.normal(ks[2], (n,)))
    g = np.array(jax.random.normal(ks[3], (rows, n)))
    x[0] = -0.0                 # a row of -0: outputs that sum zeros alone
    x[:, ::5] = -0.0
    w[::7] = -0.0
    g[:, ::3] = -0.0

    def forward_and_vjp(x, w, b, g):
        y, vjp = jax.vjp(dqn.dense, x, w, b)
        return (y,) + vjp(g)

    want = forward_and_vjp(x, w, b, g)
    monkeypatch.setattr(dqn, "_matmul", _kernel_matmul)
    got = jax.jit(forward_and_vjp)(x, w, b, g)
    for name, a, e in zip(("y", "dx", "dw", "db"), got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(e), name)
        np.testing.assert_array_equal(np.signbit(a), np.signbit(e), name)


def test_kernel_td_step_bits_do_not_depend_on_batch_width(monkeypatch):
    """Through the order-fixed kernel, a cell's TD step gives the same bits
    vmapped alone and among 27 cells."""
    monkeypatch.setattr(dqn, "_matmul", _kernel_matmul)
    cfg = AgentConfig(dqn=DQNConfig(state_dim=10, n_actions=4), min_replay=4)

    def trained(seed):
        ag = A.cold_start(seed, cfg)
        for i in range(12):
            k = jax.random.fold_in(jax.random.PRNGKey(seed + 100), i)
            ks = jax.random.split(k, 3)
            ag = A.observe(ag, jax.random.normal(ks[0], (10,)), i % 4,
                           jax.random.normal(ks[1], ()),
                           jax.random.normal(ks[2], (10,)))
        return A.train_step(ag, cfg, jax.random.PRNGKey(seed + 7)).params

    f = jax.jit(jax.vmap(trained))
    wide = f(jnp.arange(27))
    for j in (0, 26):
        alone = f(jnp.arange(j, j + 1))
        for a, b in zip(jax.tree.leaves(wide), jax.tree.leaves(alone)):
            np.testing.assert_array_equal(np.asarray(a)[j], np.asarray(b)[0])
