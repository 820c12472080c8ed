"""Dueling (double) deep Q-network in pure JAX (paper §4.3, Fig. 4-3).

The agent's function approximator is a small stack of fully connected layers
with a dueling head:  Q(s, a) = V(s) + A(s, a) - mean_a A(s, a).

Everything here is a pure function over explicit parameter pytrees so the
whole continual-learning loop (simulate -> act -> observe -> train) can live
inside a single `jax.lax.scan`.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any


@dataclasses.dataclass(frozen=True)
class DQNConfig:
    state_dim: int
    n_actions: int = 8
    hidden: tuple[int, ...] = (128, 128)
    dueling: bool = True
    double: bool = True           # double-DQN target (beyond-paper robustness)
    gamma: float = 0.95
    lr: float = 1e-3
    grad_clip: float = 1.0
    target_sync: int = 64         # train steps between target-network syncs
    batch_size: int = 64


def init_params(rng: jax.Array, cfg: DQNConfig) -> PyTree:
    dims = (cfg.state_dim,) + cfg.hidden
    keys = jax.random.split(rng, len(dims) + 2)
    params = {}
    for i in range(len(dims) - 1):
        scale = jnp.sqrt(2.0 / dims[i])
        params[f"w{i}"] = jax.random.normal(keys[i], (dims[i], dims[i + 1]), jnp.float32) * scale
        params[f"b{i}"] = jnp.zeros((dims[i + 1],), jnp.float32)
    h = dims[-1]
    if cfg.dueling:
        params["w_v"] = jax.random.normal(keys[-2], (h, 1), jnp.float32) * jnp.sqrt(1.0 / h)
        params["b_v"] = jnp.zeros((1,), jnp.float32)
        params["w_a"] = jax.random.normal(keys[-1], (h, cfg.n_actions), jnp.float32) * jnp.sqrt(1.0 / h)
        params["b_a"] = jnp.zeros((cfg.n_actions,), jnp.float32)
    else:
        params["w_q"] = jax.random.normal(keys[-1], (h, cfg.n_actions), jnp.float32) * jnp.sqrt(1.0 / h)
        params["b_q"] = jnp.zeros((cfg.n_actions,), jnp.float32)
    return params


def zeros_params(cfg: DQNConfig) -> PyTree:
    """Zero-filled parameter pytree with `init_params`' exact structure,
    shapes and dtypes, built without an RNG.  This is the restore template
    for checkpointed agents: a fresh process can rebuild the tree skeleton
    and map saved leaves onto it without replaying the init key."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


# ---------------------------------------------------------------------------
# Order-fixed arithmetic
# ---------------------------------------------------------------------------
# XLA picks a matmul algorithm and a reduction order per shape and layout.
# On the TPU the same lane's Q-network therefore computed different bits
# when vmapped alone (the serial reference) and among other lanes (the
# sweep), and learned lanes drifted apart within a few episodes.  Every sum
# the network and its gradients take is instead a fixed binary tree of
# elementwise f32 adds (so is the agent's gradient clip: `core.agent`
# passes `tree_sum` to `adamw`).  Products pass an optimization barrier
# (`_product`) before they are summed, which asks the compiler not to fuse
# a multiply into the adds (a fused multiply-add rounds once, not twice).
# Both are requests to the compiler, not guarantees: the CPU compiler drops
# the barrier and fuses products into their adds, so a jitted CPU program
# rounds them once (op-by-op execution rounds them twice, as the chip does).
# A TPU program on one device runs the three contractions of `dense` in the
# `order_fixed_dense` Pallas kernel, which keeps the products and the tree
# in VMEM and gives the jnp path's bits (`_matmul`).  What was checked: on
# a TPU v5e, act Q and a TD step at vmap widths 1, 2, 7 and 21 equal width
# 27 bit for bit; on the CPU, tests/test_dqn.py pins the same at widths 1,
# 2 and 27 with weights entering the program as inputs, as the sweep's scan
# carry holds them.  A weight computed in the same program as the forward
# may still round differently there (seen on the CPU with the init fused
# into the forward).

def _product(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.optimization_barrier(x * y)


def tree_sum(x: jnp.ndarray, axis: int, keepdims: bool = False
             ) -> jnp.ndarray:
    """Sum over `axis` by pairwise halving (zero-padded to even lengths)."""
    x = jnp.moveaxis(x, axis, 0)
    while x.shape[0] > 1:
        if x.shape[0] % 2:
            x = jnp.concatenate([x, jnp.zeros_like(x[:1])])
        half = x.shape[0] // 2
        x = x[:half] + x[half:]
    return jnp.moveaxis(x, 0, axis) if keepdims else x[0]


def _sharded() -> bool:
    """Whether the program being traced is split over several devices (the
    sweep enters its mesh as the abstract mesh).  GSPMD cannot partition a
    Pallas kernel, so such a program keeps the jnp path, which it splits
    cell by cell."""
    return jax.sharding.get_abstract_mesh().size > 1


def _matmul(a: jnp.ndarray, b: jnp.ndarray, jnp_path) -> jnp.ndarray:
    """a @ b for a (I, R), b (R, J), summed over R by `tree_sum`, as
    `jnp_path()` computes it.  A TPU program on one device computes the
    same bits with the `order_fixed_dense` kernel, which keeps the products
    and the tree in VMEM; the choice is made when the program is lowered
    for a platform."""
    if _sharded():
        return jnp_path()
    # imported here: the kernel takes `tree_sum` from this module
    from repro.kernels.order_fixed_dense.kernel import order_fixed_matmul
    return jax.lax.platform_dependent(a, b, tpu=order_fixed_matmul,
                                      default=lambda a, b: jnp_path())


@jax.custom_vjp
def dense(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """x @ w + b for x (B, K), w (K, N), b (N,), in a fixed summation order
    (its gradients too: the transpose of a broadcast would be an XLA
    reduction)."""
    return _matmul(x, w, lambda: tree_sum(_product(x[:, :, None], w), 1)) + b


def _dense_fwd(x, w, b):
    return dense(x, w, b), (x, w)


def _dense_bwd(res, g):
    x, w = res
    return (_matmul(g, w.T, lambda: tree_sum(_product(g[:, None, :], w), 2)),
            _matmul(x.T, g, lambda: tree_sum(
                _product(x[:, :, None], g[:, None, :]), 0)),
            tree_sum(g, 0))


dense.defvjp(_dense_fwd, _dense_bwd)


@jax.custom_vjp
def dueling_head(v: jnp.ndarray, a: jnp.ndarray) -> jnp.ndarray:
    """Q = V + A - mean_a A for v (B, 1), a (B, A)."""
    return v + (a - tree_sum(a, -1, keepdims=True) / a.shape[-1])


def _dueling_fwd(v, a):
    return dueling_head(v, a), None


def _dueling_bwd(_, g):
    g_sum = tree_sum(g, -1, keepdims=True)
    return g_sum, g - g_sum / g.shape[-1]


dueling_head.defvjp(_dueling_fwd, _dueling_bwd)


def q_values(params: PyTree, state: jnp.ndarray, cfg: DQNConfig) -> jnp.ndarray:
    """Q(s, .) for a single state (state_dim,) or batch (B, state_dim)."""
    squeeze = state.ndim == 1
    x = jnp.atleast_2d(state.astype(jnp.float32))
    i = 0
    while f"w{i}" in params:
        x = jnp.maximum(dense(x, params[f"w{i}"], params[f"b{i}"]), 0.0)
        i += 1
    if cfg.dueling:
        q = dueling_head(dense(x, params["w_v"], params["b_v"]),   # (B, 1)
                         dense(x, params["w_a"], params["b_a"]))   # (B, A)
    else:
        q = dense(x, params["w_q"], params["b_q"])
    return q[0] if squeeze else q


def td_loss(params: PyTree, target_params: PyTree, batch: dict, cfg: DQNConfig) -> jnp.ndarray:
    """Squared TD error (paper eq. 3), double-DQN target if cfg.double.

    Only the Q(s, a) term carries gradients; the target-network values and the
    double-DQN argmax selection are inference (stop_gradient).
    """
    q = q_values(params, batch["s"], cfg)                          # (B, A)
    q_sa = jnp.take_along_axis(q, batch["a"][:, None], axis=1)[:, 0]
    q_next_t = jax.lax.stop_gradient(
        q_values(target_params, batch["s2"], cfg))                 # (B, A)
    if cfg.double:
        q_next_o = jax.lax.stop_gradient(q_values(params, batch["s2"], cfg))
        a_star = jnp.argmax(q_next_o, axis=-1)
        q_next = jnp.take_along_axis(q_next_t, a_star[:, None], axis=1)[:, 0]
    else:
        q_next = jnp.max(q_next_t, axis=-1)
    y = batch["r"] + cfg.gamma * (1.0 - batch["done"]) * q_next
    err = (y - q_sa) * batch["w"]          # `w` masks invalid (not-yet-filled) samples
    return (tree_sum(jnp.square(err), 0)
            / jnp.maximum(tree_sum(batch["w"], 0), 1.0))


def num_params(cfg: DQNConfig) -> int:
    n, prev = 0, cfg.state_dim
    for h in cfg.hidden:
        n += prev * h + h
        prev = h
    n += prev * 1 + 1 + prev * cfg.n_actions + cfg.n_actions
    return n
