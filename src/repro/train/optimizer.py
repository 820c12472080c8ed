"""Optimizers in pure JAX (no optax dependency).

Provides:
  - adamw(lr, ...)            -> standard AdamW with optional cosine schedule
  - quantized_adamw(...)      -> AdamW with int8 blockwise-quantized moments
                                 (distributed-optimization trick: 4x optimizer-state
                                 memory reduction, needed to fit jamba-398B per-chip HBM)
  - sgd(lr)                   -> plain SGD (used by tests)

All optimizers follow the (init_fn, update_fn) protocol:
    state = init_fn(params)
    new_params, new_state = update_fn(grads, state, params, step)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

PyTree = Any


class Optimizer(NamedTuple):
    init: Callable[[PyTree], PyTree]
    update: Callable[[PyTree, PyTree, PyTree, jnp.ndarray], tuple[PyTree, PyTree]]


# ---------------------------------------------------------------------------
# Schedules
# ---------------------------------------------------------------------------

def constant_schedule(lr: float) -> Callable[[jnp.ndarray], jnp.ndarray]:
    return lambda step: jnp.asarray(lr, jnp.float32)


def cosine_schedule(lr: float, warmup: int, total: int, min_frac: float = 0.1):
    def sched(step):
        step = step.astype(jnp.float32)
        warm = lr * jnp.minimum(step / max(warmup, 1), 1.0)
        prog = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = lr * (min_frac + (1 - min_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
        return jnp.where(step < warmup, warm, cos)

    return sched


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------

def adamw(
    lr: float | Callable = 1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    grad_clip: float = 0.0,
    leaf_sum: Callable[[jnp.ndarray], jnp.ndarray] = jnp.sum,
) -> Optimizer:
    """`leaf_sum` reduces each squared gradient leaf for the clip's global
    norm (see `global_norm`)."""
    sched = lr if callable(lr) else constant_schedule(lr)

    def init(params):
        zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
        return {"m": jax.tree.map(zeros, params), "v": jax.tree.map(zeros, params)}

    def update(grads, state, params, step):
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        if grad_clip > 0:
            gnorm = global_norm(grads, leaf_sum)
            scale = jnp.minimum(1.0, grad_clip / (gnorm + 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)
        t = step.astype(jnp.float32) + 1.0
        lr_t = sched(step)
        bc1 = 1 - b1 ** t
        bc2 = 1 - b2 ** t

        def upd(p, g, m, v):
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mh = m / bc1
            vh = v / bc2
            delta = mh / (jnp.sqrt(vh) + eps)
            if weight_decay:
                delta = delta + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr_t * delta).astype(p.dtype), m, v

        out = jax.tree.map(upd, params, grads, state["m"], state["v"])
        leaves, treedef = jax.tree.flatten(out, is_leaf=lambda x: isinstance(x, tuple))
        new_p = jax.tree.unflatten(treedef, [l[0] for l in leaves])
        new_m = jax.tree.unflatten(treedef, [l[1] for l in leaves])
        new_v = jax.tree.unflatten(treedef, [l[2] for l in leaves])
        return new_p, {"m": new_m, "v": new_v}

    return Optimizer(init, update)


# ---------------------------------------------------------------------------
# Int8 blockwise-quantized AdamW (optimizer-state compression)
# ---------------------------------------------------------------------------

_QBLOCK = 256


def quantizable(shape) -> bool:
    """Blockwise-int8 eligible: last dim divisible by the block size. The
    last-dim split is a *local* reshape, so sharding on every other dim is
    preserved under SPMD (a flatten+pad would force replicated intermediates)."""
    return len(shape) >= 1 and shape[-1] % _QBLOCK == 0


def _q8(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Blockwise symmetric int8 quantization along the last dim.
    x: (..., F) -> q (..., F/B, B) int8, scale (..., F/B) f32."""
    F = x.shape[-1]
    xb = x.reshape(*x.shape[:-1], F // _QBLOCK, _QBLOCK)
    scale = jnp.max(jnp.abs(xb), axis=-1) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(xb / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dq8(q: jnp.ndarray, scale: jnp.ndarray, shape) -> jnp.ndarray:
    x = q.astype(jnp.float32) * scale[..., None]
    return x.reshape(shape)


_VLOG_FLOOR = 1e-16


def _q8_log(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Blockwise asymmetric int8 quantization in log space, for the
    non-negative second moment: symmetric linear quantization would zero out
    small entries and explode 1/sqrt(v) steps. x: (..., F) >= 0."""
    F = x.shape[-1]
    lx = jnp.log(x.reshape(*x.shape[:-1], F // _QBLOCK, _QBLOCK)
                 + _VLOG_FLOOR)
    lo = jnp.min(lx, axis=-1)
    hi = jnp.max(lx, axis=-1)
    scale = (hi - lo) / 254.0 + 1e-12
    q = jnp.clip(jnp.round((lx - lo[..., None]) / scale[..., None]) - 127,
                 -127, 127).astype(jnp.int8)
    return q, lo.astype(jnp.float32), scale.astype(jnp.float32)


def _dq8_log(q, lo, scale, shape) -> jnp.ndarray:
    lx = (q.astype(jnp.float32) + 127.0) * scale[..., None] + lo[..., None]
    return (jnp.exp(lx) - _VLOG_FLOOR).clip(min=0.0).reshape(shape)


def quantized_adamw(
    lr: float | Callable = 1e-3,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    grad_clip: float = 0.0,
) -> Optimizer:
    """AdamW whose m/v moments are stored as blockwise int8 (+fp32 scales).

    State per tensor: {mq, ms, vq, vs} when the last dim divides the block
    size, else plain fp32 {m, v} (small leaves). Dequantize -> update ->
    requantize each step; error bounded by the per-block scale (<= 0.8%
    relative), standard 8-bit-optimizer behaviour.
    """
    sched = lr if callable(lr) else constant_schedule(lr)

    def init(params):
        def one(p):
            if quantizable(p.shape):
                q, s = _q8(jnp.zeros(p.shape, jnp.float32))
                vq, vlo, vsc = _q8_log(jnp.zeros(p.shape, jnp.float32))
                return {"mq": q, "ms": s, "vq": vq, "v_lo": vlo, "v_sc": vsc}
            z = jnp.zeros(p.shape, jnp.float32)
            return {"m": z, "v": z}

        return jax.tree.map(one, params)

    def update(grads, state, params, step):
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        if grad_clip > 0:
            gnorm = global_norm(grads)
            scale = jnp.minimum(1.0, grad_clip / (gnorm + 1e-9))
            grads = jax.tree.map(lambda g: g * scale, grads)
        t = step.astype(jnp.float32) + 1.0
        lr_t = sched(step)
        bc1 = 1 - b1 ** t
        bc2 = 1 - b2 ** t

        def upd(p, g, st):
            quant = "mq" in st
            if quant:
                m = _dq8(st["mq"], st["ms"], p.shape)
                v = _dq8_log(st["vq"], st["v_lo"], st["v_sc"], p.shape)
            else:
                m, v = st["m"], st["v"]
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            delta = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            if weight_decay:
                delta = delta + weight_decay * p.astype(jnp.float32)
            newp = (p.astype(jnp.float32) - lr_t * delta).astype(p.dtype)
            if quant:
                mq, ms = _q8(m)
                vq, vlo, vsc = _q8_log(v)
                return newp, {"mq": mq, "ms": ms, "vq": vq, "v_lo": vlo,
                              "v_sc": vsc}
            return newp, {"m": m, "v": v}

        out = jax.tree.map(upd, params, grads, state,
                           is_leaf=lambda x: isinstance(x, dict) and
                           ("mq" in x or "m" in x))
        # out mirrors params-tree with (newp, newstate) tuples at leaves
        leaves, treedef = jax.tree.flatten(out, is_leaf=lambda x: isinstance(x, tuple))
        new_p = jax.tree.unflatten(treedef, [l[0] for l in leaves])
        new_s = jax.tree.unflatten(treedef, [l[1] for l in leaves])
        return new_p, new_s

    return Optimizer(init, update)


def sgd(lr: float = 1e-2) -> Optimizer:
    def init(params):
        return {}

    def update(grads, state, params, step):
        new_p = jax.tree.map(lambda p, g: (p - lr * g.astype(p.dtype)).astype(p.dtype), params, grads)
        return new_p, state

    return Optimizer(init, update)


def global_norm(tree: PyTree, leaf_sum: Callable[[jnp.ndarray], jnp.ndarray]
                = jnp.sum) -> jnp.ndarray:
    """L2 norm over every leaf: `leaf_sum` of each squared leaf, added in
    leaf order."""
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(leaf_sum(jnp.square(l.astype(jnp.float32))) for l in leaves))
