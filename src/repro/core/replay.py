"""Experience replay buffer (paper §4.3 / §5.2 "replay buffer").

A fixed-capacity ring buffer of (s, a, r, s2, done) transitions held in plain
jnp arrays, so it can be carried through `jax.lax.scan` and updated with pure
functional ops. Sampling is uniform with a validity mask for the not-yet-full
case (the TD loss masks invalid rows).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class ReplayBuffer(NamedTuple):
    s: jnp.ndarray        # (cap, state_dim) f32
    a: jnp.ndarray        # (cap,) i32
    r: jnp.ndarray        # (cap,) f32
    s2: jnp.ndarray       # (cap, state_dim) f32
    done: jnp.ndarray     # (cap,) f32
    ptr: jnp.ndarray      # () i32
    size: jnp.ndarray     # () i32


def init_replay(capacity: int, state_dim: int) -> ReplayBuffer:
    return ReplayBuffer(
        s=jnp.zeros((capacity, state_dim), jnp.float32),
        a=jnp.zeros((capacity,), jnp.int32),
        r=jnp.zeros((capacity,), jnp.float32),
        s2=jnp.zeros((capacity, state_dim), jnp.float32),
        done=jnp.zeros((capacity,), jnp.float32),
        ptr=jnp.zeros((), jnp.int32),
        size=jnp.zeros((), jnp.int32),
    )


def push(buf: ReplayBuffer, s, a, r, s2, done, where=True) -> ReplayBuffer:
    """Write one transition at `ptr` and advance the ring.  Where `where` is
    false the row at `ptr` is written back with its own contents and `ptr`
    and `size` are kept: the buffer is unchanged bit for bit, and a batched
    caller writes one row per cell in place instead of selecting between two
    whole buffers."""
    cap = buf.s.shape[0]
    i = buf.ptr
    where = jnp.asarray(where, jnp.bool_)

    def put(col, x):
        x = jnp.asarray(x).astype(col.dtype)
        return col.at[i].set(jnp.where(where, x, col[i]))

    return ReplayBuffer(
        s=put(buf.s, s),
        a=put(buf.a, a),
        r=put(buf.r, r),
        s2=put(buf.s2, s2),
        done=put(buf.done, done),
        ptr=jnp.where(where, (i + 1) % cap, i),
        size=jnp.where(where, jnp.minimum(buf.size + 1, cap), buf.size),
    )


def sample(buf: ReplayBuffer, rng: jax.Array, batch_size: int) -> dict:
    """Uniform sample with validity weights; safe when buffer is near-empty."""
    hi = jnp.maximum(buf.size, 1)
    idx = jax.random.randint(rng, (batch_size,), 0, hi)
    # Every drawn index is < size, so all rows are valid as soon as the buffer
    # is non-empty; an empty buffer masks the whole batch.
    w = jnp.where(buf.size > 0, jnp.ones((batch_size,), jnp.float32),
                  jnp.zeros((batch_size,), jnp.float32))
    return {
        "s": buf.s[idx],
        "a": buf.a[idx],
        "r": buf.r[idx],
        "s2": buf.s2[idx],
        "done": buf.done[idx],
        "w": w,
    }
