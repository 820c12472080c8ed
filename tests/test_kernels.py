"""Per-kernel validation: shape/dtype sweeps + hypothesis, allclose vs the
pure-jnp oracles (interpret mode executes the kernel body on CPU), and the
agent's Q-network against a float64 NumPy oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import dqn
from repro.kernels.flash_attention.ops import gqa_flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd_scan.ops import ssd
from repro.kernels.ssd_scan.ref import ssd_ref


def _rand(key, *shape, dtype=jnp.float32, scale=0.5):
    return (jax.random.normal(jax.random.PRNGKey(key), shape) * scale
            ).astype(dtype)


# ---------------------------------------------------------------------------
# dueling Q-network (`dqn.q_values`, the agent's forward pass)
# ---------------------------------------------------------------------------

def _dueling_oracle(x, p):
    """Q = V + A - mean_a A through two ReLU layers, in float64 NumPy."""
    f = {k: np.asarray(v, np.float64) for k, v in p.items()}
    h = np.maximum(np.asarray(x, np.float64) @ f["w0"] + f["b0"], 0.0)
    h = np.maximum(h @ f["w1"] + f["b1"], 0.0)
    v = h @ f["w_v"] + f["b_v"]
    a = h @ f["w_a"] + f["b_a"]
    return v + a - a.mean(axis=-1, keepdims=True)


_q_values = jax.jit(dqn.q_values, static_argnames="cfg")


def _qnet_params(key, state_dim, hidden, actions):
    shapes = {"w0": (state_dim, hidden), "b0": (hidden,),
              "w1": (hidden, hidden), "b1": (hidden,),
              "w_v": (hidden, 1), "b_v": (1,),
              "w_a": (hidden, actions), "b_a": (actions,)}
    return {k: _rand(key + i, *s) for i, (k, s) in enumerate(shapes.items())}


# 106 is the 4x4 paper mesh agent's state width, 298 the 8x8 one's.
@pytest.mark.parametrize("batch", [1, 37, 128, 300])
@pytest.mark.parametrize("state_dim", [64, 106, 298])
def test_qnet_shapes(batch, state_dim):
    H, A = 128, 8
    cfg = dqn.DQNConfig(state_dim=state_dim, n_actions=A, hidden=(H, H))
    params = _qnet_params(0, state_dim, H, A)
    x = _rand(8, batch, state_dim)
    got = _q_values(params, x, cfg)
    assert got.shape == (batch, A) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), _dueling_oracle(x, params),
                               rtol=1e-4, atol=1e-4)


@settings(deadline=None, max_examples=10)
@given(st.integers(1, 64), st.integers(2, 12))
def test_qnet_hypothesis(batch, actions):
    S, H = 32, 64
    cfg = dqn.DQNConfig(state_dim=S, n_actions=actions, hidden=(H, H))
    params = _qnet_params(10, S, H, actions)
    x = _rand(18, batch, S)
    got = _q_values(params, x, cfg)
    np.testing.assert_allclose(np.asarray(got), _dueling_oracle(x, params),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S", [128, 256, 384])
@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, hd, dtype):
    B, H, K = 2, 4, 2
    q = _rand(0, B, S, H, hd, dtype=dtype)
    k = _rand(1, B, S, K, hd, dtype=dtype)
    v = _rand(2, B, S, K, hd, dtype=dtype)
    got = gqa_flash_attention(q, k, v, causal=True)
    kk = jnp.repeat(k, H // K, axis=2)
    vv = jnp.repeat(v, H // K, axis=2)
    want = attention_ref(q.transpose(0, 2, 1, 3), kk.transpose(0, 2, 1, 3),
                         vv.transpose(0, 2, 1, 3)).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_noncausal():
    B, S, H, hd = 1, 256, 2, 64
    q = _rand(3, B, S, H, hd)
    k = _rand(4, B, S, H, hd)
    v = _rand(5, B, S, H, hd)
    got = gqa_flash_attention(q, k, v, causal=False)
    want = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                         v.transpose(0, 2, 1, 3),
                         causal=False).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ssd scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("L,chunk", [(64, 32), (128, 128), (256, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_sweep(L, chunk, dtype):
    B, H, P, N = 2, 4, 16, 8
    x = _rand(0, B, L, H, P, dtype=dtype)
    b = _rand(1, B, L, N, dtype=dtype)
    c = _rand(2, B, L, N, dtype=dtype)
    dt = jnp.abs(_rand(3, B, L, H)) * 0.1
    a = -jnp.abs(_rand(4, H)) - 0.1
    got = ssd(x, b, c, dt, a, chunk=chunk)
    want = ssd_ref(x, b, c, dt, a)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@settings(deadline=None, max_examples=8)
@given(st.integers(1, 3), st.integers(1, 4))
def test_ssd_hypothesis(B, nheads):
    L, P, N, chunk = 64, 8, 4, 32
    x = _rand(20, B, L, nheads, P)
    b = _rand(21, B, L, N)
    c = _rand(22, B, L, N)
    dt = jnp.abs(_rand(23, B, L, nheads)) * 0.2
    a = -jnp.abs(_rand(24, nheads)) - 0.05
    got = ssd(x, b, c, dt, a, chunk=chunk)
    want = ssd_ref(x, b, c, dt, a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)


def test_ssd_head_group_split():
    """Force the VMEM head-group split path."""
    import repro.kernels.ssd_scan.ops as ops
    B, L, H, P, N = 1, 64, 8, 8, 4
    x = _rand(30, B, L, H, P)
    b = _rand(31, B, L, N)
    c = _rand(32, B, L, N)
    dt = jnp.abs(_rand(33, B, L, H)) * 0.1
    a = -jnp.abs(_rand(34, H)) - 0.1
    old = ops.VMEM_BUDGET
    try:
        ops.VMEM_BUDGET = 64 * 64 * 4 * 2       # forces hg < H
        got = ops.ssd(x, b, c, dt, a, chunk=64)
    finally:
        ops.VMEM_BUDGET = old
    want = ssd_ref(x, b, c, dt, a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_model_attention_matches_kernel():
    """The model's scan-based chunked attention and the Pallas kernel agree."""
    from repro.models.attention import attend_chunked
    B, S, H, hd = 1, 1024, 2, 64
    q = _rand(40, B, S, H, hd)
    k = _rand(41, B, S, H, hd)
    v = _rand(42, B, S, H, hd)
    a = attend_chunked(q, k, v, "causal", 0, hd ** -0.5)
    b_ = gqa_flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-4,
                               atol=2e-4)
