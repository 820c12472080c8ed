"""Pallas TPU kernel: blocked causal GQA flash attention (prefill hot-spot).

Grid = (batch*q_heads, S/BLOCK_Q, S/BLOCK_KV); the last axis iterates
sequentially ('arbitrary' semantics) carrying the online-softmax state
(m, l, acc) in VMEM scratch. Causal skipping: KV blocks strictly above the
diagonal write nothing (pl.when guard), so wasted MXU work is at most the
diagonal block — unlike the XLA-scan fallback which computes the full S^2.

Block sizes default to 128/256: q/k tiles of (128, head_dim) with
head_dim in {64,128,256} keep the MXU's 128x128 systolic array fed while the
per-step working set (q tile + kv tile + logits tile ~ 128*256*4B) stays well
under VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 128
BLOCK_KV = 256
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_kv: int, causal: bool):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = (not causal) or (kj * block_kv <= (qi + 1) * block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                    # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                    # (bkv, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            kpos = kj * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * corr
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(kj == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "causal", "block_q", "block_kv",
                                    "interpret"))
def flash_attention(q, k, v, *, scale: float | None = None,
                    causal: bool = True, block_q: int = BLOCK_Q,
                    block_kv: int = BLOCK_KV, interpret: bool = False):
    """q: (B, H, S, hd); k/v: (B, H, S, hd) (kv already GQA-expanded or H==K).

    Returns (B, H, S, hd).
    """
    B, H, S, hd = q.shape
    assert S % block_q == 0 and S % block_kv == 0, (S, block_q, block_kv)
    scale = hd ** -0.5 if scale is None else scale
    qf = q.reshape(B * H, S, hd)
    kf = k.reshape(B * H, S, hd)
    vf = v.reshape(B * H, S, hd)
    grid = (B * H, S // block_q, S // block_kv)
    kernel = functools.partial(_flash_kernel, scale=scale, block_q=block_q,
                               block_kv=block_kv, causal=causal)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd)
