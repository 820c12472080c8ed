"""Pallas TPU kernel: Mamba2 SSD chunked scan.

Grid = (batch, n_chunks): the chunk axis iterates sequentially ('arbitrary')
carrying the inter-chunk state R (H, N, P) in VMEM scratch — the recurrence
never round-trips HBM. Each grid step computes, for one (batch, chunk):

  seg      = cumsum(dt * A) within the chunk                (Q, H)
  intra    : (C B^T ⊙ decay ⊙ dt) X  via two MXU contractions per head block
  inter    : C · R ⊙ exp(seg)
  state    : R <- exp(seg_end) R + sum_j exp(seg_end - seg_j) B_j (dt_j X_j)

The per-head decay tensor lives only at (Q, Q, Hb) block granularity in VMEM
(head-blocked to bound the working set); Q=chunk and head_block are chosen so
Q*Q*Hb*4B stays << VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, r_scr, *,
                chunk: int, n_heads: int, d_state: int, head_dim: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        r_scr[...] = jnp.zeros_like(r_scr)

    x = x_ref[0].astype(jnp.float32)          # (Q, H, P)
    B = b_ref[0].astype(jnp.float32)          # (Q, N)
    C = c_ref[0].astype(jnp.float32)          # (Q, N)
    dt = dt_ref[0].astype(jnp.float32)        # (Q, H)
    A = a_ref[...].astype(jnp.float32)        # (H,)

    dA = dt * A                               # (Q, H)
    seg = jnp.cumsum(dA, axis=0)
    seg_end = seg[-1:]                        # (1, H)

    CB = jnp.dot(C, B.T, preferred_element_type=jnp.float32)   # (Q, Q)
    Q = chunk
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tril = qi >= kj

    # decay (Q, Q, H) = exp(seg_i - seg_j); built per full head dim here —
    # head-blocking happens at the pallas grid level via vmap on H groups in
    # ops.py when H*Q*Q*4B would exceed VMEM.
    decay = jnp.exp(jnp.clip(seg[:, None, :] - seg[None, :, :], -60.0, 0.0))
    att = CB[:, :, None] * decay * jnp.where(tril[:, :, None], 1.0, 0.0)
    att = att * dt[None, :, :]                                  # weight dt_j
    y_intra = jnp.einsum("ijh,jhp->ihp", att, x)

    R = r_scr[...]                                              # (H, N, P)
    in_decay = jnp.exp(jnp.clip(seg, -60.0, 0.0))               # (Q, H)
    y_inter = jnp.einsum("in,ih,hnp->ihp", C, in_decay, R)

    state_w = jnp.exp(jnp.clip(seg_end - seg, -60.0, 0.0)) * dt  # (Q, H)
    S_new = jnp.einsum("jn,jh,jhp->hnp", B, state_w, x)
    r_scr[...] = R * jnp.exp(jnp.clip(seg_end[0], -60.0, 0.0))[:, None, None] \
        + S_new

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, b, c, dt, a, *, chunk: int = 128, interpret: bool = False):
    """x: (B, L, H, P); b,c: (B, L, N); dt: (B, L, H); a: (H,) (negative).

    Returns y: (B, L, H, P). L % chunk == 0.
    """
    Bsz, L, H, P = x.shape
    N = b.shape[-1]
    assert L % chunk == 0, (L, chunk)
    nc = L // chunk
    grid = (Bsz, nc)
    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_heads=H,
                               d_state=N, head_dim=P)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, H, P), lambda bi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, chunk, N), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, H), lambda bi, ci: (bi, ci, 0)),
            pl.BlockSpec((H,), lambda bi, ci: (0,)),
        ],
        out_specs=pl.BlockSpec((1, chunk, H, P), lambda bi, ci: (bi, ci, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz, L, H, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((H, N, P), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(x, b, c, dt, a)
