"""Pallas TPU kernel: the Q-network's order-fixed contractions in VMEM.

`core.dqn.dense` and its VJP compute each matrix product as elementwise f32
products summed by `dqn.tree_sum`'s fixed halving tree, so that a cell's
bits do not depend on how many cells run beside it.  XLA writes the whole
(rows, depth, cols) product tensor to HBM and reads it back once per level
of the tree.  This kernel keeps the products and every level in VMEM: the
same products, each rounded to f32, added pairwise in the same order, so
the same bits.

One kernel serves the forward pass and both contraction cotangents, which
the caller writes as `order_fixed_matmul(a, b)` on transposed operands:
x @ w, g @ wᵀ and xᵀ @ g.  Per cell (the caller's vmap becomes the grid),
the output is computed eight rows at a time: the products of those rows
are staged in a VMEM scratch with the contraction axis leading and
untiled, so each level of the tree is whole-vreg adds.  Staging them in
memory keeps a multiply from being fused into the add that follows it (a
fused multiply-add rounds once, not twice).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.dqn import tree_sum

ROWS = 8            # output rows per tile: the f32 sublane count


def _kernel(a_ref, b_ref, out_ref, prod_ref):
    """out = a @ b in `tree_sum`'s order; a (I, R), b (R, J), I % ROWS == 0,
    prod_ref (R, ROWS, J) scratch."""
    depth = a_ref.shape[1]

    def tile(t, carry):
        rows = pl.ds(pl.multiple_of(t * ROWS, ROWS), ROWS)
        a = a_ref[rows, :]
        for r in range(depth):
            prod_ref[r] = a[:, r:r + 1] * b_ref[r:r + 1, :]
        out_ref[rows, :] = tree_sum(prod_ref[...], 0)
        return carry

    jax.lax.fori_loop(0, a_ref.shape[0] // ROWS, tile, 0)


def order_fixed_matmul(a: jnp.ndarray, b: jnp.ndarray, *,
                       interpret: bool = False) -> jnp.ndarray:
    """a @ b for a (I, R), b (R, J) float32: out[i, j] is
    `tree_sum(a[i, :] * b[:, j], 0)`, bit for bit.

    The longer output axis goes on the lanes (a narrow output is computed
    transposed, as bᵀ @ aᵀ: the same products, in the same order), and the
    rows are zero-padded to whole tiles; rows are independent, so neither
    changes a bit of the result."""
    if b.shape[1] < a.shape[0]:
        return order_fixed_matmul(b.T, a.T, interpret=interpret).T
    n_rows, depth = a.shape
    cols = b.shape[1]
    a = jnp.pad(a, ((0, -n_rows % ROWS), (0, 0)))
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((a.shape[0], cols), jnp.float32),
        scratch_shapes=[pltpu.VMEM((depth, ROWS, cols), jnp.float32)],
        interpret=interpret,
        name="order_fixed_dense",
    )(a, b)
    return out[:n_rows]
