"""Pallas kernel for the fused epoch core.

One `pl.pallas_call` covers the epoch simulation core: the seed-invariant
shared stage (row-buffer stamp-and-count, PEI top_k threshold + hot flags,
access-EMA update, touch counts) and/or the schedule/route/count stage
(effective-table gathers, technique + AIMM-remap scheduling, one-hot-matmul
link loads and per-cube counts against the topology's pair-flattened
`routes_flat`/`hops_flat` layouts).  Stage selection is static
(`run_shared`/`run_route`), mirroring `BodyFlags`: the seed-shared epoch
driver calls the shared stage once per lane and the route stage once per
seed cell, while the unshared path fuses both into a single call.

Batching contract: the wrappers are written for ONE lane/cell (no leading
batch axis).  `pl.pallas_call` registers a vmap batching rule, so the
engine's per-lane `jax.vmap` / nested (lane, seed) vmap batches the kernel
by adding grid dimensions — no kernel-side BlockSpecs are needed, and
trace-time-constant operands (topology tensors) ride along unbatched.

The kernel body executes the exact same stage functions as the jnp dispatch
path (`ref.shared_stage` / `ref.route_stage_onehot` / `ref.tom_stage_loop`),
so interpret-mode output is bit-identical to the jnp path on the pinned
engine goldens (tests/test_pallas_parity.py).

The TPU compiler (Mosaic, JAX 0.9.0, compiled for a v5e) refuses this
kernel, so it runs in interpreter mode only and `auto` never selects it:

  * shared stage: `NotImplementedError: Unimplemented primitive in Pallas
    TPU lowering: scatter-max` — the row-buffer stamp `rb_stamp.at[...].max`
    in `ref.shared_stage`;
  * route stage and `tom_scores_call`: `NotImplementedError: Only 2D gather
    is supported` — the P-indexed effective-table and route-row gathers.

A port needs a tiled formulation (scatter via one-hot matmul tiles, a
streaming top-k) — tracked in ROADMAP.md.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.epoch_fused import ref
from repro.kernels.epoch_fused.ref import RouteParts, SharedParts


def _s(x, dtype):
    """Scalar -> (1,)-shaped kernel operand."""
    return jnp.asarray(x, dtype).reshape((1,))


def fused_epoch_call(dest, src1, src2, valid, *,
                     epochs=None, rb_stamp=None, page_ema=None, n_pages=None,
                     pei_idx=None, rb_winner=None, pei_hot1=None,
                     pei_hot2=None, eff_table=None, compute_remap=None,
                     technique=None, is_aimm=None, pending_mig_loads=None,
                     routes_flat=None, hops_flat=None, nearest_mc=None,
                     pei_k: int = 0, aimm: bool = False,
                     run_shared: bool = True, run_route: bool = True,
                     n_mcs: int = 0, packet_flits: float = 0.0,
                     interpret: bool = True
                     ) -> tuple[SharedParts | None, RouteParts | None]:
    """Run the fused epoch core for one lane/cell; see module doc.

    Operand presence follows the static stage/feature flags exactly (like
    `BodyFlags`): compiled-out machinery never even enters the kernel.
    Returns (SharedParts | None, RouteParts | None)."""
    assert run_shared or run_route
    W = dest.shape[0]
    pei = pei_k > 0

    ins: list[tuple[str, jnp.ndarray]] = [
        ("dest", dest), ("src1", src1), ("src2", src2), ("valid", valid)]
    outs: list[tuple[str, tuple, jnp.dtype]] = []
    if run_shared:
        P = rb_stamp.shape[0] - 1
        ins += [("epochs", _s(epochs, jnp.float32)), ("rb_stamp", rb_stamp)]
        if pei:
            ins += [("page_ema", page_ema),
                    ("n_pages", _s(n_pages, jnp.int32)),
                    ("pei_idx", _s(pei_idx, jnp.int32))]
        outs += [("rb_stamp", (P + 1,), jnp.int32),
                 ("rb_winner", (3 * W,), jnp.bool_)]
        if pei:
            outs += [("page_ema", (P,), jnp.float32),
                     ("pei_hot1", (W,), jnp.bool_),
                     ("pei_hot2", (W,), jnp.bool_)]
        if aimm:
            outs += [("touch_cnt", (P,), jnp.float32)]
    elif run_route:
        # Winners (and PEI hot flags) were computed by the per-lane shared
        # call; the per-cell route call takes them as inputs.
        ins += [("rb_winner", rb_winner)]
        if pei:
            ins += [("pei_hot1", pei_hot1), ("pei_hot2", pei_hot2)]
    if run_route:
        C = nearest_mc.shape[0]
        L = pending_mig_loads.shape[0]
        ins += [("eff_table", eff_table),
                ("technique", _s(technique, jnp.int32)),
                ("pending_mig_loads", pending_mig_loads),
                ("routes_flat", routes_flat), ("hops_flat", hops_flat),
                ("nearest_mc", nearest_mc)]
        if aimm:
            ins += [("compute_remap", compute_remap),
                    ("is_aimm", _s(is_aimm, jnp.bool_))]
        outs += [("ccube", (W,), jnp.int32), ("loads", (L,), jnp.float32),
                 ("hops_op", (W,), jnp.float32),
                 ("ops_c", (C,), jnp.float32), ("acc_c", (C,), jnp.float32),
                 ("distinct_c", (C,), jnp.float32),
                 ("mcq", (n_mcs,), jnp.float32)]

    in_names = [n for n, _ in ins]
    out_names = [n for n, _, _ in outs]

    def kernel(*refs):
        v = {n: r[...] for n, r in zip(in_names, refs[:len(in_names)])}
        o: dict[str, jnp.ndarray] = {}
        if run_shared:
            sp = ref.shared_stage(
                v["dest"], v["src1"], v["src2"], v["valid"],
                v["epochs"][0], v["rb_stamp"], v.get("page_ema"),
                v["n_pages"][0] if pei else None,
                v["pei_idx"][0] if pei else None, pei_k=pei_k, aimm=aimm)
            o["rb_stamp"], o["rb_winner"] = sp.rb_stamp, sp.rb_winner
            if pei:
                o["page_ema"] = sp.page_ema
                o["pei_hot1"], o["pei_hot2"] = sp.pei_hot1, sp.pei_hot2
            if aimm:
                o["touch_cnt"] = sp.touch_cnt
            winner, hot1, hot2 = sp.rb_winner, sp.pei_hot1, sp.pei_hot2
        else:
            winner = v.get("rb_winner")
            hot1, hot2 = v.get("pei_hot1"), v.get("pei_hot2")
        if run_route:
            rp = ref.route_stage_onehot(
                v["dest"], v["src1"], v["src2"], v["valid"], winner, hot1,
                hot2, v["eff_table"], v.get("compute_remap"),
                v["technique"][0], v["is_aimm"][0] if aimm else None,
                v["pending_mig_loads"], v["routes_flat"], v["hops_flat"],
                v["nearest_mc"], pei=pei, aimm=aimm, n_mcs=n_mcs,
                packet_flits=packet_flits)
            for name, val in zip(RouteParts._fields, rp):
                o[name] = val
        for n, r in zip(out_names, refs[len(in_names):]):
            r[...] = o[n]

    res = pl.pallas_call(
        kernel,
        out_shape=tuple(jax.ShapeDtypeStruct(s, d) for _, s, d in outs),
        interpret=interpret,
    )(*[a for _, a in ins])
    by_name = dict(zip(out_names, res))

    sparts = rparts = None
    if run_shared:
        sparts = SharedParts(
            rb_stamp=by_name["rb_stamp"], rb_winner=by_name["rb_winner"],
            page_ema=by_name.get("page_ema"),
            pei_hot1=by_name.get("pei_hot1"),
            pei_hot2=by_name.get("pei_hot2"),
            touch_cnt=by_name.get("touch_cnt"))
    if run_route:
        rparts = RouteParts(**{n: by_name[n] for n in RouteParts._fields})
    return sparts, rparts


def tom_scores_call(dest, src1, src2, valid, cands, *, n_cubes: int,
                    interpret: bool = True) -> jnp.ndarray:
    """(K,) TOM candidate scores for one lane's window, as a Pallas call."""
    K = cands.shape[0]

    def kernel(dest_ref, s1_ref, s2_ref, v_ref, c_ref, out_ref):
        out_ref[...] = ref.tom_stage_loop(
            dest_ref[...], s1_ref[...], s2_ref[...], v_ref[...], c_ref[...],
            n_cubes)

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((K,), jnp.float32),
        interpret=interpret,
    )(dest, src1, src2, valid, cands)
