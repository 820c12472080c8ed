"""The epoch core: the cost model's per-epoch stages as pure functions.

  shared_stage : row-buffer stamp-and-count, PEI top_k threshold + hot
                 flags, access-EMA decay/update, page touch counts -- the
                 seed-invariant half, computed once per lane when the epoch
                 driver shares it across seed replicas.
  route_stage  : effective-table gathers, technique scheduling (incl. PEI
                 hot-source placement and the AIMM compute-remap override),
                 per-link flit loads, hop counts, per-cube compute /
                 access / row-buffer-distinct counts and MC-queue depths.
  tom_stage    : TOM candidate co-location scores for one op window.

Exactness contract: every weight entering a reduction is an exact small
integer (0/1 route incidence, 0/1 validity, integer hop counts) or an exact
small-integer multiple of `packet_flits`, and all sums stay far below
2**24, so the scatter-adds and einsums give the same f32 bits under any
reduction order.  The engine goldens (tests/test_engine_golden.py) pin it.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.nmp.baselines import (TECHNIQUES, schedule_by_id,
                                 tom_colocation_score)

LDB_ID = TECHNIQUES.index("ldb")


class SharedParts(NamedTuple):
    """Outputs of the seed-invariant stage (see engine.SharedEpoch)."""
    rb_stamp: jnp.ndarray           # (P+1,) i32 updated row-buffer stamps
    rb_winner: jnp.ndarray          # (3W,) bool first-touch indicators
    page_ema: jnp.ndarray | None    # (P,) f32 updated access EMA (PEI only)
    pei_hot1: jnp.ndarray | None    # (W,) bool src1 above PEI threshold
    pei_hot2: jnp.ndarray | None    # (W,) bool
    touch_cnt: jnp.ndarray | None   # (P,) f32 window touch counts (AIMM)


class RouteParts(NamedTuple):
    """Outputs of the schedule/route/count stage of `_epoch_sim`."""
    ccube: jnp.ndarray      # (W,) i32 scheduled compute cube per op
    loads: jnp.ndarray      # (L,) f32 per-link flit loads (+ pending mig)
    hops_op: jnp.ndarray    # (W,) f32 total hops per op
    ops_c: jnp.ndarray      # (C,) f32 compute ops per cube
    acc_c: jnp.ndarray      # (C,) f32 accesses per cube
    distinct_c: jnp.ndarray  # (C,) f32 distinct pages touched per cube
    mcq: jnp.ndarray        # (M,) f32 MC queue depths


def shared_stage(dest, src1, src2, valid, epochs, rb_stamp, page_ema,
                 n_pages, pei_idx, *, pei_k: int, aimm: bool) -> SharedParts:
    """Seed-invariant epoch quantities of one lane's window."""
    P = rb_stamp.shape[0] - 1
    W = dest.shape[0]

    # Row-buffer stamp race: pages are stamped (not cubes), so winners are
    # mapping-independent even though the per-cube distinct counts are not.
    acc_page = jnp.concatenate([dest, src1, src2])
    acc_valid = jnp.concatenate([valid, valid, valid])
    tag_base = (epochs.astype(jnp.int32) + 1) * (3 * W)
    stamp_val = jnp.where(acc_valid > 0,
                          tag_base + jnp.arange(3 * W, dtype=jnp.int32), 0)
    stamp_idx = jnp.where(acc_valid > 0, acc_page, jnp.int32(P))
    new_stamp = rb_stamp.at[stamp_idx].max(stamp_val)
    rb_winner = (new_stamp[stamp_idx] == stamp_val) & (acc_valid > 0)

    if pei_k > 0:
        # PEI hot threshold = the m-th largest access EMA among the real
        # pages, read from a static top_k envelope (see engine module doc).
        # Thresholds read the PRE-update EMA; the decayed EMA is stored.
        top = jax.lax.top_k(page_ema, pei_k)[0]
        m = n_pages - pei_idx
        thresh = top[jnp.clip(m - 1, 0, pei_k - 1)]
        pei_hot1 = page_ema[src1] >= jnp.maximum(thresh, 1e-6)
        pei_hot2 = page_ema[src2] >= jnp.maximum(thresh, 1e-6)
        new_ema = 0.9 * page_ema
        new_ema = new_ema.at[dest].add(valid).at[src1].add(
            valid).at[src2].add(valid)
    else:
        pei_hot1 = pei_hot2 = new_ema = None

    touch_cnt = (jnp.zeros((P,)).at[acc_page].add(acc_valid)
                 if aimm else None)
    return SharedParts(rb_stamp=new_stamp, rb_winner=rb_winner,
                       page_ema=new_ema, pei_hot1=pei_hot1,
                       pei_hot2=pei_hot2, touch_cnt=touch_cnt)


def _compute_cubes(dest, src1, src2, eff_table, compute_remap, technique,
                   is_aimm, pei_hot1, pei_hot2, n_cubes, *, pei: bool,
                   aimm: bool):
    """Schedule the compute cube per op: technique baseline + AIMM remap."""
    dcube = eff_table[dest]
    s1cube = eff_table[src1]
    s2cube = eff_table[src2]
    if pei:
        ccube = schedule_by_id(technique, dcube, s1cube, s2cube,
                               pei_hot1, pei_hot2)
    else:
        # No PEI lane in this program: schedule_by_id collapses to LDB/BNMP.
        ccube = jnp.where(technique == LDB_ID, s1cube, dcube)
    if aimm:
        # compute-remap table: -1 none, 0..C-1 fixed cube, C = "source mode"
        cr = compute_remap[dest]
        cr = jnp.where(cr >= 0, cr, compute_remap[src1])
        cr = jnp.where(cr >= 0, cr, compute_remap[src2])
        aimm_cc = jnp.where(cr == n_cubes, s1cube,
                            jnp.where(cr >= 0, cr, ccube))
        ccube = jnp.where(is_aimm, aimm_cc, ccube)
    return dcube, s1cube, s2cube, ccube


def route_stage(dest, src1, src2, valid, rb_winner, pei_hot1, pei_hot2,
                eff_table, compute_remap, technique, is_aimm,
                pending_mig_loads, route_links, hops, nearest_mc, *,
                pei: bool, aimm: bool, n_mcs: int,
                packet_flits: float) -> RouteParts:
    """Schedule, route and count one cell's window."""
    C = route_links.shape[0]
    dcube, s1cube, s2cube, ccube = _compute_cubes(
        dest, src1, src2, eff_table, compute_remap, technique, is_aimm,
        pei_hot1, pei_hot2, C, pei=pei, aimm=aimm)

    # flows s1->c, s2->c, c->d (zero-hop flows drop out implicitly)
    fsrc = jnp.concatenate([s1cube, s2cube, ccube])
    fdst = jnp.concatenate([ccube, ccube, dcube])
    fw = jnp.concatenate([valid, valid, valid]) * packet_flits
    routes = route_links[fsrc, fdst]                           # (3W, L)
    loads = (jnp.einsum("f,fl->l", fw.astype(jnp.float32), routes)
             + pending_mig_loads)

    hops_op = (hops[s1cube, ccube] + hops[s2cube, ccube]
               + hops[ccube, dcube]).astype(jnp.float32)

    ops_c = jnp.zeros((C,)).at[ccube].add(valid)
    acc_cube = jnp.concatenate([dcube, s1cube, s2cube])
    acc_valid = jnp.concatenate([valid, valid, valid])
    distinct_c = jnp.zeros((C,)).at[acc_cube].add(
        rb_winner.astype(jnp.float32))
    acc_c = jnp.zeros((C,)).at[acc_cube].add(acc_valid)
    mcq = jnp.zeros((n_mcs,)).at[nearest_mc[dcube]].add(valid)
    return RouteParts(ccube=ccube, loads=loads, hops_op=hops_op, ops_c=ops_c,
                      acc_c=acc_c, distinct_c=distinct_c, mcq=mcq)


def tom_stage(dest, src1, src2, valid, cands, n_cubes: int) -> jnp.ndarray:
    """(K,) TOM candidate co-location scores of one window."""
    def score_k(k):
        return tom_colocation_score(cands[k], dest, src1, src2, valid,
                                    n_cubes)
    return jax.vmap(score_k)(jnp.arange(cands.shape[0]))

