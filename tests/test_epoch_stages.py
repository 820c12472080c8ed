"""The epoch core's stages (`nmp.epoch_stages`) on one real KM window,
against plain NumPy loops over the window's accesses."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nmp import NMPConfig, make_trace
from repro.nmp import epoch_stages
from repro.nmp.baselines import TECHNIQUES, tom_candidates
from repro.nmp.engine import pei_hot_index, pei_top_k
from repro.nmp.paging import default_alloc
from repro.nmp.topology import get_topology

CFG = NMPConfig()
EPOCH = 3                        # any epoch: the stamp tags scale with it


@pytest.fixture(scope="module")
def window():
    tr = make_trace("KM", n_ops=384)
    W = CFG.w_max
    valid = np.ones(W, np.float32)
    valid[-5:] = 0.0                             # a ragged window tail
    rng = np.random.default_rng(0)
    # a spread of access EMAs, so the PEI threshold splits the pages
    ema = rng.integers(0, 24, tr.n_pages).astype(np.float32)
    stamp = np.zeros(tr.n_pages + 1, np.int32)   # + the invalid-access slot
    stamp[:tr.n_pages] = rng.integers(0, 3 * W, tr.n_pages)
    return dict(tr=tr, dest=tr.dest[:W].astype(np.int32),
                src1=tr.src1[:W].astype(np.int32),
                src2=tr.src2[:W].astype(np.int32), valid=valid, ema=ema,
                stamp=stamp)


def _shared(w, pei_k):
    tr = w["tr"]
    return epoch_stages.shared_stage(
        jnp.asarray(w["dest"]), jnp.asarray(w["src1"]),
        jnp.asarray(w["src2"]), jnp.asarray(w["valid"]),
        jnp.asarray(EPOCH, jnp.int32), jnp.asarray(w["stamp"]),
        jnp.asarray(w["ema"]), jnp.asarray(tr.n_pages, jnp.int32),
        jnp.asarray(pei_hot_index(tr.n_pages, CFG), jnp.int32),
        pei_k=pei_k, aimm=True)


@pytest.mark.parametrize("pei_k", [0, 8])
def test_shared_stage_matches_loops(window, pei_k):
    w = window
    P, W = w["tr"].n_pages, w["dest"].shape[0]
    parts = _shared(w, pei_k)

    pages = np.concatenate([w["dest"], w["src1"], w["src2"]])
    valid3 = np.concatenate([w["valid"]] * 3)
    stamp = w["stamp"].copy()
    touch = np.zeros(P, np.float32)
    for j, (page, v) in enumerate(zip(pages, valid3)):
        if v > 0:
            stamp[page] = max(stamp[page], (EPOCH + 1) * 3 * W + j)
            touch[page] += 1.0
    winner = np.zeros(3 * W, bool)
    for j, (page, v) in enumerate(zip(pages, valid3)):
        winner[j] = v > 0 and stamp[page] == (EPOCH + 1) * 3 * W + j
    np.testing.assert_array_equal(np.asarray(parts.rb_stamp), stamp)
    np.testing.assert_array_equal(np.asarray(parts.rb_winner), winner)
    np.testing.assert_array_equal(np.asarray(parts.touch_cnt), touch)
    # a winner is the last valid access to its page in the window
    assert winner.sum() == len(set(pages[valid3 > 0].tolist()))

    if pei_k == 0:
        assert parts.page_ema is None and parts.pei_hot1 is None
        return
    # an envelope narrower than `pei_top_k` reads its last entry
    assert pei_k < pei_top_k(P, CFG)
    ema = w["ema"]
    top = sorted(ema.tolist(), reverse=True)[:pei_k]
    m = P - pei_hot_index(P, CFG)
    thresh = max(top[min(max(m - 1, 0), pei_k - 1)], 1e-6)
    hot1 = [ema[p] >= thresh for p in w["src1"]]
    hot2 = [ema[p] >= thresh for p in w["src2"]]
    assert 0 < sum(hot1) < W
    np.testing.assert_array_equal(np.asarray(parts.pei_hot1), hot1)
    np.testing.assert_array_equal(np.asarray(parts.pei_hot2), hot2)
    new_ema = np.float32(0.9) * ema
    for idx in (w["dest"], w["src1"], w["src2"]):
        for page, v in zip(idx, w["valid"]):
            new_ema[page] += v
    np.testing.assert_array_equal(np.asarray(parts.page_ema), new_ema)


def test_tom_stage_matches_loops(window):
    w = window
    C = CFG.n_cubes
    cands = np.asarray(tom_candidates(w["tr"].n_pages, CFG))
    got = np.asarray(epoch_stages.tom_stage(
        jnp.asarray(w["dest"]), jnp.asarray(w["src1"]),
        jnp.asarray(w["src2"]), jnp.asarray(w["valid"]),
        jnp.asarray(cands), C))
    n = w["valid"].sum()
    want = []
    for mapping in cands:
        co, ops_c = 0.0, np.zeros(C)
        for d, a, b, v in zip(w["dest"], w["src1"], w["src2"], w["valid"]):
            if v > 0:
                co += (int(mapping[a] == mapping[d])
                       + int(mapping[b] == mapping[d])) / 2.0
                ops_c[mapping[d]] += 1.0
        imb = (ops_c.max() / n - 1.0 / C) / (1.0 - 1.0 / C)
        want.append(co / n - 0.5 * min(max(imb, 0.0), 1.0))
    assert got.shape == (len(cands),)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("technique", ["bnmp", "ldb", "pei"])
def test_route_stage_loads_carry_every_routed_hop(window, technique):
    """Each link's flit load is `packet_flits` per flow routed over it, so
    the loads sum to `packet_flits` times the window's routed hops; the
    compute cube follows the technique."""
    w = window
    topo = get_topology(CFG)
    pei = technique == "pei"
    parts = _shared(w, pei_top_k(w["tr"].n_pages, CFG) if pei else 0)
    table = default_alloc(w["tr"].n_pages, CFG).astype(np.int32)
    remap = np.full(w["tr"].n_pages, -1, np.int32)
    rp = epoch_stages.route_stage(
        jnp.asarray(w["dest"]), jnp.asarray(w["src1"]),
        jnp.asarray(w["src2"]), jnp.asarray(w["valid"]), parts.rb_winner,
        parts.pei_hot1, parts.pei_hot2, jnp.asarray(table),
        jnp.asarray(remap), jnp.asarray(TECHNIQUES.index(technique)),
        jnp.asarray(False), jnp.zeros(topo.n_links, jnp.float32),
        jnp.asarray(topo.route_links), jnp.asarray(topo.hops),
        jnp.asarray(topo.nearest_mc), pei=pei, aimm=False, n_mcs=CFG.n_mcs,
        packet_flits=CFG.packet_flits)

    ccube = np.asarray(rp.ccube)
    hops = 0
    for d, a, b, c, v in zip(w["dest"], w["src1"], w["src2"], ccube,
                             w["valid"]):
        if v > 0:
            hops += (topo.hops[table[a], c] + topo.hops[table[b], c]
                     + topo.hops[c, table[d]])
    assert hops > 0
    loads = np.asarray(rp.loads)
    assert loads.shape == (topo.n_links,)
    assert loads.sum() == CFG.packet_flits * hops
    assert np.asarray(rp.hops_op) @ w["valid"] == hops
    if technique == "bnmp":
        np.testing.assert_array_equal(ccube, table[w["dest"]])
    elif technique == "ldb":
        np.testing.assert_array_equal(ccube, table[w["src1"]])
