"""Interpret-mode Pallas parity suite (`make test-pallas`).

Runs both Pallas kernel families on CPU via `interpret=True` and pins them
against the pure-jnp paths and the engine goldens:

  * the fused epoch kernel (repro.kernels.epoch_fused) — the engine golden
    table re-run under REPRO_EPOCH_BACKEND=pallas_interpret must reproduce
    the pinned values bit-for-bit (the kernel's reductions are exact-integer
    f32 sums, so any reduction order gives the same bits — see
    kernels/epoch_fused/kernel.py), across minimal and full BodyFlags
    (bnmp/none compiles the PEI/TOM/agent machinery out; pei/aimm and
    pei/tom light all of it up);
  * the batched sweep with S==1 and S>1 folded seed axes, seed-invariant
    sharing on and off — every grid cell bit-identical to the jnp backend;
  * the ops-level dispatchers (shared/route/fused/TOM stages) on a real
    trace window;
  * the dueling-qnet forward kernel in interpret mode vs its jnp oracle;
  * the backend knobs' fail-fast validation (REPRO_EPOCH_BACKEND,
    REPRO_SWEEP_LAND, REPRO_STORE_STAGING) and the auto->jnp default.

The engine reads the knob through `BodyFlags.epoch_backend` — a static jit
argument — so monkeypatching the env var between calls genuinely selects a
different compiled program instead of a stale resident one.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.epoch_fused import EPOCH_BACKENDS, resolve_backend
from repro.kernels.epoch_fused import ops as epoch_ops
from repro.nmp import NMPConfig, make_trace
from repro.nmp.engine import pei_hot_index, run_episode
from repro.nmp.stats import summarize

from tests.test_engine_golden import GOLDEN

CFG = NMPConfig()

# Subset of the golden table covering every technique, both baseline mappers
# (incl. the SPMV trace long enough for TOM to profile + commit) and the
# scripted-AIMM remap path — i.e. minimal BodyFlags (bnmp/none: PEI, TOM and
# the agent all compiled out) through full ones (pei/aimm, pei/tom).
PARITY_KEYS = sorted(k for k in GOLDEN
                     if k[0] == "KM" or k[2] == "pei" or k[3] == "aimm")


def _metrics_equal(a, b) -> bool:
    return (set(a.metrics) == set(b.metrics)
            and all(np.array_equal(np.asarray(a.metrics[k]),
                                   np.asarray(b.metrics[k]))
                    for k in a.metrics))


# ---------------------------------------------------------------------------
# fused epoch kernel vs engine goldens (serial path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("key", PARITY_KEYS,
                         ids=lambda k: "/".join(map(str, k)))
def test_fused_kernel_reproduces_engine_goldens(key, monkeypatch):
    monkeypatch.setenv(epoch_ops.ENV_KNOB, "pallas_interpret")
    app, n_ops, tech, mapper, forced = key
    tr = make_trace(app, n_ops=n_ops)
    s = summarize(run_episode(tr, CFG, tech, mapper, seed=2,
                              forced_action=forced))
    assert (s["cycles"], s["ops"], s["opc"]) == GOLDEN[key], (key, s)


# ---------------------------------------------------------------------------
# batched sweep: S==1 and S>1, seed sharing on/off
# ---------------------------------------------------------------------------

def _grid():
    from repro.nmp.scenarios import single_program_grid
    grid = single_program_grid(apps=("KM",), mappers=("aimm",), n_ops=384,
                               seeds=(0, 1, 2), aimm_episodes=2)
    grid += single_program_grid(apps=("KM",), techniques=("pei",),
                                mappers=("none", "tom"), n_ops=384, seeds=(0,))
    return grid


@pytest.mark.parametrize("share", ["on", "off"])
def test_sweep_grid_parity_seed_axes(share, monkeypatch):
    """The folded-seed grid (S>1 AIMM group + S==1 baseline lanes) must be
    bit-identical between the jnp backend and the interpret-mode kernel, with
    seed-invariant sharing both on (split shared/route kernel calls) and off
    (one fully fused call per cell)."""
    from repro.nmp.sweep import run_grid
    grid = _grid()
    monkeypatch.setenv("REPRO_SEED_SHARE", share)
    monkeypatch.setenv(epoch_ops.ENV_KNOB, "jnp")
    ref = run_grid(grid)
    monkeypatch.setenv(epoch_ops.ENV_KNOB, "pallas_interpret")
    got = run_grid(grid)
    assert _metrics_equal(ref, got)


# ---------------------------------------------------------------------------
# ops-level stage parity on a real trace window
# ---------------------------------------------------------------------------

def _window():
    from repro.nmp.engine import _init_env, phase_ring_len, state_spec_for
    from repro.nmp.paging import default_alloc
    from repro.nmp.topology import get_topology
    tr = make_trace("KM", n_ops=384)
    topo = get_topology(CFG)
    spec = state_spec_for(CFG)
    env = _init_env(default_alloc(tr.n_pages, CFG), CFG, spec, 2,
                    phase_ring_len(tr, CFG))
    W = CFG.w_max
    sl = slice(0, W)
    dest = jnp.asarray(tr.dest[sl])
    src1 = jnp.asarray(tr.src1[sl])
    src2 = jnp.asarray(tr.src2[sl])
    valid = jnp.ones((W,), jnp.float32)
    return tr, topo, env, dest, src1, src2, valid


@pytest.mark.parametrize("pei_k", [0, 8])
def test_stage_dispatchers_bit_identical(pei_k):
    tr, topo, env, dest, src1, src2, valid = _window()
    kw = dict(pei_k=pei_k, aimm=True)
    sp_ref = epoch_ops.shared_parts(
        dest, src1, src2, valid, env.epochs, env.rb_stamp,
        env.page_access_ema, tr.n_pages, jnp.asarray(pei_hot_index(tr.n_pages, CFG), jnp.int32),
        backend="jnp", **kw)
    sp_ker = epoch_ops.shared_parts(
        dest, src1, src2, valid, env.epochs, env.rb_stamp,
        env.page_access_ema, tr.n_pages, jnp.asarray(pei_hot_index(tr.n_pages, CFG), jnp.int32),
        backend="pallas_interpret", **kw)
    for name, a, b in zip(sp_ref._fields, sp_ref, sp_ker):
        if a is None:
            assert b is None
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)

    from repro.nmp.baselines import TECHNIQUES
    from repro.nmp.paging import default_alloc
    eff = jnp.asarray(default_alloc(tr.n_pages, CFG), jnp.int32)  # page->cube
    tech = jnp.asarray(TECHNIQUES.index("pei" if pei_k else "bnmp"), jnp.int32)
    rp_ref = epoch_ops.route_parts(
        dest, src1, src2, valid, sp_ref.rb_winner, sp_ref.pei_hot1,
        sp_ref.pei_hot2, eff, env.compute_remap, tech,
        jnp.asarray(True), env.pending_mig_loads, topo,
        n_mcs=CFG.n_mcs, packet_flits=CFG.packet_flits, backend="jnp", **kw)
    rp_ker = epoch_ops.route_parts(
        dest, src1, src2, valid, sp_ref.rb_winner, sp_ref.pei_hot1,
        sp_ref.pei_hot2, eff, env.compute_remap, tech,
        jnp.asarray(True), env.pending_mig_loads, topo,
        n_mcs=CFG.n_mcs, packet_flits=CFG.packet_flits,
        backend="pallas_interpret", **kw)
    for name, a, b in zip(rp_ref._fields, rp_ref, rp_ker):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


def test_tom_scores_bit_identical():
    _tr, _topo, _env, dest, src1, src2, valid = _window()
    cands = jnp.stack([jnp.arange(CFG.n_cubes, dtype=jnp.int32),
                       jnp.roll(jnp.arange(CFG.n_cubes, dtype=jnp.int32), 1),
                       jnp.flip(jnp.arange(CFG.n_cubes, dtype=jnp.int32))])
    ref = epoch_ops.tom_scores(dest, src1, src2, valid, cands,
                               n_cubes=CFG.n_cubes, backend="jnp")
    ker = epoch_ops.tom_scores(dest, src1, src2, valid, cands,
                               n_cubes=CFG.n_cubes,
                               backend="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


# ---------------------------------------------------------------------------
# dueling qnet interpret-mode parity
# ---------------------------------------------------------------------------

def test_qnet_interpret_matches_jnp_oracle():
    from repro.kernels.dueling_qnet.ops import qnet_forward
    from repro.kernels.dueling_qnet.ref import dueling_qnet_ref
    rng = np.random.default_rng(0)
    S, H, A, B = 106, 128, 8, 37
    p = {k: jnp.asarray(rng.normal(scale=0.5, size=s).astype(np.float32))
         for k, s in {"w0": (S, H), "b0": (H,), "w1": (H, H), "b1": (H,),
                      "w_v": (H, 1), "b_v": (1,), "w_a": (H, A),
                      "b_a": (A,)}.items()}
    x = jnp.asarray(rng.normal(size=(B, S)).astype(np.float32))
    got = qnet_forward(p, x, interpret=True)        # the Pallas kernel body
    want = dueling_qnet_ref(x, p["w0"], p["b0"], p["w1"], p["b1"],
                            p["w_v"], p["b_v"], p["w_a"], p["b_a"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# knob validation + resolution
# ---------------------------------------------------------------------------

def test_epoch_backend_knob_validates(monkeypatch):
    monkeypatch.setenv(epoch_ops.ENV_KNOB, "banana")
    with pytest.raises(ValueError, match="REPRO_EPOCH_BACKEND.*banana"):
        resolve_backend()
    with pytest.raises(ValueError, match="cuda"):
        resolve_backend("cuda")
    for mode in EPOCH_BACKENDS:
        monkeypatch.setenv(epoch_ops.ENV_KNOB, mode)
        assert resolve_backend() in ("jnp", "pallas", "pallas_interpret")


def test_epoch_backend_auto_is_jnp_on_cpu(monkeypatch):
    # `auto` is jnp on every platform: the TPU compiler refuses the fused
    # kernel (kernels/epoch_fused/kernel.py)
    monkeypatch.delenv(epoch_ops.ENV_KNOB, raising=False)
    assert resolve_backend() == "jnp"
    assert resolve_backend("auto") == "jnp"


def test_sweep_knobs_validate(monkeypatch):
    from repro.nmp import sweep
    monkeypatch.setenv(sweep.LAND_KNOB, "later")
    with pytest.raises(ValueError, match="REPRO_SWEEP_LAND.*later"):
        sweep.land_mode()
    monkeypatch.setenv(sweep.LAND_KNOB, "sync")
    assert sweep.land_mode() == "sync"
    monkeypatch.delenv(sweep.LAND_KNOB, raising=False)
    assert sweep.land_mode() == "async"

    monkeypatch.setenv(sweep.STAGING_KNOB, "maybe")
    with pytest.raises(ValueError, match="REPRO_STORE_STAGING.*maybe"):
        sweep.staging_enabled()
    monkeypatch.setenv(sweep.STAGING_KNOB, "off")
    assert sweep.staging_enabled() is False
    monkeypatch.delenv(sweep.STAGING_KNOB, raising=False)
    assert sweep.staging_enabled() is True


# ---------------------------------------------------------------------------
# staging + async landing equivalence (the PR's dispatch-side satellites)
# ---------------------------------------------------------------------------

def test_async_land_and_staging_bit_identical(monkeypatch):
    """Chained lineage run_grid calls under the new defaults (async landing,
    staging buffers) must produce bit-identical metrics AND final store
    snapshots to the historical sync/per-cell path."""
    import jax

    from repro.nmp import sweep
    from repro.nmp.scenarios import single_program_grid
    grid = single_program_grid(apps=("KM", "PR"), mappers=("aimm",),
                               n_ops=256, seeds=(0, 1), aimm_episodes=2)
    grid += single_program_grid(apps=("KM",), mappers=("none",), n_ops=256,
                                seeds=(0,))
    grid = [dataclasses.replace(sc, lineage=f"lin{i}")
            if sc.mapper == "aimm" else sc for i, sc in enumerate(grid)]

    def chain():
        r1 = sweep.run_grid(grid)
        return r1, sweep.run_grid(grid, store=r1.store)

    monkeypatch.setenv(sweep.LAND_KNOB, "sync")
    monkeypatch.setenv(sweep.STAGING_KNOB, "off")
    a1, a2 = chain()
    monkeypatch.setenv(sweep.LAND_KNOB, "async")
    monkeypatch.setenv(sweep.STAGING_KNOB, "on")
    b1, b2 = chain()
    assert _metrics_equal(a1, b1) and _metrics_equal(a2, b2)
    sa, sb = a2.store, b2.store
    assert sa.tags == sb.tags
    for tag in sa.tags:
        for x, y in zip(jax.tree.leaves(sa.get(tag)),
                        jax.tree.leaves(sb.get(tag))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
