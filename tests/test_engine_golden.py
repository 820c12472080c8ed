"""Golden-value regression for the engine hot path, plus the cond-vs-masked
agent-gate equality check.

The GOLDEN table pins per-lane `cycles` / `ops` / `opc` of a small fixed-seed
grid as produced by the pre-optimization engine (PR 1: full O(P log P) EMA
sort, sort-based row-buffer distinct count, compute-then-mask agent path).
The optimized cost model (top_k PEI threshold, O(W) scatter-stamp distinct
count, statically skipped feature paths) must reproduce them bit-for-bit:
deterministic lanes and scripted-AIMM lanes exercise every technique and both
baseline mappers, including a trace long enough for TOM to profile + commit.

Learned-policy lanes are deliberately absent: the invocation-gated agent
(train/act under `lax.cond` per invocation instead of per epoch) is a
documented semantic change of PR 2, so their trajectories moved.  Their
correctness bar is the cond-vs-masked equality below plus the batched/serial
equivalence suite.
"""
import jax
import numpy as np
import pytest

from repro.nmp import NMPConfig, make_trace
from repro.nmp.engine import run_episode
from repro.nmp.stats import summarize

CFG = NMPConfig()

# (app, n_ops, technique, mapper, forced_action) -> (cycles, ops, opc),
# produced with seed=2 by the PR 1 engine (see module docstring).  The two
# scripted NEAR_DATA (forced_action=1) entries draw pages with
# `jax.random.choice`, whose stream depends on `jax_threefry_partitionable`;
# they are pinned under that flag's default of JAX 0.9 (True).
GOLDEN = {
    ("KM", 384, "bnmp", "none", -1): (427.58953857421875, 384.0, 0.898057518620389),
    ("KM", 384, "bnmp", "tom", -1): (427.58953857421875, 384.0, 0.898057518620389),
    ("KM", 384, "ldb", "none", -1): (651.998779296875, 384.0, 0.5889581578881347),
    ("KM", 384, "ldb", "tom", -1): (651.998779296875, 384.0, 0.5889581578881347),
    ("KM", 384, "pei", "none", -1): (568.667236328125, 384.0, 0.6752630984677115),
    ("KM", 384, "pei", "tom", -1): (568.667236328125, 384.0, 0.6752630984677115),
    ("KM", 384, "bnmp", "aimm", 1): (1479.9920654296875, 384.0, 0.25946085047997397),
    ("KM", 384, "pei", "aimm", 5): (580.667236328125, 384.0, 0.6613081916387104),
    ("SPMV", 2048, "bnmp", "none", -1): (5710.2119140625, 2048.0, 0.3586556910359849),
    ("SPMV", 2048, "bnmp", "tom", -1): (5710.2119140625, 2048.0, 0.3586556910359849),
    ("SPMV", 2048, "ldb", "none", -1): (5890.01708984375, 2048.0, 0.3477069707541934),
    ("SPMV", 2048, "ldb", "tom", -1): (5890.01708984375, 2048.0, 0.3477069707541934),
    ("SPMV", 2048, "pei", "none", -1): (5835.72412109375, 2048.0, 0.35094188099079593),
    ("SPMV", 2048, "pei", "tom", -1): (5835.72412109375, 2048.0, 0.35094188099079593),
    ("SPMV", 2048, "bnmp", "aimm", 1): (10132.873046875, 2048.0, 0.2021144438034391),
    ("SPMV", 2048, "pei", "aimm", 5): (5927.9072265625, 2048.0, 0.3454844891672846),
}


@pytest.mark.parametrize("key", sorted(GOLDEN), ids=lambda k: "/".join(map(str, k)))
def test_hot_path_rewrite_preserves_golden_values(key):
    app, n_ops, tech, mapper, forced = key
    tr = make_trace(app, n_ops=n_ops)
    s = summarize(run_episode(tr, CFG, tech, mapper, seed=2,
                              forced_action=forced))
    want = GOLDEN[key]
    assert (s["cycles"], s["ops"], s["opc"]) == want, (key, s)


@pytest.mark.slow
def test_cond_agent_gate_equals_masked_reference():
    """The invocation-gated agent (`lax.cond` on any-lane-invokes + nested
    cond on replay readiness) must be bit-identical to the compute-every-epoch
    -and-mask reference path: same cycles, same action stream, same learned
    parameters."""
    tr = make_trace("SPMV", n_ops=1024)
    cond = run_episode(tr, CFG, "bnmp", "aimm", seed=3)
    masked = run_episode(tr, CFG, "bnmp", "aimm", seed=3, agent_gate="masked")
    assert float(cond.env.cycles) == float(masked.env.cycles)
    np.testing.assert_array_equal(np.asarray(cond.metrics["action"]),
                                  np.asarray(masked.metrics["action"]))
    np.testing.assert_array_equal(np.asarray(cond.metrics["opc"]),
                                  np.asarray(masked.metrics["opc"]))
    for c, m in zip(jax.tree.leaves(cond.agent.params),
                    jax.tree.leaves(masked.agent.params)):
        np.testing.assert_array_equal(np.asarray(c), np.asarray(m))
    for c, m in zip(jax.tree.leaves(cond.agent.replay),
                    jax.tree.leaves(masked.agent.replay)):
        np.testing.assert_array_equal(np.asarray(c), np.asarray(m))


def test_tom_gate_cond_equals_masked_reference():
    """TOM's profiling-phase candidate scoring runs under `lax.cond` on "any
    lane is in a profiling phase" (gated like the DQN invocation); it must be
    bit-identical to the score-every-epoch reference path: same cycles, same
    committed mapping, same candidate scores."""
    tr = make_trace("KM", n_ops=2048)      # long enough to profile + commit
    cond = run_episode(tr, CFG, "bnmp", "tom", seed=1)
    masked = run_episode(tr, CFG, "bnmp", "tom", seed=1, tom_gate="masked")
    assert float(cond.env.cycles) == float(masked.env.cycles)
    assert int(cond.env.tom_active) == int(masked.env.tom_active) >= 0
    np.testing.assert_array_equal(np.asarray(cond.env.tom_scores),
                                  np.asarray(masked.env.tom_scores))
    np.testing.assert_array_equal(np.asarray(cond.metrics["opc"]),
                                  np.asarray(masked.metrics["opc"]))


def test_agent_invocations_skip_between_strides():
    """With a scripted INC_INTERVAL policy the invocation stride climbs to 4;
    the invoke metric must go sparse accordingly (the whole point of gating
    the agent on `invoke`)."""
    tr = make_trace("SPMV", n_ops=2048)
    res = run_episode(tr, CFG, "bnmp", "aimm", forced_action=6, seed=0)
    inv = np.asarray(res.metrics["invoke"])
    assert int(res.env.interval_level) == 3
    # steady state: one invocation every 4 epochs
    assert inv[-8:].sum() == 2.0
