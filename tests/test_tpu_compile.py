"""Compiles for a described TPU v5e: the Q-network and the sweep program of
the default chip path, at production widths, with no chip attached.

Nothing here runs; each test lowers and compiles for `v5e:2x2` device 0,
so what the TPU compiler would refuse on the chip fails here instead.  The
topology is described inside the module-scoped fixture only (never at
import), because one process at a time may load the TPU library.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.aimm_nmp import PAPER_4X4
from repro.core import dqn
from repro.core.actions import N_ACTIONS
from repro.nmp.engine import default_agent_cfg, state_spec_for

STATE_DIM = state_spec_for(PAPER_4X4).dim
HIDDEN = (128, 128)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _qnet_param_shapes(cfg):
    shapes = jax.eval_shape(lambda: dqn.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    return {k: (v.shape, v.dtype) for k, v in shapes.items()}


def _kernel_names(hlo: str) -> list[str]:
    """The instruction names of the Pallas kernels in a compiled program."""
    return [line.split("=", 1)[0].split()[-1].lstrip("%")
            for line in hlo.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


@pytest.mark.parametrize("rows", [1, 64, 1024],
                         ids=["act", "replay_batch", "rows1024"])
def test_qnet_forward_compiles(one_chip, rows):
    """The agent's forward pass (`dqn.q_values`) at the paper's state width:
    one ε-greedy act (1 row), one replay minibatch (64 rows) and a wide
    batch.  Every Pallas kernel in it is the order-fixed contraction."""
    cfg = dqn.DQNConfig(state_dim=STATE_DIM, n_actions=N_ACTIONS,
                        hidden=HIDDEN)
    params = {k: _sds(s, d, one_chip)
              for k, (s, d) in _qnet_param_shapes(cfg).items()}
    x = _sds((rows, STATE_DIM), jnp.float32, one_chip)
    hlo = jax.jit(lambda p, s: dqn.q_values(p, s, cfg)
                  ).lower(params, x).compile().as_text()
    names = _kernel_names(hlo)
    assert names and all(n.startswith("order_fixed_dense") for n in names)


@pytest.mark.parametrize("rows,k,n", [(64, STATE_DIM, 128), (64, 128, 128),
                                      (64, 128, 1), (64, 128, N_ACTIONS),
                                      (1, STATE_DIM, 128)],
                         ids=["layer0", "layer1", "value", "advantage",
                              "act"])
def test_order_fixed_dense_compiles(one_chip, rows, k, n):
    """`dqn.dense` and its VJP at the agent's widths, vmapped over the 81
    cells of the learned benchmark grid, lowered for the chip: the forward
    pass and both contraction cotangents are the order-fixed kernel."""
    cells = 81

    def step(x, w, b, g):
        y, vjp = jax.vjp(dqn.dense, x, w, b)
        return (y,) + vjp(g)

    args = [_sds((cells,) + s, jnp.float32, one_chip)
            for s in ((rows, k), (k, n), (n,), (rows, n))]
    hlo = jax.jit(jax.vmap(step)).lower(*args).compile().as_text()
    names = _kernel_names(hlo)
    assert len(names) == 3 and all("order_fixed_dense" in n for n in names)


@pytest.mark.parametrize("n_dev", [1, 4], ids=["default-1chip",
                                               "default-4chips"])
def test_sweep_program_compiles(topo, n_dev):
    """The learned-agent group of a paper-shaped grid (AIMM lanes of every
    technique, PEI's top-k live) through plan -> build batch -> `_run_sweep`
    with the defaults, on one chip and sharded over four.  On one chip the Q-network's contractions are the
    order-fixed kernel, the program's only Pallas kernel; sharded, GSPMD
    cannot split that kernel, so the program keeps the jnp contractions and
    each chip holds only its own cells' agents."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.nmp import partition
    from repro.nmp import plan as plan_mod
    from repro.nmp import sweep
    from repro.nmp.scenarios import single_program_grid

    cfg = PAPER_4X4
    grid = single_program_grid(apps=("KM", "SPMV"),
                               techniques=("bnmp", "ldb", "pei"),
                               mappers=("none", "tom", "aimm"), n_ops=1536,
                               aimm_episodes=2)
    plan = plan_mod.plan_grid(grid, cfg)
    group = next(g for g in plan.groups if g.has_agent)
    batch = partition.pad_group_batch(
        plan_mod.build_group_batch(plan, group, cfg),
        -(-group.n_lanes // n_dev) * n_dev)
    tom = np.asarray(plan_mod.plan_tom_candidates(plan, cfg))
    flags = sweep.executed_flags(group, group.n_seeds)
    assert flags.pei_k > 0

    mesh = Mesh(np.asarray(topo.devices[:n_dev]).reshape(n_dev, 1),
                (partition.LANE_AXIS, partition.SEED_AXIS))
    lane_sh = NamedSharding(mesh, P(partition.LANE_AXIS))
    cell_sh = NamedSharding(mesh, P(partition.LANE_AXIS,
                                    partition.SEED_AXIS))
    shapes = {k: _sds(np.shape(v), np.asarray(v).dtype,
                      cell_sh if k == "ep_seed" else lane_sh)
              for k, v in batch.items()}
    with sweep.mesh_scope(shapes):
        lowered = sweep._run_sweep.lower(
            shapes, _sds(tom.shape, tom.dtype, NamedSharding(mesh, P())),
            cfg, state_spec_for(cfg), default_agent_cfg(cfg), plan.n_epochs,
            group.n_episodes, plan.ring_len, flags)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    names = _kernel_names(hlo)
    if n_dev == 1:
        assert names and all(n.startswith("order_fixed_dense")
                             for n in names)
    else:
        assert not names
        assert "all-gather" not in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30
