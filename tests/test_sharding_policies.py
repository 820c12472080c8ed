"""Sharding policy correctness: every produced spec divides its tensor dims,
for every architecture on both production meshes (via AbstractMesh — no
devices needed)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.configs import ARCHS, SHAPES, get_config, shape_applicable
from repro.models import build_model
from repro.models.model import abstract_init
from repro.sharding import policies


def _mesh(multi):
    if multi:
        return AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    return AbstractMesh((16, 16), ("data", "model"))


def _axis_size(mesh, axis):
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def _check(specs, shapes, mesh, where):
    flat_s, _ = jax.tree_util.tree_flatten(specs)
    flat_h, _ = jax.tree_util.tree_flatten(shapes)
    assert len(flat_s) == len(flat_h), where
    for sh, sp in zip(flat_h, flat_s):
        spec = sp.spec
        for d, ax in zip(sh.shape, tuple(spec) + (None,) * 10):
            sz = _axis_size(mesh, ax)
            assert d % sz == 0, (where, sh.shape, spec)


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("multi", [False, True])
def test_param_and_batch_specs_divide(arch, multi):
    mesh = _mesh(multi)
    cfg = get_config(arch)
    model = build_model(cfg)
    pshapes, roles = abstract_init(model)
    pspecs = policies.param_specs(roles, pshapes, cfg, mesh)
    _check(pspecs, pshapes, mesh, f"{arch} params")
    gspecs = policies.zero_shard_specs(pspecs, pshapes, mesh, cfg)
    _check(gspecs, pshapes, mesh, f"{arch} grads")

    for sname, shape in SHAPES.items():
        ok, _ = shape_applicable(cfg, shape)
        if not ok:
            continue
        bsds = model.input_specs(shape)
        bspecs = policies.batch_specs(cfg, shape, mesh, bsds)
        _check(bspecs, bsds, mesh, f"{arch} {sname}")


@pytest.mark.parametrize("arch", ["jamba-1.5-large-398b", "mixtral-8x22b"])
def test_fsdp_policy_engages_for_big_models(arch):
    mesh = _mesh(False)
    cfg = get_config(arch)
    pol = policies.resolve_policy(cfg, mesh)
    assert pol.fsdp_params


def test_small_models_stay_tp_only():
    mesh = _mesh(False)
    pol = policies.resolve_policy(get_config("minitron-8b"), mesh)
    assert not pol.fsdp_params


def test_decode_cache_seq_sharded():
    mesh = _mesh(False)
    cfg = get_config("qwen3-32b")
    model = build_model(cfg)
    shape = SHAPES["decode_32k"]
    bsds = model.input_specs(shape)
    bspecs = policies.batch_specs(cfg, shape, mesh, bsds)
    leaf = jax.tree.leaves(bspecs["caches"])[0]
    # (n_super, B, S, K, hd): batch over data, seq over model
    assert leaf.spec[1] is not None and leaf.spec[2] == "model"


def test_quantized_opt_specs_preserve_leading_sharding():
    mesh = _mesh(False)
    cfg = get_config("jamba-1.5-large-398b")
    model = build_model(cfg)
    pshapes, roles = abstract_init(model)
    pspecs = policies.param_specs(roles, pshapes, cfg, mesh)
    ospecs = policies.opt_state_specs(pspecs, pshapes, mesh, cfg,
                                      quantized=True)
    import jax.tree_util as jtu
    # every quantized leaf dict has the four keys with NamedShardings
    leaves = jtu.tree_leaves(ospecs, is_leaf=lambda x: isinstance(x, dict)
                             and ("mq" in x or "m" in x))
    assert any("mq" in l for l in leaves)
