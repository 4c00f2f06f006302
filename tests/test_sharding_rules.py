"""Unit tests for the sharding-rule layer (no multi-device needed:
constrain_spec / rules are pure functions of mesh metadata)."""
import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch import sharding as sh


@pytest.fixture(scope="module")
def mesh():
    # metadata-only usage: a 1-device mesh can't express 16x16, so build
    # an abstract mesh with the production shape
    from jax.sharding import AbstractMesh, AxisType

    return AbstractMesh((16, 16), ("data", "model"),
                        axis_types=(AxisType.Auto,) * 2)


def test_constrain_spec_drops_nondivisible(mesh):
    # granite vocab 49155 % 16 != 0 -> axis dropped
    out = sh.constrain_spec(mesh, P("model", "data"), (49155, 1024))
    assert tuple(out) == (None, "data")


def test_constrain_spec_dedup_keeps_specific(mesh):
    # expert tensors under "zero": embed (data, model) + expert_mlp model
    # -> embed reduces to (data,), expert_mlp keeps model
    out = sh.constrain_spec(mesh, P(None, ("data", "model"), "model"),
                            (8, 4096, 14336))
    assert tuple(out) == (None, "data", "model")


def test_constrain_spec_pads_nothing_when_legal(mesh):
    out = sh.constrain_spec(mesh, P(("data", "model"), None), (5120, 32))
    assert tuple(out) == (("data", "model"), None)


def test_param_rules_strategies(mesh):
    cfg = get_config("mixtral-8x7b")
    tp = sh.param_rules(cfg, mesh, "tp")
    assert tp["mlp"] == "model" and tp["embed"] == "data"
    assert tp["expert_mlp"] == "model"  # mixtral: 8 experts < 16 -> TP
    zero = sh.param_rules(cfg, mesh, "zero")
    assert zero["embed"] == ("data", "model") and zero["mlp"] is None
    assert zero["expert_mlp"] == "model"  # experts keep 2D sharding
    cfg_ep = get_config("granite-moe-1b-a400m")
    ep = sh.param_rules(cfg_ep, mesh, "tp")
    assert ep["experts"] == "model" and ep["expert_mlp"] is None


def test_activation_rules_opt_targets(mesh):
    cfg = get_config("qwen3-14b")
    for strat in ["tp", "zero"]:
        r = sh.activation_rules(cfg, mesh, strat)
        assert r["opt_layers"] == "model" and r["opt_rows"] == "data"


def test_tree_shardings_match_param_tree(mesh):
    from repro.models import build

    cfg = get_config("qwen3-14b")
    model = build(cfg)
    shapes = model.param_shapes()
    shards = sh.tree_shardings(mesh, model.logical_axes(),
                               sh.param_rules(cfg, mesh, "tp"), shapes)
    assert jax.tree.structure(shapes) == jax.tree.structure(shards)
    # every sharded dim divides evenly (in_shardings legality)
    for s, nshard in zip(jax.tree.leaves(shapes), jax.tree.leaves(shards)):
        spec = nshard.spec
        for dim, entry in zip(s.shape, tuple(spec)):
            if entry is None:
                continue
            n = 1
            for ax in (entry if isinstance(entry, tuple) else (entry,)):
                n *= dict(data=16, model=16)[ax]
            assert dim % n == 0, (s.shape, spec)
