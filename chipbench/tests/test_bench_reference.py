"""The plain float32 reference against the system's own model and
optimizer, at a tiny size on the CPU, for both configurations."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, program, traffic_gen
from chipbench.reference import muon as ref_muon
from chipbench.reference import transformer as ref_model
from chipbench.tests import tiny
from repro.config import OptimizerConfig, PrismConfig
from repro.models import build
from repro.optim import make_optimizer

CONFIGS = ["gpt2-paper", "qwen3-8b"]


def _setup(name, **sizes):
    c = dict(tiny.config(name), torch_dtype="float32", **sizes)
    model = build(program.model_config(c))
    key = jax.random.PRNGKey(2 ** 31 + 11)
    ref_params = ref_model.init(key, c)
    params = program.to_program(ref_params, model.param_shapes())
    traffic = dict(tiny.read("traffic", "muon-8k.json"), seq=32, batch=4)
    tokens = traffic_gen.batches(key, traffic, c["vocab_size"])[0]
    return c, model, ref_params, params, tokens


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_loss_and_grads_match_the_model(name):
    c, model, ref_params, params, tokens = _setup(name)
    (loss, _), grads = jax.value_and_grad(
        lambda p: model.loss(p, {"tokens": tokens}), has_aux=True)(params)
    ref_loss, ref_grads = ref_model.loss_and_grad(ref_params, tokens, c)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    cut = program.from_program(grads, ref_model.param_shapes(c))
    for g, r in zip(jax.tree.leaves(cut), jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(g, r, rtol=2e-3,
                                   atol=2e-4 * float(jnp.max(jnp.abs(r))))


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_muon_step_matches_the_optimizer(name):
    c, model, ref_params, params, tokens = _setup(name)
    h = tiny.read("traffic", "muon-8k.json")["optimizer"]
    ocfg = OptimizerConfig(
        name="muon", learning_rate=h["learning_rate"], matfn_method="prism",
        prism=PrismConfig(**h["prism"]))
    opt = make_optimizer(ocfg, model.logical_axes())
    _, ref_grads = ref_model.loss_and_grad(ref_params, tokens, c)
    views = ref_muon.muon_leaves(ref_model.param_shapes(c),
                                 ref_model.muon_views(c))
    # the reference clips; hand the optimizer the gradient it would get
    new_ref, _, used = ref_muon.update(ref_grads, ref_muon.init_state(
        ref_params, views), ref_params, h, views)
    grads = program.to_program(used, model.param_shapes())
    with jax.default_matmul_precision("highest"):
        new, _ = opt.update(grads, opt.init(params), params, jnp.int32(0),
                            jax.random.PRNGKey(0))
    cut = program.from_program(new, ref_model.param_shapes(c))
    for (path, got), want, p0 in zip(
            jax.tree_util.tree_flatten_with_path(cut)[0],
            jax.tree.leaves(new_ref), jax.tree.leaves(ref_params)):
        step = want - p0
        np.testing.assert_allclose(
            got - p0, step, rtol=1e-3,
            atol=1e-3 * float(jnp.max(jnp.abs(step))),
            err_msg=jax.tree_util.keystr(path))


def test_polar_iteration_is_the_configured_one():
    """Three warm iterations leave singular values between 0.6 and 1.15
    (not the exact polar factor), and a zero matrix stays zero."""
    a = jax.random.normal(jax.random.PRNGKey(0), (256, 128))
    prism = {"degree": 2, "iterations": 3, "warm_alpha_iters": 3}
    s = jnp.linalg.svd(ref_muon.polar(a, prism), compute_uv=False)
    assert 0.5 < float(s.min()) < 0.8 and 1.0 < float(s.max()) < 1.2
    assert not jnp.any(ref_muon.polar(jnp.zeros((32, 16)), prism))


def test_published_grouped_heads_of_the_qwen3_8b_cell_match():
    """Qwen3-8B's 32 query heads over 8 key/value heads need no head
    padding, so the model groups them as the reference does (4 per kv
    head).  Qwen3-14B's 40 over 8 pad to 48 and regroup 6 per kv head;
    no Qwen3-14B cell can be added until the program groups padded heads
    right."""
    c, model, ref_params, params, tokens = _setup(
        "qwen3-8b", num_attention_heads=32, num_key_value_heads=8,
        head_dim=8)
    loss, _ = model.loss(params, {"tokens": tokens})
    with jax.default_matmul_precision("highest"):
        ref_loss = ref_model.loss(ref_params, tokens, c)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)


# the rows of each dense leaf's matrix view, counted after its layer axis
DENSE_ROWS = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (2,), "w_up": (0,),
              "w_gate": (0,), "w_down": (1,), "bq": (0,), "bk": (0,),
              "bv": (0,)}


@pytest.mark.parametrize("name", CONFIGS)
def test_dense_muon_views_keep_the_dense_table(name):
    """Every dense leaf named in the table, and no other, is a stack of
    one matrix per layer with the table's rows."""
    c = (tiny.QWEN3_8B if name == "qwen3-8b"
         else tiny.read("configs", name + ".json"))
    flat = ref_muon.leaf_paths(ref_model.param_shapes(c))
    want = {path: (1, DENSE_ROWS[path.rsplit("/", 1)[-1]]) for path in flat
            if path.rsplit("/", 1)[-1] in DENSE_ROWS}
    assert ref_model.muon_views(c) == want
    assert ref_muon.muon_leaves(ref_model.param_shapes(c), want) == want


def test_a_layer_outside_its_leaf_raises():
    driver = harness.load_module(harness.HERE + "/drivers/train.py")
    shapes = {"layers/attn/wq": (10, 64, 16, 4), "dense/w": (1, 64, 64),
              "layers/experts/w_up": (26, 8, 64, 32)}
    key = jax.random.PRNGKey(2 ** 31 + 7)
    layers = driver.sampled_layers(key, shapes)
    # each leaf is drawn within its own layer axis; ten-layer leaves
    # keep the draw of one layer for the whole model
    assert layers["dense/w"] == 0 and 0 <= layers["layers/experts/w_up"] < 26
    assert layers["layers/attn/wq"] == int(jax.random.randint(
        jax.random.fold_in(key, 2), (), 0, 10))
    driver.check_layers(layers, shapes)
    for bad in (10, -1):
        with pytest.raises(IndexError, match="layers/attn/wq"):
            driver.check_layers(dict(layers, **{"layers/attn/wq": bad}),
                                shapes)
