"""Moonlight-16B-A3B's family (``reference/deepseek_v3.py``) against the
program's own layers at a tiny size on the CPU: latent attention, the
dropless expert layer that holds a share of the experts, the whole
model's loss and gradient, and Muon's matrix views."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import program, traffic_gen
from chipbench.reference import deepseek_v3 as ref_model
from chipbench.reference import muon as ref_muon
from chipbench.tests import tiny
from repro.config import MoEConfig
from repro.models import attention, build
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.optim import base

SEED = 2 ** 31 + 1601
# float32 throughout: the program and the reference sum the same terms
# in another order, so they agree to float32 rounding of the result's
# scale (about 1e-7 relative per operation, a few hundred operations
# deep), far under any routing or precision difference
TOL = dict(rtol=2e-5, atol=2e-5)


def tiny_config(**sizes):
    """The Moonlight configuration file with every width cut down; the
    layer kinds (a dense layer, then expert layers with shared experts
    and a held share of the routed ones), float32."""
    c = dict(tiny.read("configs", "moonlight-16b-a3b.json"),
             num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
             moe_intermediate_size=32, n_routed_experts=16,
             num_experts_per_tok=4, ep_size=2, vocab_size=256,
             torch_dtype="float32")
    return dict(c, **sizes)


def _layer(params, i=0):
    return jax.tree.map(lambda a: a[i], params["layers"])


def _tokens(c, seq=32, batch=4):
    traffic = dict(tiny.read("traffic", "muon-8k.json"), seq=seq, batch=batch)
    return traffic_gen.batches(jax.random.PRNGKey(SEED), traffic,
                               c["vocab_size"])[0]


@pytest.mark.parametrize("seq,block", [(32, 1024), (64, 16)])
def test_latent_attention_matches_the_reference(monkeypatch, seq, block):
    """One MLA layer, in one block of keys and queries and in several
    (the jnp loop's KV blocks, the reference's query blocks)."""
    monkeypatch.setattr(ref_model, "Q_BLOCK", block)
    c = tiny_config()
    cfg = program.model_config(c)
    p = _layer(ref_model.init(jax.random.PRNGKey(SEED), c))["attn"]
    x = jax.random.normal(jax.random.PRNGKey(SEED + 1), (2, seq, 64))
    pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (2, seq))
    got, (k, v) = attention.attend(p, x, pos, cfg, kv_block=block)
    with jax.default_matmul_precision("highest"):
        want = ref_model._mla(p, x, c, lambda a: a)
    assert k.shape == (2, seq, 4, 24) and v.shape == (2, seq, 4, 16)
    np.testing.assert_allclose(got, want, **TOL)


def test_the_model_matches_the_reference():
    """Loss and every leaf's gradient of the whole model (the dense
    layer, two expert layers, the balance losses, the head)."""
    c = tiny_config()
    model = build(program.model_config(c))
    ref_params = ref_model.init(jax.random.PRNGKey(SEED), c)
    params = program.to_program(ref_params, model.param_shapes())
    tokens = _tokens(c)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: model.loss(p, {"tokens": tokens}), has_aux=True)(params)
    ref_loss, ref_grads = ref_model.loss_and_grad(ref_params, tokens, c,
                                                  block_rows=2)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    assert float(metrics["aux"]) > 0
    cut = program.from_program(grads, ref_model.param_shapes(c))
    flat = ref_muon.leaf_paths(ref_grads)
    for (path, r), g in zip(flat.items(), jax.tree.leaves(cut)):
        np.testing.assert_allclose(
            g, r, rtol=2e-3, atol=2e-4 * float(jnp.max(jnp.abs(r))) + 1e-12,
            err_msg=path)
    assert not np.any(flat["layers/moe/correction_bias"])


def _share(m, j, held):
    """Expert layer params of share ``j`` of ``held`` experts as rank 0
    holds them: the router's outputs and the bias rotated so that share
    j's experts come first, its experts' weights, no shared MLP."""
    lo = j * held
    return {"router": jnp.roll(m["router"], -lo, axis=1),
            "correction_bias": jnp.roll(m["correction_bias"], -lo),
            **{w: m[w][lo:lo + held] for w in ("w_gate", "w_up", "w_down")}}


def test_the_shares_add_up_to_the_whole_layer():
    """Eight chips' expert layers, each holding 8 of the 64 experts: the
    routed parts they compute, with the shared experts counted once,
    are the uncut layer's output."""
    ep = 8
    c = tiny_config(hidden_size=32, moe_intermediate_size=16,
                    n_routed_experts=64, num_experts_per_tok=6, ep_size=1)
    whole = _layer(ref_model.init(jax.random.PRNGKey(SEED), c))["moe"]
    x = jax.random.normal(jax.random.PRNGKey(SEED + 2), (2, 16, 32))
    with jax.default_matmul_precision("highest"):
        want, want_aux = ref_model.moe(whole, x, c)
    cfg = program.model_config(dict(c, ep_size=ep))
    cut = cfg.replace(moe=dataclasses.replace(cfg.moe, n_shared_experts=0))
    got, rows = L.mlp(whole["shared"], x, cfg), 0
    for j in range(ep):
        y, aux, stats = moe_lib.moe_layer(_share(whole, j, 64 // ep), x, cut)
        got, rows = got + y, rows + stats["moe_held_rows"]
        np.testing.assert_allclose(aux, want_aux, rtol=1e-6)
    # a sum of eight float32 partial results in another order
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert rows == 2 * 16 * 6


@pytest.mark.parametrize("chosen", [(0, 1, 2, 3), (1, 6, 9, 12),
                                    (8, 9, 14, 15)])
def test_every_token_routed_to_the_same_experts_is_kept(chosen):
    """Every token picks the same four experts (the bias decides, the
    router's scores being all equal): the held ones take all the tokens,
    nothing is dropped, and the layer equals the reference."""
    c = tiny_config()
    cfg = program.model_config(c)
    m = _layer(ref_model.init(jax.random.PRNGKey(SEED), c))["moe"]
    m = dict(m, router=jnp.zeros_like(m["router"]),
             correction_bias=jnp.zeros(16).at[jnp.array(chosen)].set(1.0))
    x = jax.random.normal(jax.random.PRNGKey(SEED + 3), (2, 32, 64))
    y, _, stats = moe_lib.moe_layer(m, x, cfg)
    with jax.default_matmul_precision("highest"):
        want, _ = ref_model.moe(m, x, c)
    np.testing.assert_allclose(y, want, **TOL)
    held = sum(e < 8 for e in chosen)
    assert stats["moe_held_rows"] == 64 * held
    assert stats["moe_load_max"] == 16 / 4


def _program_views(c):
    model = build(program.model_config(c))
    shapes = model.param_shapes()
    axes = model.logical_axes()
    flat_s = ref_muon.leaf_paths(jax.tree.map(lambda s: s.shape, shapes))
    flat_a = dict(zip(flat_s, jax.tree.leaves(
        axes, is_leaf=lambda t: isinstance(t, tuple))))
    return flat_s, flat_a


def test_muon_views_are_the_references():
    """Every leaf the program's Muon takes is one the reference names,
    viewed as the same matrices, element for element."""
    c = tiny_config()
    shapes, axes = _program_views(c)
    views = ref_muon.muon_leaves(ref_model.param_shapes(c),
                                 ref_model.muon_views(c))
    assert {p for p in shapes if base.is_matrix_param(axes[p], shapes[p])} \
        == set(views)
    for i, (path, view) in enumerate(sorted(views.items())):
        x = jax.random.normal(jax.random.PRNGKey(i), shapes[path])
        got, _ = base.to_matrix_view(x, axes[path])
        want, _ = ref_muon._to_matrix(view, x)
        np.testing.assert_array_equal(got, want, err_msg=path)


def test_published_widths_view_as_the_published_matrices():
    """At the cell's sizes: W_q [2048, 3072], W_kv_a [2048, 576], W_kv_b
    [512, 4096], W_o [2048, 2048], and 96 expert matrices of [2048,
    1408] (4 layers, 8 held experts, 3 projections) in one bucket."""
    c = tiny.read("configs", "moonlight-16b-a3b.json")
    shapes, axes = _program_views(c)
    dims = {p: base.matrix_view_dims(axes[p], s) for p, s in shapes.items()
            if base.is_matrix_param(axes[p], s)}
    for s in ("layers", "dense_layers"):
        assert [dims[f"{s}/attn/{w}"] for w in ("wq", "wkv_a", "wkv_b", "wo")
                ] == [(2048, 3072), (2048, 576), (512, 4096), (2048, 2048)]
    stacks = [shapes[p][0] * shapes[p][1] for p in shapes
              if dims.get(p) == (2048, 1408)]
    assert stacks == [32, 32, 32]
    assert "layers/moe/correction_bias" not in dims
    views = ref_model.muon_views(c)
    ref_shapes = ref_muon.leaf_paths(ref_model.param_shapes(c))
    assert {p: ref_muon.matrix_dims(v, ref_shapes[p])
            for p, v in views.items()} == dims


def test_the_step_flops_count_the_held_experts_at_their_share():
    """275.6M matrix parameters a token at the cell's sizes, the 8 held
    experts at 6 * 8 / 64 = 0.75 assignments a token, and causal
    attention at 8k: 37.4 TFLOP a step of 2 x 8192 tokens."""
    c = tiny.read("configs", "moonlight-16b-a3b.json")
    assert ref_model.matmul_params(c) == 275_644_416
    flops = ref_model.train_step_flops(c, 8192, 2)
    assert 37.3e12 < flops < 37.5e12


def test_the_translation_refuses_what_the_program_does_not_implement():
    c = tiny.read("configs", "moonlight-16b-a3b.json")
    cfg = program.model_config(c)
    assert cfg.moe == MoEConfig(
        num_experts=64, num_experts_per_tok=6, dispatch="grouped", ep_size=8,
        n_shared_experts=2, selection_bias=True, norm_topk_prob=True,
        routed_scaling_factor=2.446, seq_aux=True, router_aux_loss_coef=1e-4)
    assert (cfg.num_layers, cfg.first_k_dense, cfg.dense_d_ff, cfg.d_ff,
            cfg.vocab_size, cfg.kv_lora_rank) == (5, 1, 11264, 1408, 20480,
                                                  512)
    for key, value in [("q_lora_rank", 1536), ("n_group", 8),
                       ("topk_method", "greedy"), ("scoring_func", "softmax")]:
        with pytest.raises(ValueError, match=key):
            program.model_config(dict(c, **{key: value}))


def test_the_model_control_rounds_its_backward_at_scale():
    """The float8 control's gradient reads a precision: every leaf the
    reference moves keeps its norm within a factor of 2 (float8's
    rounding moves it by a few percent), where the float8 casts' own
    transpose, unscaled, flushes the earliest leaves' cotangents to 0."""
    from chipbench.reference import controls
    c = tiny_config()
    params = ref_model.init(jax.random.PRNGKey(SEED), c)
    tokens = _tokens(c)
    norms = lambda g: {k: float(jnp.linalg.norm(v)) for k, v in
                       jax.tree_util.tree_flatten_with_path(g)[0]}
    want = norms(ref_model.loss_and_grad(params, tokens, c)[1])
    got = norms(ref_model.loss_and_grad(params, tokens, c,
                                        controls.fp8_round)[1])
    moved = {k: v for k, v in want.items() if v > 1e-6}
    assert len(moved) > 20
    for k, v in moved.items():
        assert 0.5 < got[k] / v < 2, (k, got[k], v)
    assert ref_model.both_passes(controls.identity) is controls.identity
