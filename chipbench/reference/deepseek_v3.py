"""Plain float32 reference of a DeepSeek-V3-style decoder (Moonlight).

As DeepSeek-V3's report (arXiv 2412.19437, §2.1) and its published
``modeling_deepseek.py`` describe it, without multi-token prediction:
token embedding; ``first_k_dense_replace`` dense layers, then expert
layers; each layer RMSNorm -> latent attention -> residual, RMSNorm ->
MLP or expert layer -> residual; final RMSNorm, untied head, mean
next-token cross-entropy plus the expert layers' balance losses.

* Latent attention (no q-LoRA): ``q = x W_q`` (``qk_nope`` + ``qk_rope``
  dims a head); ``[c | k_rope] = x W_kv_a``; ``[k_nope | v] =
  RMSNorm(c) W_kv_b``; rotary positions on q's rope dims and on
  ``k_rope``, which every head shares; causal softmax at scale
  ``1/sqrt(qk_nope + qk_rope)``; ``o W_o``.
* Expert layer: ``s = sigmoid(x W_g)`` over all ``n_routed_experts``;
  the ``num_experts_per_tok`` largest of ``s + b`` are chosen (``b`` the
  correction bias, for selection only); their gates are ``s`` there,
  normalized to sum 1 (``norm_topk_prob``) and scaled by
  ``routed_scaling_factor``.  This chip holds the first
  ``n_routed_experts / ep_size`` experts: each of them runs densely on
  every token and is weighted by its gate, zero where it was not
  chosen; the other chips' experts are left out.  The shared experts
  form one SwiGLU MLP of width ``n_shared_experts *
  moe_intermediate_size`` on every token.  The sequence-wise balance
  loss (``seq_aux``) is ``aux_loss_alpha * sum_i f_i P_i`` per sequence,
  averaged over sequences, with ``f_i = E / (K S) * #{t: i chosen}``
  and ``P_i`` the sequence's mean of ``s_i / sum_j s_j``, over all
  experts.

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no grouping, no
padding.  Attention is computed one block of queries at a time, an
exact softmax over all keys, and the cross-entropy one block of
positions at a time, so that 8k-token sequences fit.  Imports nothing
of the system under test.

Departures from the published model, each stated in the configuration
file:

* rotary positions rotate the pairs (i, i + d/2) of the rope dims; the
  published code rotates interleaved pairs (2i, 2i + 1).  The two are
  one fixed permutation of the rope columns of ``W_q`` and ``W_kv_a``
  apart, so with random weights they are the same model;
* an RMSNorm weight is stored as an offset, ``x_hat * (1 + s)`` with
  ``s`` starting at 0 (the published form is ``x_hat * w``);
* the correction bias is a seeded leaf held fixed (DeepSeek-V3's bias
  update speed gamma = 0, its last phase): it receives no gradient, and
  the optimizer treats it as any other non-matrix leaf;
* the latent's RMSNorm takes eps 1e-6, the default of the published
  code's norm, and the block norms ``rms_norm_eps``.

Parameters are a nested dict, layer-stacked::

    embed [V, d]  head [d, V]  final_ln [d]
    {dense_layers, layers}/ln1, ln2 [L, d]
    {dense_layers, layers}/attn/wq [L, d, H, dn + dr]  wkv_a [L, d, r + dr]
        kv_norm [L, r]  wkv_b [L, r, H, dn + dv]  wo [L, H, dv, d]
    dense_layers/mlp/w_gate, w_up [Ld, d, F]  w_down [Ld, F, d]
    layers/moe/router [L, d, E]  correction_bias [L, E]
    layers/moe/w_gate, w_up [L, E_held, d, f]  w_down [L, E_held, f, d]
    layers/moe/shared/w_gate, w_up [L, d, n_s f]  w_down [L, n_s f, d]
"""
from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.controls import identity

LATENT_EPS = 1e-6
# the correction bias's seed scale
BIAS_STD = 1e-2
# queries per attention block, positions per cross-entropy block
Q_BLOCK = 512
CE_BLOCK = 1024


def _dims(c: Dict) -> Dict[str, int]:
    return {"d": c["hidden_size"], "H": c["num_attention_heads"],
            "r": c["kv_lora_rank"], "dn": c["qk_nope_head_dim"],
            "dr": c["qk_rope_head_dim"], "dv": c["v_head_dim"],
            "Ld": c["first_k_dense_replace"],
            "Le": c["num_hidden_layers"] - c["first_k_dense_replace"],
            "F": c["intermediate_size"], "f": c["moe_intermediate_size"],
            "E": c["n_routed_experts"],
            "Eh": c["n_routed_experts"] // c["ep_size"],
            "K": c["num_experts_per_tok"], "ns": c["n_shared_experts"],
            "V": c["vocab_size"]}


def param_shapes(c: Dict) -> Dict:
    """Leaf shapes of the parameter tree for configuration ``c``."""
    n = _dims(c)
    d, H, r, dn, dr, dv = (n[k] for k in ("d", "H", "r", "dn", "dr", "dv"))

    def block(L):
        return {"ln1": (L, d), "ln2": (L, d),
                "attn": {"wq": (L, d, H, dn + dr), "wkv_a": (L, d, r + dr),
                         "kv_norm": (L, r), "wkv_b": (L, r, H, dn + dv),
                         "wo": (L, H, dv, d)}}

    def mlp(L, w):
        return {"w_gate": (L, d, w), "w_up": (L, d, w), "w_down": (L, w, d)}

    Ld, Le, E, Eh, f = n["Ld"], n["Le"], n["E"], n["Eh"], n["f"]
    out = {"embed": (n["V"], d), "head": (d, n["V"]), "final_ln": (d,),
           "layers": dict(block(Le), moe={
               "router": (Le, d, E), "correction_bias": (Le, E),
               "w_gate": (Le, Eh, d, f), "w_up": (Le, Eh, d, f),
               "w_down": (Le, Eh, f, d), "shared": mlp(Le, n["ns"] * f)})}
    if Ld:
        out["dense_layers"] = dict(block(Ld), mlp=mlp(Ld, n["F"]))
    return out


def muon_views(c: Dict) -> Dict:
    """Muon's matrix view of each hidden matrix, layer by layer (and
    expert by expert): the model width as the rows (W_q [d, H (dn+dr)],
    W_kv_a [d, r + dr], W_o and the down projections by their output
    side), and the latent as W_kv_b's rows ([r, H (dn + dv)])."""
    views = {}
    stacks = ["layers"] + (["dense_layers"] if c["first_k_dense_replace"]
                           else [])
    for s in stacks:
        views.update({f"{s}/attn/wq": (1, (0,)), f"{s}/attn/wkv_a": (1, (0,)),
                      f"{s}/attn/wkv_b": (1, (0,)), f"{s}/attn/wo": (1, (2,))})
    mlps = ["layers/moe/shared"] + (["dense_layers/mlp"]
                                    if c["first_k_dense_replace"] else [])
    for m in mlps:
        views.update({f"{m}/w_gate": (1, (0,)), f"{m}/w_up": (1, (0,)),
                      f"{m}/w_down": (1, (1,))})
    views.update({"layers/moe/router": (1, (0,)),
                  "layers/moe/w_gate": (2, (0,)),
                  "layers/moe/w_up": (2, (0,)),
                  "layers/moe/w_down": (2, (1,))})
    return views


def matmul_params(c: Dict) -> int:
    """Matrix parameters a token goes through, per token: every layer's
    attention projections, the dense MLPs, the routers and shared
    experts, the held experts at their balanced share of the token
    (``num_experts_per_tok * E_held / E`` assignments of a token land
    here on average), and the head.  The embedding lookup is a gather
    and is not counted."""
    n = _dims(c)
    d = n["d"]
    mla = (d * n["H"] * (n["dn"] + n["dr"]) + d * (n["r"] + n["dr"])
           + n["r"] * n["H"] * (n["dn"] + n["dv"]) + n["H"] * n["dv"] * d)
    dense = mla + 3 * d * n["F"]
    routed = n["K"] * n["Eh"] / n["E"] * 3 * d * n["f"]
    expert = mla + d * n["E"] + 3 * d * n["ns"] * n["f"] + routed
    return round(n["Ld"] * dense + n["Le"] * expert + d * n["V"])


def train_step_flops(c: Dict, seq: int, batch: int) -> int:
    """Model FLOPs of one forward and backward pass over [batch, seq]
    tokens: 6 per matrix parameter a token goes through
    (``matmul_params``: the held experts counted at their balanced 0.75
    assignments a token, not at the realized routing), plus the causal
    attention the kernel computes, 3 H (dqk + dv) seq per token and
    layer (half the square of scores and values, forward and backward).
    Recomputation and the optimizer are not counted."""
    n = _dims(c)
    attn = (3 * (n["Ld"] + n["Le"]) * n["H"] * (n["dn"] + n["dr"] + n["dv"])
            * seq)
    return round(seq * batch * (6 * matmul_params(c) + attn))


def _fan_in(path, shape) -> int:
    name = path[-1]
    if name == "wo":
        return shape[-3] * shape[-2]
    if name == "wq" or name == "wkv_b":
        return shape[-3]
    return shape[-2]


def init(key: jax.Array, c: Dict) -> Dict:
    """Weights from ``key``: matrices ~ N(0, 1/fan_in) rounded to
    bfloat16 (the dtype the model computes in) and held in float32, the
    embedding ~ N(0, 1/d) likewise, the correction bias ~ N(0,
    ``BIAS_STD``^2) in float32, norm offsets 0."""
    shapes = param_shapes(c)
    paths = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))[0]
    flat = []
    for i, (path, shape) in enumerate(paths):
        names = [k.key for k in path]
        k = jax.random.fold_in(key, i)
        if names[-1] == "correction_bias":
            flat.append(BIAS_STD * jax.random.normal(k, shape, jnp.float32))
        elif len(shape) == 1 or names[-1] in ("ln1", "ln2", "kv_norm"):
            flat.append(jnp.zeros(shape, jnp.float32))
        else:
            fan = shape[-1] if names[-1] == "embed" else _fan_in(names, shape)
            w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan)
            flat.append(w.astype(jnp.bfloat16).astype(jnp.float32))
    treedef = jax.tree.structure(shapes,
                                 is_leaf=lambda s: isinstance(s, tuple))
    return jax.tree.unflatten(treedef, flat)


def _rms(x, s, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + s)


def _rope(x, theta):
    """x [B, S, N, dr]: rotates each pair (i, i + dr/2) by the angle
    pos * theta^(-2i/dr)."""
    S, dr = x.shape[1], x.shape[-1]
    half = dr // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _causal_attention(q, k, v, scale, q8: Callable):
    """softmax(q k^T * scale, causal) v over every key, one block of
    queries at a time; q/k [B, S, H, dqk], v [B, S, H, dv]."""
    B, S, H, _ = q.shape
    blk = min(Q_BLOCK, S)
    if S % blk:
        raise ValueError(f"{S} positions do not split into blocks of {blk}")
    nb = S // blk

    @jax.checkpoint
    def one(args):
        qi, i = args
        s = jnp.einsum("bqnk,btnk->bnqt", q8(qi), q8(k)) * scale
        pos = i * blk + jnp.arange(blk)
        causal = pos[:, None] >= jnp.arange(S)[None, :]
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bnqt,btnk->bqnk", q8(w), q8(v))

    qb = q.reshape(B, nb, blk, H, q.shape[-1]).swapaxes(0, 1)
    out = jax.lax.map(one, (qb, jnp.arange(nb)))
    return out.swapaxes(0, 1).reshape(B, S, H, v.shape[-1])


def _mla(a, x, c, q8: Callable):
    n = _dims(c)
    r, dn = n["r"], n["dn"]
    q = jnp.einsum("bsd,dnk->bsnk", q8(x), q8(a["wq"]))
    kv_a = q8(x) @ q8(a["wkv_a"])
    latent = _rms(kv_a[..., :r], a["kv_norm"], LATENT_EPS)
    kv = jnp.einsum("bsr,rnk->bsnk", q8(latent), q8(a["wkv_b"]))
    k_rope = _rope(kv_a[..., None, r:], c["rope_theta"])
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], c["rope_theta"])],
                        -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, kv.shape[:3] + (n["dr"],))],
        -1)
    o = _causal_attention(q, k, kv[..., dn:],
                          1.0 / math.sqrt(dn + n["dr"]), q8)
    return jnp.einsum("bsnk,nkd->bsd", q8(o), q8(a["wo"]))


def _swiglu(m, x, q8: Callable, weight=1.0):
    """``weight * SwiGLU(x)``; recomputed in the backward pass, so that
    only its inputs are kept."""
    @jax.checkpoint
    def f(m, x, weight):
        h = jax.nn.silu(q8(x) @ q8(m["w_gate"])) * (q8(x) @ q8(m["w_up"]))
        return weight * (q8(h) @ q8(m["w_down"]))
    return f(m, x, weight)


def routing(m, x, c, q8: Callable = identity):
    """x [..., d] -> (scores [..., E], chosen ids [..., K], gates [...,
    K]): the sigmoid scores of all experts, the K largest of scores plus
    the correction bias, and the chosen scores normalized and scaled."""
    s = jax.nn.sigmoid(q8(x) @ q8(m["router"]))
    _, ids = jax.lax.top_k(s + m["correction_bias"],
                           c["num_experts_per_tok"])
    g = jnp.take_along_axis(s, ids, axis=-1)
    if c["norm_topk_prob"]:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return s, ids, g * c["routed_scaling_factor"]


def moe(m, x, c, q8: Callable = identity):
    """The expert layer on x [B, S, d]: the held experts' part of the
    routed output plus the shared experts -> (y, balance loss)."""
    n = _dims(c)
    E, Eh, K = n["E"], n["Eh"], n["K"]
    s, ids, g = routing(m, x, c, q8)
    onehot = jax.nn.one_hot(ids, E, dtype=jnp.float32)  # [B, S, K, E]
    weight = jnp.einsum("bske,bsk->bse", onehot[..., :Eh], g)
    experts = {w: m[w] for w in ("w_gate", "w_up", "w_down")}

    def one(y, expert_and_weight):  # one held expert on every token
        expert, w = expert_and_weight
        return y + _swiglu(expert, x, q8, w[..., None]), None

    y, _ = jax.lax.scan(one, _swiglu(m["shared"], x, q8),
                        (experts, jnp.moveaxis(weight, -1, 0)))
    aux = jnp.float32(0.0)
    if c["seq_aux"]:
        S = x.shape[1]
        f = jnp.sum(onehot, axis=(1, 2)) * (E / (K * S))
        p = jnp.mean(s / jnp.sum(s, axis=-1, keepdims=True), axis=1)
        aux = c["aux_loss_alpha"] * jnp.mean(jnp.sum(f * p, axis=-1))
    return y, aux


def _dense_layer(p, x, c, q8):
    eps = c["rms_norm_eps"]
    x = x + _mla(p["attn"], _rms(x, p["ln1"], eps), c, q8)
    return x + _swiglu(p["mlp"], _rms(x, p["ln2"], eps), q8)


def _expert_layer(p, x, c, q8):
    eps = c["rms_norm_eps"]
    x = x + _mla(p["attn"], _rms(x, p["ln1"], eps), c, q8)
    y, aux = moe(p["moe"], _rms(x, p["ln2"], eps), c, q8)
    return x + y, aux


def _cross_entropy(x, head, targets, q8):
    """Mean next-token cross-entropy of hidden states x [B, P, d] against
    targets [B, P], one block of positions at a time."""
    B, P, _ = x.shape
    blk = min(CE_BLOCK, P)
    nb = -(-P // blk)
    pad = nb * blk - P
    xs = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    ts = jnp.pad(targets, ((0, 0), (0, pad)))
    ok = jnp.pad(jnp.ones((B, P), jnp.float32), ((0, 0), (0, pad)))

    @jax.checkpoint
    def one(args):
        xi, ti, oi = args
        logp = jax.nn.log_softmax(q8(xi) @ q8(head), axis=-1)
        nll = -jnp.take_along_axis(logp, ti[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * oi)

    split = lambda a: a.reshape((B, nb, blk) + a.shape[2:]).swapaxes(0, 1)
    return jnp.sum(jax.lax.map(one, (split(xs), split(ts), split(ok)))) \
        / (B * P)


def loss(params: Dict, tokens: jax.Array, c: Dict,
         q8: Callable = identity) -> jax.Array:
    """Mean cross-entropy of predicting tokens[:, 1:] from tokens[:, :-1]
    context over every position of the batch [B, S], plus every expert
    layer's balance loss."""
    x = params["embed"][tokens]
    if c["first_k_dense_replace"]:
        x, _ = jax.lax.scan(jax.checkpoint(
            lambda x, p: (_dense_layer(p, x, c, q8), None)),
            x, params["dense_layers"])
    x, aux = jax.lax.scan(jax.checkpoint(
        lambda x, p: _expert_layer(p, x, c, q8)), x, params["layers"])
    aux = jnp.sum(aux)
    x = _rms(x, params["final_ln"], c["rms_norm_eps"])[:, :-1]
    return _cross_entropy(x, params["head"], tokens[:, 1:], q8) + aux


def both_passes(q8: Callable) -> Callable:
    """``q8`` in the backward pass too: the cotangent that reaches a
    rounded operand is rounded the same way, with its own scale.  A
    float8 cast's own transpose would round the cotangent with no scale,
    and the cotangents of this loss, mostly far under float8's smallest
    step, would flush to 0 (at a tiny size the earliest leaves' gradients
    read 0 to 1e-7 of the reference's 0.2-0.4), so the control's
    gradient would read a flushed backward, not a precision."""
    if q8 is identity:
        return q8

    @jax.custom_vjp
    def rounded(x):
        return q8(x)

    rounded.defvjp(lambda x: (q8(x), None), lambda _, ct: (q8(ct),))
    return rounded


def loss_and_grad(params: Dict, tokens: jax.Array, c: Dict,
                  q8: Callable = identity, block_rows: int = 0):
    """The loss and its gradient, ``q8`` in both passes
    (``both_passes``); with ``block_rows`` the batch is taken in blocks
    of that many rows, one after the other, and their equal shares
    summed (every row has the same length, and the balance loss is a
    mean over sequences), so that only one block's activations are alive
    at a time."""
    q8 = both_passes(q8)
    rows = tokens.shape[0]
    block = block_rows or rows
    if rows % block:
        raise ValueError(f"{rows} rows do not split into blocks of {block}")
    total, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for i in range(0, rows, block):
            lv, g = jax.value_and_grad(loss)(params, tokens[i:i + block],
                                             c, q8)
            total = total + lv * (block / rows)
            g = jax.tree.map(lambda x: x * (block / rows), g)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total, grads
