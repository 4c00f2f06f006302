"""Plain float32 reference of the dense decoder the training cells run.

A pre-norm decoder as the model cards describe it: token embedding, per
layer RMSNorm -> causal self-attention (grouped-query, optional q/k/v
bias, optional per-head RMSNorm on q and k, rotary positions on the
half-split head dimension) -> residual, RMSNorm -> MLP (SwiGLU or tanh
GELU) -> residual; final RMSNorm, untied LM head, mean next-token
cross-entropy.  Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no chunking, no
padding.  Imports nothing of the system under test.

Departures from the published models, each one the system's documented
choice and stated in the configuration file:

* an RMSNorm weight is stored as an offset, ``x_hat * (1 + s)`` with
  ``s`` starting at 0 (the published form is ``x_hat * w`` with ``w``
  starting at 1; the forward pass is the same, weight decay acts on
  ``s``);
* gpt2-paper uses rotary positions, biases on q, k and v only, and an
  untied head.

Parameters are a nested dict laid out per layer-stacked leaf::

    embed [V, d]  head [d, V]  final_ln [d]
    layers/ln1 [L, d]  layers/ln2 [L, d]
    layers/attn/wq [L, d, H, hd]  wk, wv [L, d, KV, hd]  wo [L, H, hd, d]
    layers/attn/bq [L, H, hd]  bk, bv [L, KV, hd]        (attention_bias)
    layers/attn/q_norm, k_norm [L, hd]                   (qk_norm)
    layers/mlp/w_up [L, d, f]  w_down [L, f, d]  w_gate [L, d, f] (silu)
"""
from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp

from chipbench.reference.controls import identity


def param_shapes(c: Dict) -> Dict:
    """Leaf shapes of the parameter tree for configuration ``c``."""
    d, V, L = c["hidden_size"], c["vocab_size"], c["num_hidden_layers"]
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    f = c["intermediate_size"]
    attn = {"wq": (L, d, H, hd), "wk": (L, d, KV, hd), "wv": (L, d, KV, hd),
            "wo": (L, H, hd, d)}
    if c["attention_bias"]:
        attn.update(bq=(L, H, hd), bk=(L, KV, hd), bv=(L, KV, hd))
    if c["qk_norm"]:
        attn.update(q_norm=(L, hd), k_norm=(L, hd))
    mlp = {"w_up": (L, d, f), "w_down": (L, f, d)}
    if c["hidden_act"] == "silu":
        mlp["w_gate"] = (L, d, f)
    out = {"embed": (V, d), "final_ln": (d,),
           "layers": {"ln1": (L, d), "ln2": (L, d), "attn": attn,
                      "mlp": mlp}}
    if not c["tie_word_embeddings"]:
        out["head"] = (d, V)
    return out


def muon_views(c: Dict) -> Dict:
    """Muon's matrix view of each hidden matrix, layer by layer: the
    model width as the rows (``wo``, ``w_down``: their output side), a
    bias's heads as its rows."""
    views = {"layers/attn/wq": (1, (0,)), "layers/attn/wk": (1, (0,)),
             "layers/attn/wv": (1, (0,)), "layers/attn/wo": (1, (2,)),
             "layers/mlp/w_up": (1, (0,)), "layers/mlp/w_down": (1, (1,))}
    if c["attention_bias"]:
        views.update({"layers/attn/" + b: (1, (0,))
                      for b in ("bq", "bk", "bv")})
    if c["hidden_act"] == "silu":
        views["layers/mlp/w_gate"] = (1, (0,))
    return views


def matmul_params(c: Dict) -> int:
    """Parameters that take part in a matrix product per token: every
    layer's projections and MLP, and the LM head.  The embedding lookup
    is a gather and is not counted."""
    d, f, L = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    attn = d * H * hd * 2 + d * KV * hd * 2
    mlp = d * f * (3 if c["hidden_act"] == "silu" else 2)
    return L * (attn + mlp) + d * c["vocab_size"]


def layer_matrix_params(c: Dict) -> int:
    """Matrix parameters of one decoder layer (projections and MLP)."""
    return (matmul_params(dict(c, num_hidden_layers=1))
            - c["hidden_size"] * c["vocab_size"])


def train_step_flops(c: Dict, seq: int, batch: int) -> int:
    """Model FLOPs of one forward and backward pass over [batch, seq]
    tokens: 6 per matrix parameter per token, plus the attention score
    and value products (12 L H hd seq per token, the full square the
    program computes).  Recomputation and the optimizer are not counted."""
    tokens = seq * batch
    attn = 12 * c["num_hidden_layers"] * c["num_attention_heads"] \
        * c["head_dim"] * seq
    return tokens * (6 * matmul_params(c) + attn)


# fan-in axis of each matrix leaf (negative, counted from the end)
_FAN_IN = {"wq": -3, "wk": -3, "wv": -3, "wo": -3, "w_up": -2,
           "w_down": -2, "w_gate": -2, "head": -2}


def init(key: jax.Array, c: Dict) -> Dict:
    """Weights from ``key``: matrices ~ N(0, 1/fan_in), the embedding
    ~ N(0, 1/d), biases and norm offsets 0; every value rounded to
    bfloat16 (the dtype the model computes in) and held in float32."""
    shapes = param_shapes(c)
    paths = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))[0]
    flat = []
    for i, (path, shape) in enumerate(paths):
        name = path[-1].key
        if name in _FAN_IN or name == "embed":
            std = 1.0 / math.sqrt(shape[_FAN_IN[name]] if name in _FAN_IN
                                  else shape[-1])
            w = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * std
            flat.append(w.astype(jnp.bfloat16).astype(jnp.float32))
        else:
            flat.append(jnp.zeros(shape, jnp.float32))
    treedef = jax.tree.structure(shapes,
                                 is_leaf=lambda s: isinstance(s, tuple))
    return jax.tree.unflatten(treedef, flat)


def _rms(x, s, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + s)


def _rope(x, theta):
    """x [B, S, N, hd]: rotates each pair (i, i + hd/2) by the angle
    pos * theta^(-2i/hd)."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _layer(p, x, c, q8: Callable):
    """One decoder layer on x [B, S, d].  ``q8`` is applied to both
    operands of every matrix product (identity, or the control's
    lower-precision rounding)."""
    eps = c["rms_norm_eps"]
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    a = p["attn"]
    h = _rms(x, p["ln1"], eps)
    q = jnp.einsum("bsd,dnk->bsnk", q8(h), q8(a["wq"]))
    k = jnp.einsum("bsd,dnk->bsnk", q8(h), q8(a["wk"]))
    v = jnp.einsum("bsd,dnk->bsnk", q8(h), q8(a["wv"]))
    if c["attention_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    if c["qk_norm"]:
        q = _rms(q, a["q_norm"], eps)
        k = _rms(k, a["k_norm"], eps)
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    S = x.shape[1]
    s = jnp.einsum("bsnk,btnk->bnst", q8(q), q8(k)) / math.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bnst,btnk->bsnk", q8(w), q8(v))
    x = x + jnp.einsum("bsnk,nkd->bsd", q8(o), q8(a["wo"]))
    m = p["mlp"]
    h = _rms(x, p["ln2"], eps)
    up = q8(h) @ q8(m["w_up"])
    if c["hidden_act"] == "silu":
        act = jax.nn.silu(q8(h) @ q8(m["w_gate"])) * up
    else:
        act = _gelu_tanh(up)
    return x + q8(act) @ q8(m["w_down"])


def loss(params: Dict, tokens: jax.Array, c: Dict,
         q8: Callable = identity) -> jax.Array:
    """Mean cross-entropy of predicting tokens[:, 1:] from tokens[:, :-1]
    context, over every position of the batch [B, S]."""
    x = params["embed"][tokens]
    layer = jax.checkpoint(lambda p, x: _layer(p, x, c, q8))
    for i in range(c["num_hidden_layers"]):
        x = layer(jax.tree.map(lambda a: a[i], params["layers"]), x)
    x = _rms(x, params["final_ln"], c["rms_norm_eps"])[:, :-1]
    head = params["embed"].T if c["tie_word_embeddings"] else params["head"]
    logits = q8(x) @ q8(head)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return jnp.mean(nll)


def loss_and_grad(params: Dict, tokens: jax.Array, c: Dict,
                  q8: Callable = identity, block_rows: int = 0):
    """The loss and its gradient; with ``block_rows`` the batch is taken
    in blocks of that many rows, one after the other, and their equal
    shares summed (every row has the same length), so that only one
    block's activations are alive at a time."""
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
