"""Plain float32 reference of the Muon optimizer the training cells run.

Muon as the PRISM paper trains with it (arXiv 2601.22137, Sec. 6.2):

* gradients are clipped to a global norm of ``grad_clip_norm``;
* hidden weight matrices (attention q/k/v/o, their biases when they are
  [heads, head_dim] matrices of at least 16 x 16, the MLP) take
  Nesterov momentum ``m = mu m + g``, ``u = g + mu m``; the update is
  ``polar(U) * sqrt(max(1, rows / cols))``, where ``U`` is ``u`` viewed
  as a matrix with the model width as its rows (a bias: heads as rows),
  and the parameter moves by ``-lr (update + weight_decay p)``;
* every other leaf (embedding, LM head, norm offsets) takes AdamW at
  ``lr * adamw_lr_scale`` with bias-corrected moments.

``polar`` runs the Newton-Schulz iteration the configuration states
(degree, iteration count, alpha) in float32 at the highest matmul
precision (or, for the control, with every product's operands rounded
to the precision below).  It is not the exact polar factor: three warm
iterations leave the singular values between about 0.6 and 1.15, and the
system is held to the iteration it is configured to run.  Imports
nothing of the system under test.
"""
from __future__ import annotations

import math
from typing import Callable, Dict

import jax
import jax.numpy as jnp

# matrix leaves -> (axes of the leaf, without the layer axis, that form
# the rows of the matrix view)
_MATRIX_ROWS = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (2,),
                "w_up": (0,), "w_gate": (0,), "w_down": (1,),
                "bq": (0,), "bk": (0,), "bv": (0,)}


def _identity(x):
    return x


def is_muon_leaf(name: str, shape) -> bool:
    if name not in _MATRIX_ROWS:
        return False
    rows, cols = matrix_dims(name, shape)
    return min(rows, cols) >= 16


def matrix_dims(name: str, shape):
    """(rows, cols) of the matrix view of one layer's slice of a leaf."""
    per_layer = shape[1:]
    rows_ax = _MATRIX_ROWS[name]
    rows = 1
    for a in rows_ax:
        rows *= per_layer[a]
    cols = 1
    for a, n in enumerate(per_layer):
        if a not in rows_ax:
            cols *= n
    return rows, cols


def _to_matrix(name: str, x: jax.Array) -> jax.Array:
    rows_ax = [a + 1 for a in _MATRIX_ROWS[name]]
    rest = [a for a in range(1, x.ndim) if a not in rows_ax]
    y = jnp.transpose(x, [0] + rows_ax + rest)
    r, c = matrix_dims(name, x.shape)
    return y.reshape(x.shape[0], r, c), y.shape


def _from_matrix(name: str, m: jax.Array, mid_shape, shape) -> jax.Array:
    rows_ax = [a + 1 for a in _MATRIX_ROWS[name]]
    rest = [a for a in range(1, len(shape)) if a not in rows_ax]
    perm = [0] + rows_ax + rest
    inv = [perm.index(a) for a in range(len(shape))]
    return jnp.transpose(m.reshape(mid_shape), inv)


# the PRISM paper's alpha interval [l, u] per degree d (Sec. 3)
ALPHA_BOUNDS = {1: (0.5, 1.0), 2: (3.0 / 8.0, 29.0 / 20.0)}


def polar(M: jax.Array, prism: Dict, q: Callable = _identity) -> jax.Array:
    """The configured polar iteration on every [..., m, n] slice, in
    float32 at the highest matmul precision: the slice (taken with its
    long side as rows) is divided by its Frobenius norm, then each of
    ``prism["iterations"]`` steps sets ``R = I - X^T X`` and
    ``X = X (f(R) + alpha R^d)``, with ``f`` the degree ``d - 1`` Taylor
    polynomial of ``(1 - r)^(-1/2)`` and ``alpha`` the interval's upper
    end ``u`` (the paper's warm start).  ``q`` is applied to both
    operands of every product.  Fitted iterations (beyond
    ``warm_alpha_iters``) are not modelled here."""
    d, iters = prism["degree"], prism["iterations"]
    if prism["warm_alpha_iters"] < iters:
        raise NotImplementedError("the reference models warm iterations "
                                  "only")
    alpha = ALPHA_BOUNDS[d][1]
    taylor = [math.comb(2 * j, j) / 4 ** j for j in range(d)]
    m, n = M.shape[-2], M.shape[-1]
    X = jnp.swapaxes(M, -1, -2) if m < n else M
    fro = jnp.sqrt(jnp.sum(jnp.square(X), axis=(-2, -1), keepdims=True))
    X = X / jnp.maximum(fro, 1e-30)
    eye = jnp.eye(X.shape[-1], dtype=X.dtype)
    with jax.default_matmul_precision("highest"):
        for _ in range(iters):
            R = eye - q(jnp.swapaxes(X, -1, -2)) @ q(X)
            P, Rj = taylor[0] * eye, eye
            for c in taylor[1:]:
                Rj = q(Rj) @ q(R)
                P = P + c * Rj
            P = P + alpha * (q(Rj) @ q(R))
            X = q(X) @ q(P)
    return jnp.swapaxes(X, -1, -2) if m < n else X


def bf16_round(x: jax.Array) -> jax.Array:
    """Round trip through bfloat16: the control's precision, one step
    below the float32 the chain is configured to run in."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def direction(name: str, u: jax.Array, prism: Dict,
              q: Callable = _identity) -> jax.Array:
    """The orthogonalized direction of a Muon leaf: ``polar(U)`` scaled
    by ``sqrt(max(1, rows / cols))``, in the leaf's own layout."""
    U, mid = _to_matrix(name, u)
    rows, cols = U.shape[-2], U.shape[-1]
    O = polar(U, prism, q) * jnp.sqrt(jnp.maximum(1.0, rows / cols))
    return _from_matrix(name, O, mid, u.shape)


def _named_leaves(tree):
    paths, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return [(p[-1].key, x) for p, x in paths], treedef


def init_state(params: Dict) -> Dict:
    """Zero momentum for every leaf; a second moment for AdamW leaves
    (a Muon leaf keeps a scalar placeholder)."""
    named, treedef = _named_leaves(params)
    nu = [jnp.zeros((), jnp.float32) if is_muon_leaf(n, p.shape)
          else jnp.zeros_like(p) for n, p in named]
    return {"mom": jax.tree.map(jnp.zeros_like, params),
            "nu": jax.tree.unflatten(treedef, nu),
            "count": jnp.zeros((), jnp.int32)}


def update(grads: Dict, state: Dict, params: Dict, h: Dict,
           q: Callable = _identity):
    """One optimizer step; ``h`` holds the hyperparameters and ``q``
    rounds the polar chain's operands.  Returns (new params, new state,
    the clipped gradients the step used)."""
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, h["grad_clip_norm"] / jnp.maximum(gn, 1e-12))
    grads = jax.tree.map(lambda g: g * clip, grads)
    t = (state["count"] + 1).astype(jnp.float32)
    lr, wd = h["learning_rate"], h["weight_decay"]
    named_g, treedef = _named_leaves(grads)
    flat_p = jax.tree.leaves(params)
    flat_m = jax.tree.leaves(state["mom"])
    flat_v = jax.tree.leaves(state["nu"])
    new_p, new_m, new_v = [], [], []
    for (name, g), p, m, v in zip(named_g, flat_p, flat_m, flat_v):
        if is_muon_leaf(name, p.shape):
            m = h["momentum"] * m + g
            u = g + h["momentum"] * m
            p = p * (1.0 - lr * wd) - lr * direction(name, u, h["prism"], q)
        else:
            b1, b2 = h["beta1"], h["beta2"]
            m = b1 * m + (1.0 - b1) * g
            v = b2 * v + (1.0 - b2) * jnp.square(g)
            alr = lr * h["adamw_lr_scale"]
            mhat, vhat = m / (1.0 - b1 ** t), v / (1.0 - b2 ** t)
            p = p * (1.0 - alr * wd) - alr * mhat / (jnp.sqrt(vhat)
                                                     + h["eps"])
        new_p.append(p)
        new_m.append(m)
        new_v.append(v)
    un = lambda xs: jax.tree.unflatten(treedef, xs)  # noqa: E731
    return un(new_p), {"mom": un(new_m), "nu": un(new_v),
                       "count": state["count"] + 1}, grads
