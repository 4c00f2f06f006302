"""Plain float32 reference of the Muon optimizer the training cells run.

Muon as the PRISM paper trains with it (arXiv 2601.22137, Sec. 6.2):

* gradients are clipped to a global norm of ``grad_clip_norm``;
* the leaves the model family's reference names in ``muon_views`` (its
  hidden weight matrices, each at least 16 x 16) take Nesterov momentum
  ``m = mu m + g``, ``u = g + mu m``; the update is
  ``polar(U) * sqrt(max(1, rows / cols))``, where ``U`` is ``u`` viewed
  as a stack of matrices by the leaf's view (its leading batch axes
  index the matrices, its row axes form their rows), and the parameter
  moves by ``-lr (update + weight_decay p)``;
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
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from chipbench.reference.controls import identity

# a leaf's matrix view: (leading axes that index separate matrices, axes
# after them that form each matrix's rows)
View = Tuple[int, Tuple[int, ...]]


def leaf_paths(tree) -> Dict[str, object]:
    """Every leaf of a nested dict (of arrays, or of shape tuples) by its
    path, ``"layers/attn/wq"``."""
    flat = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda s: isinstance(s, tuple))[0]
    return {"/".join(str(k.key) for k in path): x for path, x in flat}


def matrix_dims(view: View, shape) -> Tuple[int, int]:
    """(rows, cols) of each matrix of a leaf."""
    batch, rows_ax = view
    per = shape[batch:]
    rows = math.prod(per[a] for a in rows_ax)
    return rows, math.prod(per) // rows


def muon_leaves(shapes: Dict, views: Dict[str, View]) -> Dict[str, View]:
    """The leaves of ``shapes`` (a parameter tree's leaf shapes) that Muon
    orthogonalizes, by path: those ``views`` names whose matrices are at
    least 16 x 16."""
    flat = leaf_paths(shapes)
    return {path: view for path, view in views.items()
            if min(matrix_dims(view, flat[path])) >= 16}


def _perm(view: View, ndim: int):
    batch, rows_ax = view
    rows = [batch + a for a in rows_ax]
    rest = [a for a in range(batch, ndim) if a not in rows]
    return list(range(batch)) + rows + rest


def _to_matrix(view: View, x: jax.Array):
    y = jnp.transpose(x, _perm(view, x.ndim))
    r, c = matrix_dims(view, x.shape)
    return y.reshape(x.shape[:view[0]] + (r, c)), y.shape


def _from_matrix(view: View, m: jax.Array, mid_shape, shape) -> jax.Array:
    perm = _perm(view, len(shape))
    inv = [perm.index(a) for a in range(len(shape))]
    return jnp.transpose(m.reshape(mid_shape), inv)


# the PRISM paper's alpha interval [l, u] per degree d (Sec. 3)
ALPHA_BOUNDS = {1: (0.5, 1.0), 2: (3.0 / 8.0, 29.0 / 20.0)}


def polar(M: jax.Array, prism: Dict, q: Callable = identity) -> jax.Array:
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


def direction(view: View, u: jax.Array, prism: Dict,
              q: Callable = identity) -> jax.Array:
    """The orthogonalized direction of a Muon leaf: ``polar(U)`` scaled
    by ``sqrt(max(1, rows / cols))``, in the leaf's own layout."""
    U, mid = _to_matrix(view, u)
    rows, cols = U.shape[-2], U.shape[-1]
    O = polar(U, prism, q) * jnp.sqrt(jnp.maximum(1.0, rows / cols))
    return _from_matrix(view, O, mid, u.shape)


def init_state(params: Dict, views: Dict[str, View]) -> Dict:
    """Zero momentum for every leaf; a second moment for AdamW leaves
    (a Muon leaf of ``views`` keeps a scalar placeholder)."""
    nu = {path: jnp.zeros((), jnp.float32) if path in views
          else jnp.zeros_like(p) for path, p in leaf_paths(params).items()}
    treedef = jax.tree.structure(params)
    return {"mom": jax.tree.map(jnp.zeros_like, params),
            "nu": jax.tree.unflatten(treedef, list(nu.values())),
            "count": jnp.zeros((), jnp.int32)}


def update(grads: Dict, state: Dict, params: Dict, h: Dict,
           views: Dict[str, View], q: Callable = identity):
    """One optimizer step; ``h`` holds the hyperparameters, ``views`` the
    Muon leaves (``muon_leaves``) and ``q`` rounds the polar chain's
    operands.  Returns (new params, new state, the clipped gradients the
    step used)."""
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, h["grad_clip_norm"] / jnp.maximum(gn, 1e-12))
    grads = jax.tree.map(lambda g: g * clip, grads)
    t = (state["count"] + 1).astype(jnp.float32)
    lr, wd = h["learning_rate"], h["weight_decay"]
    treedef = jax.tree.structure(grads)
    flat_g = leaf_paths(grads)
    flat_p = jax.tree.leaves(params)
    flat_m = jax.tree.leaves(state["mom"])
    flat_v = jax.tree.leaves(state["nu"])
    new_p, new_m, new_v = [], [], []
    for (path, g), p, m, v in zip(flat_g.items(), flat_p, flat_m, flat_v):
        if path in views:
            m = h["momentum"] * m + g
            u = g + h["momentum"] * m
            p = p * (1.0 - lr * wd) - lr * direction(views[path], u,
                                                     h["prism"], q)
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
