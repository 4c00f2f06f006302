"""Chebyshev iteration for the matrix inverse (paper App. A.4).

  X_0 = A^T / ||A||_F^2-free normalization (we scale A by ||A||_F first),
  R_k = I - A X_k,
  X_{k+1} = X_k (I + R_k + a_k R_k^2).

Classical Chebyshev is a_k = 1; PRISM fits a_k over [1/2, 2] by minimizing
||S (R^2 - a (R^2 - R^3))||_F^2, a closed-form quadratic in a.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import polynomials as poly
from repro.core import prism
from repro.core.newton_schulz import IterInfo, _safe_fro
from repro.kernels import ops as kops


def inv(A: jax.Array, iters: int = 20, method: str = "prism",
        sketch_dim: int = 8, key: Optional[jax.Array] = None,
        dtype=jnp.float32, alpha_bounds=(0.5, 2.0),
        return_info: bool = False, tol: Optional[float] = None,
        return_iters: bool = False, return_status: bool = False,
        divergence_factor: float = 10.0):
    """A^{-1} for full-rank square A via (PRISM-)Chebyshev iteration.

    tol: adaptive early-stopping certificate (DESIGN.md §11): with
      ``method="prism"`` the whole chain runs as one ``lax.while_loop``
      that freezes each batch slice (bit-stably, masked identity update)
      once its sketched residual estimate est_r ~ ||I - A X_k||_F drops
      to tol, exiting when the slowest slice certifies; ``iters`` is
      then a budget.  The classical method has no trace chain to read a
      certificate from, so it ignores tol and runs the fixed ``iters``
      (as does ``return_info``, which must stack per-iteration values).
    return_iters: also return per-matrix ``iters_used`` (int32,
      shape ``A.shape[:-2]``).
    return_status: also return the per-matrix int8 guardian status
      (prism.STATUS_*, DESIGN.md §15); ``divergence_factor`` is the
      detector threshold of the adaptive loop.  All-zeros on the
      non-adaptive paths, which carry no certificate to read.
    """
    in_dtype = A.dtype
    n = A.shape[-1]
    # zero-slice guard (§15): 0/0 normalization would poison X_0 before
    # the certificate ever runs — clamp like the NS entry points do
    c = _safe_fro(A).astype(dtype)
    Ah = A.astype(dtype) / c
    X = jnp.swapaxes(Ah, -1, -2)
    apoly = poly.chebyshev_residual()
    batch = A.shape[:-2]
    adaptive = tol is not None and method == "prism" and not return_info

    def residual(X_):
        # fp32-accumulated products, rounded once to the compute dtype
        # (matches the kernel accumulation contract, DESIGN.md §9)
        return (jnp.eye(n, dtype=jnp.float32)
                - jnp.matmul(Ah, X_, preferred_element_type=jnp.float32)
                ).astype(dtype)

    def fit(R, k):
        # R = I - A X is NOT symmetric in general; the trace machinery
        # needs symmetric R, which holds here because X_0 = A^T makes
        # every X_k a polynomial in A^T A times A^T => A X_k symmetric.
        kk = prism.alpha_schedule_key(key, k) if key is not None else None
        return prism.fit_alpha(R, apoly, *alpha_bounds, key=kk,
                               sketch_dim=sketch_dim, return_est_r=True)

    def step(X_, R, a):
        ab = a.astype(dtype)[..., None, None]
        XR = kops.matmul_add(X_, R)
        return X_ + XR + ab * kops.matmul_add(XR, R)

    if adaptive:
        out_it, used, status = prism.adaptive_masked_loop(
            {"X": X},
            lambda it, k: (lambda R: (R,) + fit(R, k))(residual(it["X"])),
            lambda it, R, a: {"X": step(it["X"], R, a)},
            tol, 0, iters, batch, divergence_factor=divergence_factor)
        X = out_it["X"]
    else:
        alphas, fros = [], []
        for k in range(iters):
            R = residual(X)
            if method == "prism":
                a, _ = fit(R, k)
            else:
                a = jnp.full(batch, 1.0, dtype=jnp.float32)
            if return_info:
                alphas.append(a)
                fros.append(_fro(R)[..., 0, 0])
            X = step(X, R, a)
        used = jnp.full(batch, iters, jnp.int32)
        status = jnp.zeros(batch, jnp.int8)
    out = (X / c).astype(in_dtype)
    res = (out,)
    if return_info:
        res = res + (IterInfo(jnp.stack(alphas), jnp.stack(fros)),)
    if return_iters:
        res = res + (used,)
    if return_status:
        res = res + (status,)
    return res if len(res) > 1 else res[0]
