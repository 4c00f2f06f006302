"""Coupled inverse-Newton iteration for A^{-1/p} (paper App. A.3).

  R_k = I - M_k
  X_{k+1} = X_k (I + a_k R_k),         X_0 = I / c
  M_{k+1} = (I + a_k R_k)^p M_k,       M_0 = A / c^p
  c = (2 ||A||_F / (p+1))^{1/p}

PRISM picks a_k by minimizing the sketched Frobenius norm of the next
residual, a degree-2p polynomial in alpha whose coefficients come from the
generic trace machinery (core/polynomials.inverse_newton_residual).  For
p <= 2 the minimization is closed-form; p >= 3 uses the grid+Newton path.
Classical inverse Newton is a_k = 1/p; the default constraint interval
[1/p, 2/p] contains it, so the PRISM step is never worse in ||.||_F.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import PrismConfig
from repro.core import polynomials as poly
from repro.core import prism
from repro.core.newton_schulz import IterInfo, _fro
from repro.kernels import ops as kops


def inv_proot(A: jax.Array, p: int, iters: int = 20, method: str = "prism",
              sketch_dim: int = 8, key: Optional[jax.Array] = None,
              dtype=jnp.float32, alpha_bounds: Optional[Tuple[float, float]] = None,
              return_info: bool = False, tol: Optional[float] = None,
              return_iters: bool = False, return_status: bool = False,
              divergence_factor: float = 10.0):
    """A^{-1/p} for SPD A via (PRISM-)coupled inverse Newton.

    tol: adaptive early-stopping certificate (DESIGN.md §11): with
      ``method="prism"`` the chain runs as one ``lax.while_loop`` that
      freezes BOTH coupled iterates of a batch slice (bit-stably) once
      its sketched est_r ~ ||I - M_k||_F drops to tol; ``iters`` becomes
      a budget.  Classical inverse Newton (and ``return_info``) ignores
      tol and runs the fixed count — it computes no sketched traces to
      certify from.
    return_iters: also return per-matrix ``iters_used`` (int32).
    return_status: also return the per-matrix int8 guardian status
      (prism.STATUS_*, DESIGN.md §15); ``divergence_factor`` is the
      adaptive loop's detector threshold.  All-zeros on non-adaptive
      paths.
    """
    in_dtype = A.dtype
    n = A.shape[-1]
    A32 = A.astype(dtype)
    # zero-slice guard (§15): for an all-zero slice the scale underflows
    # (XLA CPU flushes the subnormal to 0) and X_0 = I/c would start the
    # chain at inf, upstream of any certificate.  c = 1 instead keeps
    # the iterates bounded; the slice then exits as MAXITER, never OK.
    c_raw = (2.0 * _fro(A32).astype(dtype) / (p + 1)) ** (1.0 / p)
    c = jnp.where(jnp.isfinite(c_raw) & (c_raw > 0), c_raw,
                  jnp.ones_like(c_raw))
    X = jnp.broadcast_to(jnp.eye(n, dtype=dtype), A32.shape) / c
    M = A32 / c ** p
    lo, hi = alpha_bounds if alpha_bounds is not None else (1.0 / p, 2.0 / p)
    apoly = poly.inverse_newton_residual(p)
    eye = jnp.eye(n, dtype=dtype)
    batch = A.shape[:-2]
    adaptive = tol is not None and method == "prism" and not return_info

    def fit(R, k):
        kk = prism.alpha_schedule_key(key, k) if key is not None else None
        return prism.fit_alpha(R, apoly, lo, hi, key=kk,
                               sketch_dim=sketch_dim, return_est_r=True)

    def step(X_, M_, a):
        ab = a.astype(dtype)[..., None, None]
        T = eye + ab * (eye - M_)
        # fp32-accumulated chain products (DESIGN.md §9)
        Xn = kops.matmul_add(X_, T)
        Mn = M_
        for _ in range(p):
            Mn = kops.matmul_add(T, Mn)
        return Xn, Mn

    if adaptive:
        def afit(it, k):
            a, est = fit(eye - it["M"], k)
            return None, a, est

        def astep(it, _aux, a):
            Xn, Mn = step(it["X"], it["M"], a)
            return {"X": Xn, "M": Mn}

        out_it, used, status = prism.adaptive_masked_loop(
            {"X": X, "M": M}, afit, astep, tol, 0, iters, batch,
            divergence_factor=divergence_factor)
        X = out_it["X"]
    else:
        alphas, fros = [], []
        for k in range(iters):
            R = eye - M
            if method == "prism":
                a, _ = fit(R, k)
            else:
                a = jnp.full(batch, 1.0 / p, dtype=jnp.float32)
            if return_info:
                alphas.append(a)
                fros.append(_fro(R)[..., 0, 0])
            X, M = step(X, M, a)
        used = jnp.full(batch, iters, jnp.int32)
        status = jnp.zeros(batch, jnp.int8)
    # M_k = X_k^p A is invariant, so M_k -> I gives X_k -> A^{-1/p} directly;
    # the initial 1/c scaling needs no undoing.
    out = X.astype(in_dtype)
    res = (out,)
    if return_info:
        res = res + (IterInfo(jnp.stack(alphas), jnp.stack(fros)),)
    if return_iters:
        res = res + (used,)
    if return_status:
        res = res + (status,)
    return res if len(res) > 1 else res[0]
