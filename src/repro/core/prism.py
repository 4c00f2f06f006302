"""The PRISM alpha-fitting engine (meta-algorithm Part II).

Given the residual matrix R_k of any Table-1 iteration, the engine

  1. sketches power traces t_i = tr(S_k R_k^i S_k^T)  (ops.sketch_traces)
  2. maps them through the algorithm's fixed trace-weight matrix W to get
     the coefficients of the quartic (degree-2s) objective m(alpha)
  3. minimizes m over the constraint interval [l, u] in closed form.

Everything is jittable, batched over leading dims of R, and costs
O(n^2 p) — the paper's headline overhead bound.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import PrismConfig
from repro.core import polynomials as poly
from repro.core import sketch as sk
from repro.kernels import ops as kops


def fit_alpha(
    R: jax.Array,
    apoly: poly.AlphaPoly,
    lo: float,
    hi: float,
    key: Optional[jax.Array] = None,
    sketch_dim: int = 8,
    n_real: Optional[jax.Array] = None,
    return_est_r: bool = False,
):
    """alpha~_k = argmin_{alpha in [lo, hi]} || S h(R; alpha) ||_F^2.

    Args:
      R: residual matrix [..., n, n], symmetric.
      apoly: the iteration's residual polynomial h(x; alpha).
      lo, hi: the constraint interval [l, u].
      key: PRNG key for the sketch; None => exact (unsketched) traces.
      sketch_dim: p; 0 => exact traces regardless of key.
      n_real: per-matrix count of REAL dimensions when R comes from a
        zero-padded pad-to-bucket matrix (shape R.shape[:-2]); None => no
        padding.  For zero-padded polar NS the residual is exactly
        block-diagonal, R = diag(R_real, I_pad), so every power trace picks
        up the SAME pad contribution c = sum_{j >= n_real} ||S[:, j]||^2
        (identity block, i-independent).  Subtracting c from every t_i
        recovers the traces of R_real exactly — the fitted alpha is
        bit-identical to the unpadded fit with sketch S[:, :n_real]
        (DESIGN.md §7).
      return_est_r: also return the convergence certificate est_r (see
        ``fit_alpha_from_traces``) read off the same trace chain.

    Returns alpha with shape R.shape[:-2]; with ``return_est_r`` the
    tuple (alpha, est_r), est_r of the same shape (fp32).
    """
    n = R.shape[-1]
    max_pow = poly.max_trace_power(apoly)
    # Precision (DESIGN.md §9): the sketch S lives in the COMPUTE dtype of
    # R (its products are chain GEMMs), but everything downstream of the
    # trace accumulators — t, the pad-trace correction c, the W map, the
    # closed-form minimization — is pinned fp32 (MatfnPrecision.fit).  In
    # particular c must be reduced in fp32 from the same (possibly
    # bf16-rounded) S values the chain consumed: the pad block of R is
    # exactly I in any dtype, so the fp32-accumulated trace picks up
    # exactly the fp32 sum of squared pad columns, and the correction
    # stays exact under bf16 compute.
    if key is None or sketch_dim == 0:
        t = sk.exact_power_traces(R, max_pow)
        if n_real is not None:
            # exact traces: the I_pad block adds (n - n_real) to every tr(R^i)
            pad_tr = (n - n_real).astype(jnp.float32)
            t = t - pad_tr[..., None]
        return fit_alpha_from_traces(t, apoly, lo, hi,
                                     return_est_r=return_est_r)
    S = sk.gaussian_sketch(key, sketch_dim, n, dtype=R.dtype)
    t = kops.sketch_traces(R, S, max_pow)
    return fit_alpha_from_traces(t, apoly, lo, hi, S=S, n_real=n_real,
                                 return_est_r=return_est_r)


def sketch_pad_trace_correction(S: jax.Array, n_real: jax.Array) -> jax.Array:
    """c = sum_{j >= n_real} ||S[:, j]||^2 — the i-independent contribution
    the residual's identity pad block adds to EVERY sketched power trace
    of a zero-padded polar iterate (DESIGN.md §7).  fp32 end-to-end: the
    correction must be reduced in fp32 from the same (possibly
    bf16-rounded) sketch values the chain consumed (§9)."""
    n = S.shape[-1]
    s2 = jnp.sum(jnp.square(S.astype(jnp.float32)), axis=0)  # [n]
    pad_mask = jnp.arange(n) >= n_real[..., None]
    return jnp.sum(s2 * pad_mask, axis=-1)


def fit_alpha_from_traces(
    t: jax.Array,
    apoly: poly.AlphaPoly,
    lo: float,
    hi: float,
    S: Optional[jax.Array] = None,
    n_real: Optional[jax.Array] = None,
    return_est_r: bool = False,
):
    """Closed-form alpha fit from PRECOMPUTED power traces.

    The back half of ``fit_alpha``, split out so the fused
    residual+chain kernel tier (kernels/ops.residual_chain, DESIGN.md
    §10) — which reduces the traces inside the residual launch — feeds
    the identical W-map + constrained minimization.  ``t`` holds powers
    0..max_trace_power (fp32); with ``n_real`` the sketched pad-trace
    correction (requires ``S``) is applied first.

    ``return_est_r`` additionally returns the convergence certificate

        est_r = sqrt(max(t_2, 0)),   t_2 = tr(S R^2 S^T)  (pad-corrected)

    — an unbiased estimate of ||R||_F for symmetric R (E[S^T S] = I for
    the N(0, 1/p) sketch), read off the SAME trace chain the fit already
    consumed, so a per-iteration stopping certificate costs zero extra
    launches (DESIGN.md §11).  fp32 end-to-end like the fit itself; the
    §7 n_real correction keeps it exact for zero-padded bucket slices.
    With exact traces (sketch_dim=0) est_r == ||R||_F exactly; with a
    p-row sketch its relative std is ~sqrt(2/p) — a certificate, not a
    bound (see PrismConfig.tol).
    """
    if n_real is not None:
        t = t - sketch_pad_trace_correction(S, n_real)[..., None]
    W = jnp.asarray(poly.trace_weight_matrix(apoly), dtype=jnp.float32)
    coeffs = jnp.einsum("ki,...i->...k", W, t)
    alpha = poly.minimize_alpha_poly(coeffs, lo, hi)
    if return_est_r:
        return alpha, jnp.sqrt(jnp.maximum(t[..., 2], 0.0))
    return alpha


def objective_value(R: jax.Array, apoly: poly.AlphaPoly, alpha) -> jax.Array:
    """Exact m(alpha) = ||h(R; alpha)||_F^2 (test/diagnostic helper)."""
    max_pow = poly.max_trace_power(apoly)
    t = sk.exact_power_traces(R, max_pow)
    W = jnp.asarray(poly.trace_weight_matrix(apoly), dtype=jnp.float32)
    coeffs = jnp.einsum("ki,...i->...k", W, t)
    return poly._polyval_asc(coeffs, jnp.asarray(alpha, jnp.float32))


def alpha_schedule_key(key: jax.Array, k: jax.Array) -> jax.Array:
    """Per-iteration sketch key (fresh S_k each iteration, as in Thm 2)."""
    return jax.random.fold_in(key, k)


# Per-slice guardian status codes (DESIGN.md §15), int8.  Ordered by
# severity so multi-phase runs (and the optimizer's per-leaf telemetry)
# aggregate with a plain ``maximum``.
STATUS_OK = np.int8(0)           # certified: est_r <= tol before budget
STATUS_MAXITER = np.int8(1)      # budget exhausted without certifying
STATUS_QUARANTINED = np.int8(2)  # divergence detected; rolled back


def adaptive_masked_loop(iterates, fit, step, tol: float, k0: int,
                         budget: int, batch,
                         divergence_factor: float = 10.0):
    """The §11 certify-then-freeze loop driver, shared by every adaptive
    iteration family (newton_schulz fit runs, chebyshev, inverse newton).

    Runs ``lax.while_loop`` over iteration index k in [k0, k0+budget):

      aux, alpha, est_r = fit(iterates, k)     # reads the certificate
      done |= est_r <= tol                     # certify BEFORE updating
      new = step(iterates, aux, alpha)         # one family iteration
      iterates = where(~done, new, iterates)   # frozen slices: bitwise

    exiting when every batch slice is certified or the budget runs out.

    Divergence containment (DESIGN.md §15): the same free certificate
    doubles as a divergence detector.  Each slice tracks its best
    (smallest) est_r so far together with a snapshot of the iterates
    that achieved it; the step est_r goes non-finite or exceeds
    ``divergence_factor ×`` that best, the slice is QUARANTINED —
    rolled back to the best-so-far snapshot (a ``jnp.where`` select,
    bitwise like the freeze masks, zero extra launches) and withdrawn
    from further updates.  Certification wins ties: a slice whose est_r
    clears tol freezes as OK even if the detector would also fire.

    Args:
      iterates: dict of same-batch [..., n, n] iterate arrays (e.g.
        {"X": X} or the coupled {"X": X, "Y": Y} / {"X": X, "M": M}).
      fit: (iterates, k) -> (aux, alpha, est_r); ``aux`` is whatever
        ``step`` needs (typically the residual R), est_r fp32 of shape
        ``batch``.
      step: (iterates, aux, alpha) -> dict of updated iterates.
      tol, k0, budget: certificate threshold and the static run bounds.
      batch: the shared leading batch shape of every iterate.
      divergence_factor: the §15 detector threshold (> 1), see
        ``PrismConfig.divergence_factor``.

    Returns (iterates, used, status): the frozen/final iterates, the
    int32 per-slice count of updates actually applied, and the int8
    per-slice STATUS_OK / STATUS_MAXITER / STATUS_QUARANTINED code.
    """
    names = tuple(iterates)

    def cond(c):
        return (c["k"] < k0 + budget) & ~jnp.all(c["done"])

    def body(c):
        cur = {n: c[n] for n in names}
        aux, a, est = fit(cur, c["k"])
        certified = est <= tol                 # NaN est never certifies
        diverged = ~jnp.isfinite(est) | (est > divergence_factor * c["best"])
        quarantine = diverged & ~certified & ~c["done"]
        done = c["done"] | certified | quarantine
        active = ~done
        improved = est < c["best"]             # finite: NaN compares False
        keep = (improved & active)[..., None, None]
        new = step(cur, aux, a)
        mask = active[..., None, None]
        qmask = quarantine[..., None, None]
        out = dict(c, k=c["k"] + 1, done=done,
                   used=c["used"] + active.astype(jnp.int32),
                   best=jnp.where(improved & active, est, c["best"]),
                   status=jnp.where(quarantine, STATUS_QUARANTINED,
                                    c["status"]))
        for n in names:
            # snapshot BEFORE rollback: the iterate the best est_r was
            # measured on is the pre-step `cur`, not `new`
            snap = jnp.where(keep, cur[n], c["snap." + n])
            out["snap." + n] = snap
            out[n] = jnp.where(qmask, snap, jnp.where(mask, new[n], c[n]))
        return out

    carry = dict(iterates, k=jnp.asarray(k0, jnp.int32),
                 done=jnp.zeros(batch, bool),
                 used=jnp.zeros(batch, jnp.int32),
                 best=jnp.full(batch, jnp.inf, jnp.float32),
                 status=jnp.zeros(batch, jnp.int8))
    for n in names:
        carry["snap." + n] = iterates[n]
    out = jax.lax.while_loop(cond, body, carry)
    status = jnp.where(out["done"], out["status"],
                       jnp.asarray(STATUS_MAXITER))
    return {n: out[n] for n in names}, out["used"], status


def resolve_alpha(
    k: int,
    R: jax.Array,
    apoly: poly.AlphaPoly,
    cfg: PrismConfig,
    key: Optional[jax.Array],
    n_real: Optional[jax.Array] = None,
) -> jax.Array:
    """alpha_k per the config: warm iterations pin alpha = u (paper Sec. C),
    later iterations fit via the sketched objective.

    ``k`` is a STATIC Python int (the Table-1 iterations unroll), so warm
    iterations compile to a constant — no sketch, no fit, zero overhead.
    The Newton-Schulz family (polar / sqrtm / signm) routes through here;
    chebyshev and inverse_newton carry their own bounds and no warm
    schedule, so they call fit_alpha directly.
    """
    lo, hi = cfg.bounds
    if k < cfg.warm_alpha_iters:
        return jnp.full(R.shape[:-2], hi, dtype=jnp.float32)
    if key is not None:
        key = alpha_schedule_key(key, k)
    return fit_alpha(R, apoly, lo, hi, key=key, sketch_dim=cfg.sketch_dim,
                     n_real=n_real)
