"""Newton-Schulz iterations (classical + PRISM-accelerated).

Covers the paper's Table-1 rows:
  * matrix sign                 X_{k+1} = X_k g_d(R_k; a),  R_k = I - X_k^2
  * square / inverse sqrt       coupled (X, Y), R_k = I - X_k Y_k   (Thm 3)
  * polar factor U V^T          R_k = I - X_k^T X_k                 (Thm 4)

for d=1 (3rd order) and d=2 (5th order).  ``alpha`` per iteration is either
the classical Taylor coefficient, a fixed warm value u (paper Sec. C), or
the PRISM sketched fit (core/prism.py).

All entry points broadcast over leading batch dims (stacked layer params)
and are jit/vmap/grad-safe; iteration counts are static Python ints so warm
iterations compile to zero fitting overhead.

Phase structure (DESIGN.md §10): every chain is an explicit sequence of
WARM phases — maximal runs of iterations whose alpha is a static Python
float (the PRISM warm-start value u, or the classical Taylor coefficient,
which makes a whole classical chain one phase) — and FIT iterations,
whose alpha is the sketched argmin and therefore data-dependent.  Every
product runs through kernels/ops.py, which picks the backend from the
platform and the tier from the shape: when the per-slice working set
fits the VMEM budget (``ops.fused_fits``), a warm phase runs as ONE
multi-iteration Pallas launch with X ping-ponging in VMEM, and a fit
iteration as TWO launches: fused residual+sketch-chain, then the fused
d-GEMM Horner application — the closed-form alpha minimization runs
between them in XLA, which is exactly why the fit phase cannot fuse
across iterations (alpha_{k+1} needs the traces of R_{k+1}).

Adaptive early stopping (DESIGN.md §11): with ``cfg.tol`` set, each
maximal run of fitted iterations becomes one ``lax.while_loop`` whose
body is a single fitted iteration plus a per-matrix convergence mask —
the certificate est_r ~ ||R_k||_F is read off the trace chain the fit
already computes (prism.fit_alpha_from_traces), converged [B, n, n]
slices freeze bit-stably, and the loop exits when the slowest slice
certifies.  ``iterations`` is then a budget; the realized per-matrix
counts surface through ``return_iters``.  tol=None (default) keeps the
fully-unrolled static chains (and is required for reverse-mode autodiff
through the iteration, which lax.while_loop does not support).
"""
from __future__ import annotations

import collections
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import PrismConfig
from repro.core import polynomials as poly
from repro.core import prism
from repro.core import sketch as sk
from repro.kernels import ops as kops


# trace-time count of ``apply_g`` calls by evaluation order: "gram_side"
# or "horner" (``Trainer.counters["polar_apply"]`` copies it)
path_counts: collections.Counter = collections.Counter()


class IterInfo(NamedTuple):
    alphas: jax.Array          # [iters, ...]
    residual_fro: jax.Array    # [iters, ...] ||R_k||_F before each update


def _eye_like(M: jax.Array) -> jax.Array:
    n = M.shape[-1]
    return jnp.eye(n, dtype=M.dtype)


def _fro(M: jax.Array) -> jax.Array:
    return jnp.sqrt(jnp.sum(jnp.square(M.astype(jnp.float32)),
                            axis=(-2, -1), keepdims=True))


def _safe_fro(M: jax.Array) -> jax.Array:
    """||M||_F clamped away from zero for the entry-point normalizations.

    A zero slice (rank-collapsed momentum, freshly-padded bucket slot)
    would otherwise normalize as 0/0 = NaN before the first iteration
    ever runs — the one poisoning the §15 guardian cannot contain,
    because it happens upstream of the certificate.  Clamping to the
    smallest normal fp32 leaves every slice with ||M||_F >= tiny
    bit-identical and turns zero slices into exact zero pass-throughs
    (0 / tiny = 0), which the chains then fix at X = 0.
    """
    return jnp.maximum(_fro(M), jnp.float32(np.finfo(np.float32).tiny))


def apply_g(X: jax.Array, R: jax.Array, alpha, d: int,
            side: str = "right", family: str = "polar") -> jax.Array:
    """X @ g_d(R; alpha)  (side='right')  or  g_d(R; alpha) @ X  (side='left').

    g_d(x; a) = f_{d-1}(x) + a x^d with f the Taylor series of (1-x)^{-1/2}.

    The polar family at d >= 2 evaluates it on the Gram side (DESIGN.md
    §7): Q = a R^d + f_{d-1} R^{d-1} + ... + f_1 R is formed as an
    [n, n] matrix by d - 1 power products (Horner on R: Q = a R R +
    f_{d-1} R, then Q <- R Q + f_j R), each on the upper block triangle
    (``ops.sym_matmul_add``), since R = I - X^T X and every polynomial
    in it are symmetric; Q then reaches the tall iterate in ONE product
    X Q + f_0 X.  Every other case (d = 1, which has no power product;
    the sign family, whose R is not symmetric; the coupled sqrt family)
    runs Horner on the iterate, a chain of d GEMMs that never forms
    g(R).  ``path_counts`` counts the calls each order took at trace
    time.

    alpha is applied IN fp32 (DESIGN.md §9): the PRISM fit is pinned
    fp32, so under a bf16 compute policy the fitted alpha multiplies an
    fp32 accumulator (the Gram side's power product, or Horner's
    fp32-upcast X) and the product rounds ONCE to the compute dtype —
    never pre-rounding alpha itself to bf16 (which would throw away the
    fit's precision before it reaches the update).  The fused kernel
    tier (kernels/fused_iter.apply_g) and ref.apply_g keep the same
    contract inside the fp32 Horner accumulator.
    """
    f = poly.taylor_inv_sqrt(d - 1)  # ascending, length d
    alpha = jnp.asarray(alpha, jnp.float32)
    if family == "polar" and d >= 2:
        path_counts["gram_side"] += 1
        Q = kops.sym_matmul_add(R, R, R, alpha=alpha, beta=float(f[d - 1]))
        for j in range(d - 2, 0, -1):
            Q = kops.sym_matmul_add(R, Q, R, alpha=1.0, beta=float(f[j]))
        if side == "right":
            return kops.matmul_add(X, Q, C=X, beta=float(f[0]))
        return kops.matmul_add(Q, X, C=X, beta=float(f[0]))
    path_counts["horner"] += 1
    if alpha.ndim:
        alpha = alpha[..., None, None]
    acc = (alpha * X.astype(jnp.float32)).astype(X.dtype)
    if side == "right":
        # X (f0 I + f1 R + ... + a R^d) = f0 X + (f1 X + (... + a X R) R) R
        for j in range(d - 1, 0, -1):
            acc = kops.matmul_add(acc, R, C=X, beta=float(f[j]))
        return kops.matmul_add(acc, R, C=X, beta=float(f[0]))
    else:
        for j in range(d - 1, 0, -1):
            acc = kops.matmul_add(R, acc, C=X, beta=float(f[j]))
        return kops.matmul_add(R, acc, C=X, beta=float(f[0]))


def _classical_alpha(d: int) -> float:
    return float(poly.taylor_inv_sqrt(d)[d])


def _resolve_alpha(k: int, R: jax.Array, cfg: PrismConfig, method: str,
                   key: Optional[jax.Array],
                   n_real: Optional[jax.Array] = None):
    """Static-k alpha resolution: classical coefficient or the shared
    warm/PRISM-fit implementation in prism.resolve_alpha."""
    if method == "newton_schulz":
        return jnp.full(R.shape[:-2], _classical_alpha(cfg.degree),
                        dtype=jnp.float32)
    assert method == "prism"
    return prism.resolve_alpha(k, R, poly.newton_schulz_residual(cfg.degree),
                               cfg, key, n_real=n_real)


# ---------------------------------------------------------------------------
# Phase plan + fused-tier routing (DESIGN.md §10)
# ---------------------------------------------------------------------------


def _static_alpha(k: int, cfg: PrismConfig, method: str) -> Optional[float]:
    """alpha_k when it is a compile-time constant, else None (fit)."""
    if method == "newton_schulz":
        return _classical_alpha(cfg.degree)
    # fail fast on unknown methods on BOTH tiers (the unfused path's
    # _resolve_alpha asserts the same; the fused fit path skips it)
    assert method == "prism", method
    if k < cfg.warm_alpha_iters:
        return float(cfg.bounds[1])
    return None


def _phase_plan(iters: int, cfg: PrismConfig,
                method: str) -> List[Tuple[str, object]]:
    """[("warm", (a_0, ..)), ("fit", (k0, count)), ...] — maximal runs
    of static-alpha iterations become single warm phases (one fused
    launch, §10) and maximal runs of consecutive FITTED iterations
    become single fit phases.  A fit phase unrolls statically
    (``cfg.tol is None``: count data-independent iterations, the
    pre-§11 behavior) or runs as ONE ``lax.while_loop`` with per-matrix
    convergence masks (``cfg.tol`` set): ``count`` is then the budget,
    not the cost."""
    phases: List[Tuple[str, object]] = []
    for k in range(iters):
        a = _static_alpha(k, cfg, method)
        if a is None:
            if phases and phases[-1][0] == "fit":
                k0, count = phases[-1][1]
                phases[-1] = ("fit", (k0, count + 1))
            else:
                phases.append(("fit", (k, 1)))
        else:
            if phases and phases[-1][0] == "warm":
                phases[-1] = ("warm", phases[-1][1] + (a,))
            else:
                phases.append(("warm", (a,)))
    return phases


def _fused_fit_step(X, cfg: PrismConfig, k: int, key, n_real,
                    family: str, Y=None):
    """One fitted iteration in TWO launches: fused residual+sketch-chain,
    the XLA closed-form alpha fit, then the fused Horner application."""
    apoly = poly.newton_schulz_residual(cfg.degree)
    lo, hi = cfg.bounds
    n = X.shape[-1]
    S = sk.gaussian_sketch(prism.alpha_schedule_key(key, k), cfg.sketch_dim,
                           n, dtype=X.dtype)
    R, t = kops.residual_chain(X, S, poly.max_trace_power(apoly),
                               family=family, Y=Y)
    a = prism.fit_alpha_from_traces(t, apoly, lo, hi, S=S, n_real=n_real)
    return kops.apply_g(X, R, a, degree=cfg.degree, Y=Y)


def _adaptive_fit_run(X, Y, cfg: PrismConfig, k0: int, count: int, key,
                      n_real, family: str, residual_fn, fused: bool):
    """A maximal run of fitted iterations as ONE ``lax.while_loop`` with
    per-matrix convergence masks (DESIGN.md §11).

    Every loop step reads the certificate est_r ~ ||R_k||_F off the same
    sketched trace chain the alpha fit consumes (zero extra launches) and
    freezes any batch slice with est_r <= cfg.tol: frozen slices pass
    through a masked identity update (``jnp.where`` on the untouched
    iterate — bitwise-stable) while stragglers keep iterating.  The loop
    exits when the SLOWEST slice certifies or the ``count`` budget runs
    out.  The same certificate drives the §15 divergence detector
    (``cfg.divergence_factor``): a slice whose est_r goes non-finite or
    blows past its best-so-far is QUARANTINED — rolled back to the
    best-certified iterate and withdrawn.  Returns (X, Y, used, status)
    with ``used`` the per-slice number of updates actually applied
    (shape ``X.shape[:-2]``, int32) and ``status`` the per-slice int8
    guardian code (prism.STATUS_*).

    The §10 launch contracts survive unchanged: the loop body is the
    body of one fitted iteration — 2 launches on the fused tier, 2+d on
    the §7 tier — issued per RUNTIME iteration, while a single trace of
    the while_loop (what ``ops.count_launches`` counts) contains the
    body once, independent of the budget and of the data.
    """
    coupled = Y is not None
    apoly = poly.newton_schulz_residual(cfg.degree)
    lo, hi = cfg.bounds
    n = X.shape[-1]
    use_fused_fit = fused and key is not None and cfg.sketch_dim > 0

    def fit(it, k):
        """(R, alpha, est_r) for iteration k (k is traced)."""
        X_, Y_ = it["X"], it.get("Y")
        if use_fused_fit:
            S = sk.gaussian_sketch(prism.alpha_schedule_key(key, k),
                                   cfg.sketch_dim, n, dtype=X_.dtype)
            R, t = kops.residual_chain(X_, S, poly.max_trace_power(apoly),
                                       family=family, Y=Y_)
            a, est = prism.fit_alpha_from_traces(t, apoly, lo, hi, S=S,
                                                 n_real=n_real,
                                                 return_est_r=True)
            return R, a, est
        R = residual_fn(X_, Y_)
        kk = prism.alpha_schedule_key(key, k) if key is not None else None
        a, est = prism.fit_alpha(R, apoly, lo, hi, key=kk,
                                 sketch_dim=cfg.sketch_dim,
                                 n_real=n_real, return_est_r=True)
        return R, a, est

    def step(it, R, a):
        X_, Y_ = it["X"], it.get("Y")
        if fused:
            out = kops.apply_g(X_, R, a, degree=cfg.degree, Y=Y_)
            Xn, Yn = out if coupled else (out, None)
        else:
            Xn = apply_g(X_, R, a, cfg.degree, "right", family)
            Yn = apply_g(Y_, R, a, cfg.degree, "left",
                         family) if coupled else None
        return {"X": Xn, "Y": Yn} if coupled else {"X": Xn}

    iterates = {"X": X, "Y": Y} if coupled else {"X": X}
    out, used, status = prism.adaptive_masked_loop(
        iterates, fit, step, cfg.tol, k0, count, X.shape[:-2],
        divergence_factor=cfg.divergence_factor)
    return out["X"], out.get("Y", Y), used, status


def _run_phases(X, cfg: PrismConfig, method: str, iters: int, key,
                return_info: bool, family: str, residual_fn,
                Y=None, n_real=None):
    """Shared warm/fit phase driver for the three NS families (§10/§11).

    ``residual_fn(X, Y)`` computes the family residual on the unfused
    path; ``Y`` is non-None only for the coupled sqrt family (both
    iterates then update per phase).  Returns (X, Y, alphas, fros,
    iters_used): the info lists are populated only under ``return_info``
    (which disables the fused tier, whose launches never materialize the
    per-iteration residuals, and the adaptive engine, whose
    per-iteration quantities a dynamic loop cannot stack);
    ``iters_used`` is the per-matrix count of applied updates, shape
    ``X.shape[:-2]`` — the static total unless ``cfg.tol`` turns the fit
    phases adaptive (§11).  ``status`` is the per-matrix int8 guardian
    code (prism.STATUS_*), the severity-maximum across all adaptive fit
    runs (all-zeros on static chains, which carry no certificate).
    """
    coupled = Y is not None
    fused = not return_info and kops.fused_fits(
        X.shape[-2:], jnp.dtype(cfg.dtype), coupled=coupled)
    alphas, fros = [], []
    iters_used = jnp.zeros(X.shape[:-2], jnp.int32)
    status = jnp.zeros(X.shape[:-2], jnp.int8)
    adaptive = cfg.tol is not None and not return_info

    def unpack(out):
        return out if coupled else (out, Y)

    for kind, payload in _phase_plan(iters, cfg, method):
        if kind == "warm":
            iters_used = iters_used + len(payload)
            if fused:
                X, Y = unpack(kops.warm_tail(X, payload, degree=cfg.degree,
                                             family=family, Y=Y))
                continue
            for a in payload:
                R = residual_fn(X, Y)
                aa = jnp.full(R.shape[:-2], a, dtype=jnp.float32)
                X = apply_g(X, R, aa, cfg.degree, "right", family)
                if coupled:
                    Y = apply_g(Y, R, aa, cfg.degree, "left", family)
                if return_info:
                    alphas.append(aa)
                    fros.append(_fro(R)[..., 0, 0])
            continue
        k0, count = payload
        if adaptive:
            X, Y, used, st = _adaptive_fit_run(X, Y, cfg, k0, count, key,
                                               n_real, family, residual_fn,
                                               fused)
            iters_used = iters_used + used
            status = jnp.maximum(status, st)
            continue
        for k in range(k0, k0 + count):
            iters_used = iters_used + 1
            if fused and key is not None and cfg.sketch_dim > 0:
                X, Y = unpack(_fused_fit_step(X, cfg, k, key, n_real,
                                              family, Y=Y))
                continue
            R = residual_fn(X, Y)
            a = _resolve_alpha(k, R, cfg, method, key, n_real=n_real)
            if fused:
                X, Y = unpack(kops.apply_g(X, R, a, degree=cfg.degree,
                                           Y=Y))
            else:
                X = apply_g(X, R, a, cfg.degree, "right", family)
                if coupled:
                    Y = apply_g(Y, R, a, cfg.degree, "left", family)
            if return_info:
                alphas.append(a)
                fros.append(_fro(R)[..., 0, 0])
    return X, Y, alphas, fros, iters_used, status


# ---------------------------------------------------------------------------
# Polar factor (orthogonalization) — the Muon primitive
# ---------------------------------------------------------------------------


def _with_telemetry(out, info, iters_used, return_info, return_iters,
                    status=None, return_status=False):
    """(out[, IterInfo][, iters_used][, status]) per the telemetry
    flags — ``status`` is the per-matrix int8 guardian code
    (prism.STATUS_*), appended last so existing unpackers are
    untouched."""
    res = (out,)
    if return_info:
        alphas, fros = info
        res = res + (IterInfo(jnp.stack(alphas), jnp.stack(fros)),)
    if return_iters:
        res = res + (iters_used,)
    if return_status:
        res = res + (status,)
    return res if len(res) > 1 else res[0]


def polar(A: jax.Array, cfg: Optional[PrismConfig] = None,
          method: str = "prism", iters: Optional[int] = None,
          key: Optional[jax.Array] = None, return_info: bool = False,
          n_real: Optional[jax.Array] = None, return_iters: bool = False,
          return_status: bool = False):
    """Polar factor U V^T of A [..., m, n] via (PRISM-)Newton-Schulz.

    method: "prism" | "newton_schulz" (classical Taylor alpha).
    n_real: per-matrix real extent of the Gram dimension (= min(m, n) side)
      when A is a zero-padded pad-to-bucket stack; makes the sketched alpha
      fit exactly ignore the padding (see prism.fit_alpha).  Zero-padding
      itself is exact for the iterations: pad rows/cols of X stay zero and
      the real block evolves as if unpadded.
    return_iters: also return ``iters_used`` — the per-matrix number of
      iterations actually applied, shape ``A.shape[:-2]`` (int32).  Equals
      ``iters`` unless ``cfg.tol`` enables adaptive early stopping
      (DESIGN.md §11), where converged slices freeze early.
    return_status: also return the per-matrix int8 guardian status
      (prism.STATUS_*, DESIGN.md §15) — appended after ``iters_used``.
      All-zeros unless ``cfg.tol`` runs the adaptive certificate.
    """
    cfg = PrismConfig() if cfg is None else cfg
    iters = cfg.iterations if iters is None else iters
    transpose = A.shape[-2] < A.shape[-1]
    X = jnp.swapaxes(A, -1, -2) if transpose else A
    in_dtype = X.dtype
    X = X.astype(cfg.dtype) / _safe_fro(X).astype(cfg.dtype)
    X, _, alphas, fros, used, status = _run_phases(
        X, cfg, method, iters, key, return_info, "polar",
        lambda x, y: kops.gram(x), n_real=n_real)
    X = jnp.swapaxes(X, -1, -2) if transpose else X
    X = X.astype(in_dtype)
    return _with_telemetry(X, (alphas, fros), used, return_info,
                           return_iters, status, return_status)


# ---------------------------------------------------------------------------
# Coupled square root / inverse square root (Higham Thm 3)
# ---------------------------------------------------------------------------


def _coupled_residual(X, Y):
    # R = I - Y X (Thm 3 coupling: X <- X h(YX), Y <- h(YX) Y).  This is
    # Higham's numerically *stable* coupled form; the R = I - X Y variant
    # written in the paper's Table-1 "Residual" column is the classically
    # unstable coupling and diverges right after convergence (verified
    # empirically in fp64 — see tests/test_matfn.py::test_sqrt_stability).
    R = _eye_like(X) - kops.matmul_add(Y, X)
    return 0.5 * (R + jnp.swapaxes(R, -1, -2))  # stability: re-symmetrize


def sqrtm(A: jax.Array, cfg: Optional[PrismConfig] = None,
          method: str = "prism", iters: Optional[int] = None,
          key: Optional[jax.Array] = None, return_info: bool = False,
          return_iters: bool = False, return_status: bool = False):
    """(A^{1/2}, A^{-1/2}) for symmetric PSD A via coupled (PRISM-)NS.

    Normalizes by ||A||_F (so spectrum in (0, 1]) and rescales the outputs.
    ``return_iters`` appends the per-matrix ``iters_used`` telemetry (see
    ``polar``); with ``cfg.tol`` set, BOTH coupled iterates freeze
    together once the slice's certificate est_r ~ ||I - Y X||_F clears
    tol (DESIGN.md §11).  ``return_status`` appends the per-matrix int8
    guardian status (prism.STATUS_*, DESIGN.md §15).
    """
    cfg = PrismConfig() if cfg is None else cfg
    iters = cfg.iterations if iters is None else iters
    in_dtype = A.dtype
    c = _safe_fro(A).astype(cfg.dtype)
    X = A.astype(cfg.dtype) / c
    Y = jnp.broadcast_to(_eye_like(X), X.shape)
    X, Y, alphas, fros, used, status = _run_phases(
        X, cfg, method, iters, key, return_info, "sqrt",
        _coupled_residual, Y=Y)
    sqrt_c = jnp.sqrt(c)
    out = (X * sqrt_c).astype(in_dtype), (Y / sqrt_c).astype(in_dtype)
    return _with_telemetry(out, (alphas, fros), used, return_info,
                           return_iters, status, return_status)


# ---------------------------------------------------------------------------
# Matrix sign
# ---------------------------------------------------------------------------


def signm(A: jax.Array, cfg: Optional[PrismConfig] = None,
          method: str = "prism", iters: Optional[int] = None,
          key: Optional[jax.Array] = None, return_info: bool = False,
          return_iters: bool = False, return_status: bool = False):
    """sign(A) for A with A^2 symmetric and ||A||_2 <= 1 after ||.||_F
    scaling.  ``return_iters`` appends per-matrix ``iters_used`` (see
    ``polar``); ``return_status`` the int8 guardian status (§15)."""
    cfg = PrismConfig() if cfg is None else cfg
    iters = cfg.iterations if iters is None else iters
    in_dtype = A.dtype
    X = A.astype(cfg.dtype) / _safe_fro(A).astype(cfg.dtype)
    X, _, alphas, fros, used, status = _run_phases(
        X, cfg, method, iters, key, return_info, "sign",
        lambda x, y: _eye_like(x) - kops.matmul_add(x, x))
    X = X.astype(in_dtype)
    return _with_telemetry(X, (alphas, fros), used, return_info,
                           return_iters, status, return_status)
