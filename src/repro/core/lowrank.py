"""Low-rank sketched orthogonalization (DESIGN.md §14).

The cubic polar path costs O(m n^2) per Newton-Schulz iteration, so the
views that dominate a foundation-scale model — embedding / LM-head /
MoE-expert tables, m >> n or m ~ 10^5 — historically bypassed the PRISM
engine.  Per He et al. (arXiv 2509.11983), Muon's convergence survives
orthogonalizing only the dominant rank-k subspace of the momentum; this
module computes that at O(mnl) with l = k + oversample << min(m, n):

  1. rangefinder: Y = M @ Omega with a Gaussian test matrix Omega in
     R^{n x l} (core/sketch.py Gaussians, shared per bucket through the
     PRNG key), optionally refined by power iterations Y <- M (M^T Y),
     and orthonormalized into Q in R^{m x l} by the SAME fitted PRISM-NS
     polar the engine already runs — Gram side l, so O(m l^2) per
     iteration and LAPACK-free (batched, kernel-tiered, bf16-capable);
  2. subspace fit: B = Q^T M in R^{l x n} runs the existing fitted polar
     — alpha fit, §11 adaptive early stopping and the §9 precision
     policy apply unchanged at l << m;
  3. lift: O = Q @ polar(B), one [m, l] x [l, n] GEMM.

Exactness: when M carries a genuine l-dimensional spectrum (rank ~= l,
no crossing of the fp32 rounding floor) every sketched direction is
real and the composition matches the top-l SVD orthogonalization
U_l V_l^T to NS-convergence precision; for general M it approximates
the top-l truncated polar with the classical rangefinder error, shrunk
by power iterations.  Caveat shared with every msign-style scheme: the
NS chain amplifies rounding-level singular values toward 1, so if
rank(M) < l the (l - rank) surplus sketch directions contribute unit
noise — pick l at or below the expected momentum rank, never far above.

Pad-exactness (§7 composition): zero pad rows/cols of M keep Y's pad
rows, Q's pad rows and B's pad cols identically zero through every
right-multiplied NS chain, and the Gram residuals live on the l side —
which is never padded — so the alpha fits need NO n_real correction.

Everything broadcasts over leading batch dims (M: [..., m, n]) so the
§7 bucketed engine and the §8 batch-dim shard_map dispatch it unchanged.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import PrismConfig
from repro.core import sketch as sk
from repro.core.newton_schulz import _fro
from repro.kernels import ops as kops


def _gaussian_test_matrix(key: jax.Array, n: int, l: int,
                          dtype) -> jax.Array:
    """Omega in R^{n x l}: the core/sketch.py Gaussian, transposed.  The
    1/sqrt(l) OSE scaling is irrelevant here (Q re-orthonormalizes) but
    keeps Y's magnitude tame for the fro-normalized polar."""
    return sk.gaussian_sketch(key, l, n, dtype=dtype).T


def rangefinder(M: jax.Array, l: int, key: jax.Array,
                cfg: Optional[PrismConfig] = None, method: str = "prism",
                power_iters: int = 1) -> jax.Array:
    """Sketched orthonormal range basis Q in R^{..., m, l} of M [..., m, n].

    Randomized rangefinder (Halko/Martinsson/Tropp): Y = M Omega captures
    the dominant column space; ``power_iters`` rounds of Y <- M (M^T Y)
    sharpen the capture to the (2q+1)-th power of the spectrum.  The
    orthonormalization is the engine's own NS polar (fitted when
    method="prism") instead of a LAPACK QR: batched, kernel-tiered, and
    exact on rank-deficient Y (zero singular values stay zero, yielding
    a partial isometry spanning range(M)).
    """
    cfg = PrismConfig() if cfg is None else cfg
    from repro.core import matfn

    n = M.shape[-1]
    Om = _gaussian_test_matrix(jax.random.fold_in(key, 0), n, l, M.dtype)
    Y = kops.matmul_add(M, Om)
    Mt = jnp.swapaxes(M, -1, -2)
    for _ in range(power_iters):
        # per-slice fro rescale between products: keeps the power
        # iterates away from bf16 overflow without touching directions
        Y = Y / jnp.maximum(_fro(Y).astype(Y.dtype), 1e-30)
        Y = kops.matmul_add(M, kops.matmul_add(Mt, Y))
    return matfn.polar(Y, method=method, cfg=cfg,
                       key=jax.random.fold_in(key, 1))


def polar_lowrank(M: jax.Array, rank: int, oversample: int,
                  cfg: Optional[PrismConfig] = None,
                  key: Optional[jax.Array] = None, method: str = "prism",
                  power_iters: int = 1, return_iters: bool = False,
                  return_status: bool = False):
    """Rank-l orthogonalization O ~ U_l V_l^T of M [..., m, n] (§14).

    l = min(rank + oversample, min(m, n)).  Orientation-equivariant: a
    wide M is processed through its transpose (polar(M^T) = polar(M)^T),
    so the rangefinder always sketches the long side.  ``return_iters``
    surfaces the realized per-slice iteration count of the SUBSPACE
    fitted chain (the §11 telemetry consumers' contract — the
    rangefinder's auxiliary polar is not the certified product).
    """
    cfg = PrismConfig() if cfg is None else cfg
    from repro.core import matfn

    if key is None:
        key = jax.random.PRNGKey(0)
    transpose = M.shape[-2] < M.shape[-1]
    X = jnp.swapaxes(M, -1, -2) if transpose else M
    m, n = X.shape[-2], X.shape[-1]
    l = min(rank + oversample, n)
    Q = rangefinder(X, l, jax.random.fold_in(key, 0), cfg=cfg,
                    method=method, power_iters=power_iters)
    B = kops.matmul_add(jnp.swapaxes(Q, -1, -2), X)  # [..., l, n]
    P = matfn.polar(B, method=method, cfg=cfg,
                    key=jax.random.fold_in(key, 1),
                    return_iters=return_iters,
                    return_status=return_status)
    if return_iters or return_status:
        P, *aux = P
    O = kops.matmul_add(Q, P)
    O = jnp.swapaxes(O, -1, -2) if transpose else O
    if return_iters or return_status:
        return (O, *aux)
    return O


def svd_topk(M: jax.Array, k: int) -> jax.Array:
    """Oracle: exact top-k truncated orthogonalization U_k V_k^T (the
    target ``polar_lowrank`` approximates; tests/benchmarks only)."""
    U, _, Vt = jnp.linalg.svd(M.astype(jnp.float32), full_matrices=False)
    return (U[..., :, :k] @ Vt[..., :k, :]).astype(M.dtype)
