"""PolarExpress baseline (Amsel et al. 2025, Algorithm 1).

Minimax-optimal composed degree-5 polynomials for the polar factor,
pre-optimized for singular values in [1e-3, 1] — the exact variant the
PRISM paper compares against (and the one that degrades when the true
sigma_min deviates from 1e-3; reproduced in benchmarks/fig1_sigma_sweep.py).

Coefficients are the published Algorithm-1 schedule; after the listed
iterations the (numerically safe) asymptotic tuple (1.875, -1.25, 0.375)
repeats.  Each update is X <- a X + b X (X^T X) + c X (X^T X)^2.

Via Higham's Theorem 3 the same h(M) = aI + bM + cM^2 schedule runs in
coupled form for the (inverse) square root.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops  # fp32-accumulate (DESIGN.md §9)

# Algorithm 1 of Amsel et al. (2025), sigma_min = 1e-3 variant.
POLAR_EXPRESS_COEFFS: Tuple[Tuple[float, float, float], ...] = (
    (8.28721201814563, -23.595886519098837, 17.300387312530933),
    (4.107059111542203, -2.9478499167379106, 0.5448431082926601),
    (3.9486908534822946, -2.908902115962949, 0.5518191394370137),
    (3.3184196573706015, -2.488488024314874, 0.51004894012372),
    (2.300652019954817, -1.6689039845747493, 0.4188073119525673),
    (1.891301407787398, -1.2679958271945868, 0.37680408948524835),
    (1.8750014808534479, -1.2500016453999487, 0.3750001645474248),
    (1.875, -1.25, 0.375),
)
_SAFETY = 1.01  # Amsel et al. divide by 1.01 * ||A||_F for bf16 safety


def _fro(M):
    return jnp.sqrt(jnp.sum(jnp.square(M.astype(jnp.float32)),
                            axis=(-2, -1), keepdims=True))


def _coeff(k: int) -> Tuple[float, float, float]:
    return POLAR_EXPRESS_COEFFS[min(k, len(POLAR_EXPRESS_COEFFS) - 1)]


def polar(A: jax.Array, iters: int = 8, dtype=jnp.float32,
          return_info: bool = False):
    """Polar factor of A [..., m, n] via PolarExpress."""
    transpose = A.shape[-2] < A.shape[-1]
    X = jnp.swapaxes(A, -1, -2) if transpose else A
    in_dtype = X.dtype
    X = X.astype(dtype) / (_SAFETY * _fro(X).astype(dtype))
    fros = []
    for k in range(iters):
        a, b, c = _coeff(k)
        M = kops.matmul_add(jnp.swapaxes(X, -1, -2), X)
        if return_info:
            eye = jnp.eye(M.shape[-1], dtype=M.dtype)
            fros.append(_fro(eye - M)[..., 0, 0])
        M2 = kops.matmul_add(M, M)
        X = a * X + b * kops.matmul_add(X, M) + c * kops.matmul_add(X, M2)
    X = jnp.swapaxes(X, -1, -2) if transpose else X
    X = X.astype(in_dtype)
    if return_info:
        return X, jnp.stack(fros)
    return X


def sqrtm(A: jax.Array, iters: int = 8, dtype=jnp.float32,
          return_info: bool = False):
    """(A^{1/2}, A^{-1/2}) via PolarExpress in coupled form (Thm 3).

    Any sign iteration X <- X h(X^2) couples as X <- X h(YX), Y <- h(YX) Y.
    For PolarExpress on the square root, the optimized interval [1e-3, 1]
    on singular values becomes [1e-6, 1] on eigenvalues of YX (the paper's
    Fig. 1 note).
    """
    in_dtype = A.dtype
    c0 = _SAFETY * _fro(A).astype(dtype)
    X = A.astype(dtype) / c0
    Y = jnp.broadcast_to(jnp.eye(X.shape[-1], dtype=dtype), X.shape)
    fros = []
    for k in range(iters):
        a, b, c = _coeff(k)
        M = kops.matmul_add(Y, X)
        if return_info:
            eye = jnp.eye(M.shape[-1], dtype=M.dtype)
            fros.append(_fro(eye - M)[..., 0, 0])
        M2 = kops.matmul_add(M, M)
        H = a * jnp.broadcast_to(jnp.eye(M.shape[-1], dtype=M.dtype), M.shape) \
            + b * M + c * M2
        X = kops.matmul_add(X, H)
        Y = kops.matmul_add(H, Y)
    sc = jnp.sqrt(c0)
    out = (X * sc).astype(in_dtype), (Y / sc).astype(in_dtype)
    if return_info:
        return out, jnp.stack(fros)
    return out
