"""Symmetric upper-block-triangle Pallas TPU kernels: the Gram residual
R = alpha * I + beta * X^T X, and the power product alpha_b * A @ B +
beta * C whose result is symmetric.

Newton-Schulz for the polar factor forms R_k = I - X_k^T X_k every
iteration, and evaluates its polynomial on the Gram side (DESIGN.md §7):
Q = alpha R^d + ... + f1 R as an [n, n] matrix by d - 1 power products
R @ Q' + f_j R, then X' = X Q + f0 X in one ``matmul_add``.  R and every
polynomial in R are symmetric, but a generic GEMM computes all n^2
output tiles.  Both kernels enumerate ONLY the upper-triangular block
grid (T = nb (nb+1) / 2 tiles instead of nb^2) — the linear tile index t
is unranked to (block-row i, block-col j) in closed form inside the
BlockSpec index maps — cutting MXU work and HBM write traffic nearly in
half.  This is a TPU-native beyond-paper optimization (DESIGN.md §3).

The kernels emit the upper-block-triangle U (lower blocks never
written); ``mirror_upper`` completes it to an exactly symmetric matrix
with one elementwise pass: the upper triangle of U, element by element,
and its transpose below the diagonal.

Precision (DESIGN.md §9): bf16 operands accumulate in fp32 on the VMEM
scratch; the epilogues (alpha*I; the per-slice fp32 alpha and the
beta * C add) run on the fp32 accumulator and the tile rounds once to
the operand dtype — the residual a bf16 Newton-Schulz iteration consumes
is the correctly-rounded fp32 Gram, and the fitted fp32 alpha reaches
the polynomial unrounded (it is read from SMEM, one scalar per slice).

Batching: the grid is (B, T, K/bk) so a whole [B, ., n] parameter bucket
runs in ONE launch (DESIGN.md §7); 2-D inputs run as B = 1.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu


def _unrank_upper(t, nb: int):
    """Row-major upper-triangle unranking: t -> (i, j), i <= j < nb.

    f(i) = i*nb - i(i-1)/2 elements precede block-row i; invert via a
    float sqrt estimate + integer correction (robust to rounding).
    """
    tf = t.astype(jnp.float32)
    b = 2 * nb + 1
    i_est = jnp.floor((b - jnp.sqrt(b * b - 8.0 * tf)) / 2).astype(jnp.int32)

    def f(i):
        return i * nb - (i * (i - 1)) // 2

    i = i_est
    i = jnp.where(f(i + 1) <= t, i + 1, i)
    i = jnp.where(f(i) > t, i - 1, i)
    i = jnp.clip(i, 0, nb - 1)
    j = t - f(i) + i
    return i, jnp.clip(j, 0, nb - 1)


def _upper_map(nb: int, index):
    """BlockSpec index map on the (B, T, K) upper-block grid:
    ``index(b, i, j, kk)`` for tile t = (block-row i, block-col j)."""

    def index_map(b, t, kk):
        i, j = _unrank_upper(t, nb)
        return index(b, i, j, kk)

    return index_map


def _kernel(x1_ref, x2_ref, out_ref, acc_ref, *, alpha, beta, n_k, bn, nb):
    k = pl.program_id(2)
    t = pl.program_id(1)  # hoisted: program_id inside pl.when bodies does
    # not interpret on CPU (substitution happens at kernel top level only)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x1, x2 = x1_ref[0], x2_ref[0]
    acc_ref[...] += jnp.dot(x1.T, x2, precision=mxu.precision(x1, x2),
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        i, j = _unrank_upper(t, nb)
        out = beta * acc_ref[...]
        if alpha != 0.0:
            # add alpha * I only on diagonal blocks
            row = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
            eye = jnp.where((row == col) & (i == j), alpha, 0.0)
            out = out + eye
        out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "bn", "bk",
                                             "interpret"))
def gram_upper(X: jax.Array, *, alpha: float = 1.0, beta: float = -1.0,
               bn: int = 256, bk: int = 256,
               interpret: bool = False) -> jax.Array:
    """Upper-block-triangle of alpha * I + beta * X^T X for X [m, n] or
    [B, m, n].

    Only tiles (i, j) with i <= j are computed; strictly-lower blocks of
    the result are undefined.  Use ``ops.gram`` for the full symmetric
    matrix.
    """
    squeeze = X.ndim == 2
    if squeeze:
        X = X[None]
    nbatch, m, n = X.shape
    bn, bk = min(bn, n), min(bk, m)
    np_, kp = (-n) % bn, (-m) % bk
    Xp = jnp.pad(X, ((0, 0), (0, kp), (0, np_)))
    M, N = Xp.shape[1], Xp.shape[2]
    nb, n_k = N // bn, M // bk
    T = nb * (nb + 1) // 2
    out_map = _upper_map(nb, lambda b, i, j, kk: (b, i, j))
    out = pl.pallas_call(
        functools.partial(_kernel, alpha=alpha, beta=beta, n_k=n_k, bn=bn,
                          nb=nb),
        grid=(nbatch, T, n_k),
        in_specs=[
            pl.BlockSpec((1, bk, bn),
                         _upper_map(nb, lambda b, i, j, kk: (b, kk, i))),
            pl.BlockSpec((1, bk, bn),
                         _upper_map(nb, lambda b, i, j, kk: (b, kk, j))),
        ],
        out_specs=pl.BlockSpec((1, bn, bn), out_map),
        out_shape=jax.ShapeDtypeStruct((nbatch, N, N), X.dtype),
        scratch_shapes=[pltpu.VMEM((bn, bn), jnp.float32)],
        interpret=interpret,
        name="prism_gram",
    )(Xp, Xp)
    out = out[:, :n, :n]
    return out[0] if squeeze else out


def _power_kernel(a_ref, x1_ref, x2_ref, *rest, beta, n_k, has_c):
    if has_c:
        c_ref, out_ref, acc_ref = rest
    else:
        out_ref, acc_ref = rest
    k = pl.program_id(2)
    alpha = a_ref[pl.program_id(0)]  # hoisted, as in _kernel

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x1, x2 = x1_ref[0], x2_ref[0]
    acc_ref[...] += jnp.dot(x1, x2, precision=mxu.precision(x1, x2),
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        out = alpha * acc_ref[...]
        if has_c:
            out = out + beta * c_ref[0].astype(jnp.float32)
        out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("beta", "bn", "bk",
                                             "interpret"))
def matmul_add_upper(A: jax.Array, B: jax.Array, alpha: jax.Array,
                     C: jax.Array | None = None, *, beta: float = 0.0,
                     bn: int = 256, bk: int = 256,
                     interpret: bool = False) -> jax.Array:
    """Upper-block-triangle of alpha_b * A @ B + beta * C for [B, n, n]
    operands whose result is symmetric (A, B and C polynomials in one
    symmetric matrix: the Gram side's power products R @ Q + f_j R).

    alpha: [B] fp32, one scalar per slice (SMEM; never rounded to the
    operand dtype).  C is read at the output's block index only.  Only
    tiles (i, j) with i <= j are computed; use ``mirror_upper`` (or
    ``ops.sym_matmul_add``) for the full symmetric matrix.
    """
    nbatch, n, _ = A.shape
    bn, bk = min(bn, n), min(bk, n)
    pad = (-n) % math.lcm(bn, bk)
    sq = ((0, 0), (0, pad), (0, pad))
    operands = [alpha.astype(jnp.float32), jnp.pad(A, sq), jnp.pad(B, sq)]
    N = n + pad
    nb, n_k = N // bn, N // bk
    out_map = _upper_map(nb, lambda b, i, j, kk: (b, i, j))
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, bn, bk),
                             _upper_map(nb, lambda b, i, j, kk: (b, i, kk))),
                pl.BlockSpec((1, bk, bn),
                             _upper_map(nb, lambda b, i, j, kk: (b, kk, j)))]
    if C is not None:
        operands.append(jnp.pad(C, sq))
        in_specs.append(pl.BlockSpec((1, bn, bn), out_map))
    out = pl.pallas_call(
        functools.partial(_power_kernel, beta=beta, n_k=n_k,
                          has_c=C is not None),
        grid=(nbatch, nb * (nb + 1) // 2, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bn, bn), out_map),
        out_shape=jax.ShapeDtypeStruct((nbatch, N, N), A.dtype),
        scratch_shapes=[pltpu.VMEM((bn, bn), jnp.float32)],
        interpret=interpret,
        name="prism_power",
    )(*operands)
    return out[:, :n, :n]


def mirror_upper(U: jax.Array) -> jax.Array:
    """The exactly symmetric matrix whose upper triangle is U's.

    Every element on or above the diagonal lies in a computed upper
    block, and every element below it takes its transpose's value, so
    the result is symmetric bit for bit (a diagonal block's own lower
    triangle may differ from its upper one in the last bit: the MXU's
    multi-pass fp32 products need not commute).  Lower blocks of U were
    never visited by the kernel (undefined memory): ``jnp.where`` (not
    a multiply) discards them.
    """
    n = U.shape[-1]
    idx = jnp.arange(n)
    upper = idx[:, None] <= idx[None, :]
    return jnp.where(upper, U, jnp.swapaxes(U, -1, -2))
