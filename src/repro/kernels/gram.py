"""Symmetric Gram (syrk) Pallas TPU kernel:  R = alpha * I + beta * X^T X.

Newton-Schulz for the polar factor forms R_k = I - X_k^T X_k every
iteration; the product is symmetric, but a generic GEMM computes all n^2
output tiles.  This kernel enumerates ONLY the upper-triangular block grid
(T = nb (nb+1) / 2 tiles instead of nb^2) — the linear tile index t is
unranked to (block-row i, block-col j) in closed form inside the BlockSpec
index maps — cutting MXU work and HBM write traffic nearly in half.  This
is a TPU-native beyond-paper optimization (DESIGN.md §3).

The kernel emits the upper-block-triangle U (lower blocks zero);
``ops.gram`` mirrors it with one elementwise pass:
    R = U + transpose(strictly-upper-block part of U).

Precision (DESIGN.md §9): bf16 X accumulates X^T X in fp32 on the VMEM
scratch; the alpha*I epilogue is fp32 and the tile rounds once to the
operand dtype — the residual a bf16 Newton-Schulz iteration consumes is
the correctly-rounded fp32 Gram, not a bf16-accumulated one.

Batching: the grid is (B, T, K/bk) so a whole [B, m, n] parameter bucket
forms its residuals in ONE launch (DESIGN.md §7); 2-D inputs run as B = 1.
"""
from __future__ import annotations

import functools

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
    the result are zero.  Use ``ops.gram`` for the full symmetric matrix.
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

    def in_map_a(b, t, kk):
        i, _ = _unrank_upper(t, nb)
        return (b, kk, i)

    def in_map_b(b, t, kk):
        _, j = _unrank_upper(t, nb)
        return (b, kk, j)

    def out_map(b, t, kk):
        i, j = _unrank_upper(t, nb)
        return (b, i, j)

    out = pl.pallas_call(
        functools.partial(_kernel, alpha=alpha, beta=beta, n_k=n_k, bn=bn,
                          nb=nb),
        grid=(nbatch, T, n_k),
        in_specs=[
            pl.BlockSpec((1, bk, bn), in_map_a),
            pl.BlockSpec((1, bk, bn), in_map_b),
        ],
        out_specs=pl.BlockSpec((1, bn, bn), out_map),
        out_shape=jax.ShapeDtypeStruct((nbatch, N, N), X.dtype),
        scratch_shapes=[pltpu.VMEM((bn, bn), jnp.float32)],
        interpret=interpret,
        name="prism_gram",
    )(Xp, Xp)
    out = out[:, :n, :n]
    return out[0] if squeeze else out


def mirror_upper(U: jax.Array, bn: int) -> jax.Array:
    """R = upper-blocks(U) + transpose(strictly-upper-blocks(U)).

    Lower blocks of U were never visited by the kernel (undefined memory),
    so both terms mask at block granularity before combining.
    """
    n = U.shape[-1]
    blk = jnp.arange(n) // bn
    upper = blk[:, None] <= blk[None, :]
    strictly_upper = blk[:, None] < blk[None, :]
    zero = jnp.zeros((), U.dtype)
    # jnp.where (not multiply): unvisited blocks may be NaN-filled
    return jnp.where(upper, U, zero) + \
        jnp.swapaxes(jnp.where(strictly_upper, U, zero), -1, -2)
