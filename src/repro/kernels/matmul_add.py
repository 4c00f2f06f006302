"""Fused GEMM-with-epilogue Pallas TPU kernel:  D = alpha * A @ B + beta * C.

This is the workhorse of the PRISM Newton-Schulz chains: the polar update
applies its Gram-side polynomial Q = a R^d + ... + f1 R to the iterate as
X Q + f0 X in one call (DESIGN.md §7), and the sign and coupled sqrt
families evaluate X (f0 I + f1 R + ... + a R^d) as d fused GEMMs (Horner
on R), so the `+ beta * C` epilogue removes one full HBM
read-modify-write of the [m, n] output per product compared to separate
dot + add ops.

Tiling: (bm x bk) @ (bk x bn) MXU tiles with an fp32 VMEM scratch
accumulator; K is the innermost grid dimension, the C-epilogue and the
output cast happen on the last K step.  Tile sizes are 128-aligned for the
128x128 MXU systolic array.

Precision (DESIGN.md §9): operands may be fp32 or bf16 — the dot always
accumulates fp32 (``preferred_element_type``), the alpha/beta epilogue
runs on the fp32 accumulator (C upcast per tile), and the output rounds
once to the operand dtype.  ref.matmul_add is the bit-level oracle for
both dtypes.

Batching: the grid carries a leading batch dimension (B, M/bm, N/bn, K/bk)
so a whole [B, m, n] parameter bucket runs in ONE kernel launch instead of
a vmap of B independent 2-D launches (DESIGN.md §7).  2-D operands are
promoted to B = 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu


def _kernel(a_ref, b_ref, c_ref, d_ref, acc_ref, *, alpha, beta, n_k):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a, b = a_ref[0], b_ref[0]
    acc_ref[...] += jnp.dot(a, b, precision=mxu.precision(a, b),
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        out = alpha * acc_ref[...]
        if beta != 0.0:
            out = out + beta * c_ref[0].astype(jnp.float32)
        d_ref[0] = out.astype(d_ref.dtype)


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "bm", "bn",
                                             "bk", "interpret"))
def matmul_add(A: jax.Array, B: jax.Array, C: jax.Array | None = None,
               *, alpha: float = 1.0, beta: float = 0.0,
               bm: int = 256, bn: int = 256, bk: int = 256,
               interpret: bool = False) -> jax.Array:
    """D = alpha * A @ B + beta * C for [m, k] or [B, m, k] operands.

    All operands must share the same (possibly absent) batch dimension;
    leading-dim collapsing and broadcasting live in ``ops.py``.
    """
    squeeze = A.ndim == 2
    if squeeze:
        A = A[None]
        B = B[None]
        C = None if C is None else C[None]
    nb, m, k = A.shape
    _, k2, n = B.shape
    assert k == k2, (A.shape, B.shape)
    if C is None:
        C = jnp.zeros((nb, m, n), dtype=A.dtype)
        beta = 0.0
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    # zero-pad to tile multiples (mathematically exact for GEMM+epilogue)
    mp, np_, kp = (-m) % bm, (-n) % bn, (-k) % bk
    Ap = jnp.pad(A, ((0, 0), (0, mp), (0, kp)))
    Bp = jnp.pad(B, ((0, 0), (0, kp), (0, np_)))
    Cp = jnp.pad(C, ((0, 0), (0, mp), (0, np_)))
    M, N, K = Ap.shape[1], Bp.shape[2], Ap.shape[2]
    n_k = K // bk
    out = pl.pallas_call(
        functools.partial(_kernel, alpha=alpha, beta=beta, n_k=n_k),
        grid=(nb, M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda b, i, j, kk: (b, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda b, i, j, kk: (b, kk, j)),
            pl.BlockSpec((1, bm, bn), lambda b, i, j, kk: (b, i, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda b, i, j, kk: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, M, N), A.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="prism_matmul_add",
    )(Ap, Bp, Cp)
    out = out[:, :m, :n]
    return out[0] if squeeze else out
