"""Fused sketched-trace Pallas TPU kernels for PRISM's alpha fit.

One PRISM fit needs t_i = tr(S R^i S^T), i = 1..4d+2, via the chain
V_i = R V_{i-1} (V_0 = S^T, S in R^{p x n}).  On GPU these are p-wide
GEMMs + separate trace reductions; on TPU a p~8 matmul wastes the 128x128
MXU, so ``ops.sketch_traces`` pads the sketch to 128 lanes and these
kernels fuse each chain step with its trace epilogue:

    (V', t') = (R @ V,  sum(St * (R @ V)))

saving one full HBM round-trip of V' per power (the trace is reduced from
the fp32 accumulator while the tile is still in VMEM).

Precision (DESIGN.md §9): with bf16 R/St the chain stays bf16 in VMEM
(the ping-pong V buffers take R.dtype) but every trace is reduced in
fp32 FROM THE fp32 ACCUMULATOR of R @ V — before V' rounds to bf16 —
so the PRISM fit always sees fp32 traces; ref.sketch_traces mirrors
this ordering exactly.

Two entry points:

  * ``sketch_step`` — one chain step, grid (row-tiles, k-tiles); the
    original per-power kernel, kept as the building block contract.
  * ``sketch_chain`` — the ENTIRE chain for a whole [B, n, n] residual
    bucket in ONE launch, grid (B, powers, row-tiles, k-tiles).  V never
    leaves VMEM between powers: two ping-pong scratch buffers hold
    V_{i-1} / V_i, so the only HBM traffic is streaming R's tiles once
    per power.  This collapses the ~(4d+2) * B launches per fitted
    iteration of the per-step kernel into one (DESIGN.md §7).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu


def _kernel(r_ref, v_ref, st_ref, vout_ref, t_ref, acc_ref, *, n_k):
    b, i, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (k == 0))
    def _init_trace():
        t_ref[b] = jnp.float32(0.0)

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    r, v = r_ref[0], v_ref[0]
    acc_ref[...] += jnp.dot(r, v, precision=mxu.precision(r, v),
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        vnew = acc_ref[...]
        vout_ref[0] = vnew.astype(vout_ref.dtype)
        # fused trace epilogue: tr contribution of this row tile
        t_ref[b] += jnp.sum(st_ref[...].astype(jnp.float32) * vnew)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def sketch_step(R: jax.Array, V: jax.Array, St: jax.Array,
                *, bm: int = 256, bk: int = 256,
                interpret: bool = False):
    """(V', t') = (R @ V, tr-contraction of St with R @ V).

    R: [n, n] or [B, n, n]; V matches R's batching with [.., n, p128]
    rows; St: [n, p128], shared across the batch (sketch transposed,
    lane-padded).  Returns V' and t' = sum(St * V') per batch element.
    Bounded-VMEM building block: ops.sketch_traces falls back to a loop
    of these when the whole-chain kernel's VMEM footprint (which grows
    with n) exceeds the budget (DESIGN.md §10).
    """
    squeeze = R.ndim == 2
    if squeeze:
        R, V = R[None], V[None]
    nb, n, _ = R.shape
    p = V.shape[-1]
    bm, bk = min(bm, n), min(bk, n)
    mp = (-n) % bm   # row padding (output rows)
    kp = (-n) % bk   # contraction-dim padding
    Rp = jnp.pad(R, ((0, 0), (0, mp), (0, kp)))
    Vp = jnp.pad(V, ((0, 0), (0, kp), (0, 0)))
    Stp = jnp.pad(St, ((0, mp), (0, 0)))
    N, K = Rp.shape[1], Rp.shape[2]
    n_k = K // bk
    vout, t = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=(nb, N // bm, n_k),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda b, i, k: (b, i, k)),
            pl.BlockSpec((1, bk, p), lambda b, i, k: (b, k, 0)),
            pl.BlockSpec((bm, p), lambda b, i, k: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm, p), lambda b, i, k: (b, i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, N, p), R.dtype),
            jax.ShapeDtypeStruct((nb,), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, p), jnp.float32)],
        interpret=interpret,
        name="prism_sketch_step",
    )(Rp, Vp, Stp)
    vout = vout[:, :n]
    return (vout[0], t[0]) if squeeze else (vout, t)


# ---------------------------------------------------------------------------
# Whole-chain kernel: one launch per (bucket, fit)
# ---------------------------------------------------------------------------


def _chain_kernel(r_ref, st_ref, t_ref, v0_ref, v1_ref, acc_ref,
                  *, n_k, bn):
    b = pl.program_id(0)
    pw = pl.program_id(1)   # chain step: computes V_{pw+1} = R V_pw
    i = pl.program_id(2)    # output row tile of V_{pw+1}
    k = pl.program_id(3)    # contraction tile over rows of V_pw

    @pl.when((i == 0) & (k == 0))
    def _init_trace():
        t_ref[b, pw] = jnp.float32(0.0)

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # V_pw rows [k*bn, (k+1)*bn): St for pw == 0, else the ping-pong buffer
    # written by the previous power ((pw-1) % 2).  All three candidate loads
    # are tiny (bn x p128) next to the R tile; the selects keep the kernel
    # branch-free (unvisited buffers may hold garbage — select discards it).
    ks = pl.multiple_of(k * bn, bn)
    st_k = st_ref[pl.ds(ks, bn), :]
    v_prev = jnp.where((pw % 2) == 1, v0_ref[pl.ds(ks, bn), :],
                       v1_ref[pl.ds(ks, bn), :])
    v_in = jnp.where(pw == 0, st_k, v_prev)
    r = r_ref[0]
    acc_ref[...] += jnp.dot(r, v_in, precision=mxu.precision(r, v_in),
                            preferred_element_type=jnp.float32)

    last = k == n_k - 1
    is_ = pl.multiple_of(i * bn, bn)

    @pl.when(last)
    def _trace_epilogue():
        # fused trace: tr contribution of this row tile of V_{pw+1}
        t_ref[b, pw] += jnp.sum(
            st_ref[pl.ds(is_, bn), :].astype(jnp.float32) * acc_ref[...])

    @pl.when(last & ((pw % 2) == 0))
    def _write_v0():
        v0_ref[pl.ds(is_, bn), :] = acc_ref[...].astype(v0_ref.dtype)

    @pl.when(last & ((pw % 2) == 1))
    def _write_v1():
        v1_ref[pl.ds(is_, bn), :] = acc_ref[...].astype(v1_ref.dtype)


@functools.partial(jax.jit, static_argnames=("max_power", "bn", "interpret"))
def sketch_chain(R: jax.Array, St: jax.Array, max_power: int,
                 *, bn: int = 256, interpret: bool = False) -> jax.Array:
    """t_i = tr(S R^i S^T) for i = 1..max_power, one launch for the batch.

    R: [B, n, n] (or [n, n]); St: [n, p128] (sketch transposed, lane-padded,
    shared across the batch).  Returns [B, max_power] fp32 traces (the
    i = 0 trace is sketch-only and computed by the caller).
    """
    squeeze = R.ndim == 2
    if squeeze:
        R = R[None]
    nb, n, _ = R.shape
    p = St.shape[1]
    bn = min(bn, n)
    pad = (-n) % bn
    Rp = jnp.pad(R, ((0, 0), (0, pad), (0, pad)))
    Stp = jnp.pad(St, ((0, pad), (0, 0)))
    N = n + pad
    n_k = N // bn
    t = pl.pallas_call(
        functools.partial(_chain_kernel, n_k=n_k, bn=bn),
        grid=(nb, max_power, n_k, n_k),
        in_specs=[
            pl.BlockSpec((1, bn, bn), lambda b, pw, i, k: (b, i, k)),
            # full St resident in VMEM: needed at row-tile k (chain input)
            # and row-tile i (trace epilogue) in the same grid step
            pl.BlockSpec((N, p), lambda b, pw, i, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((nb, max_power), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((N, p), R.dtype),   # V ping-pong buffer (even pw)
            pltpu.VMEM((N, p), R.dtype),   # V ping-pong buffer (odd pw)
            pltpu.VMEM((bn, p), jnp.float32),
        ],
        interpret=interpret,
        name="prism_sketch_chain",
    )(Rp, Stp)
    return t[0] if squeeze else t
