"""Single-launch fused-iteration Pallas TPU kernels (DESIGN.md §10).

The batch-grid kernels of §7 made one fitted PRISM-NS iteration cost a
constant 2+d launches per bucket — but X and R still make a full HBM
round-trip between every launch, and a whole polar call costs
iters*(2+d) launches.  For buckets whose per-slice working set fits a
VMEM budget (the tier choice lives in ``ops.fused_fits``; it depends
only on the matrix shape, never on B), these kernels collapse the
iteration structure itself:

  * ``residual_chain`` — the residual R (I - X^T X for polar, I - X^2
    for sign, sym(I - Y X) for the coupled sqrt family) AND the whole
    sketched power-trace chain in ONE launch, grid (B,).  R is formed on
    the fp32 MXU accumulator, rounded once to the compute dtype, and the
    chain runs on it while it is still in VMEM — R reaches HBM exactly
    once (as the output the Horner launch reads back), instead of once
    per chain power.
  * ``apply_g`` — the d-GEMM Horner application X g_d(R; alpha) (and the
    coupled g_d(R; alpha) Y) in ONE launch, grid (B,).  The Horner
    accumulator stays fp32 in VMEM across all d GEMMs (each dot rounds
    its operand to the compute dtype — that is what the MXU consumes —
    but the carried f_j*X epilogues never round), and the FITTED fp32
    alpha multiplies the fp32 accumulator directly instead of
    pre-rounding to bf16 (DESIGN.md §9: the fit is pinned fp32; this
    keeps it fp32 all the way into the update).
  * ``warm_tail`` — an entire run of constant-alpha iterations (the
    warm-start phase of PRISM, or a whole classical-alpha chain) as ONE
    launch, grid (B, iters): X ping-pongs between two VMEM scratch
    buffers, each grid step computes the residual and the Horner update
    in-register, and X touches HBM exactly twice for the whole run —
    one read, one write — instead of (1+d) launches and 2(1+d) n^2
    round-trips per iteration.  The per-iteration alphas arrive as an
    SMEM vector, so mixed constant schedules fuse too.

Why the fit phase cannot fuse across iterations: alpha_{k+1} is the
argmin of a quartic whose coefficients are the sketched traces of
R_{k+1}, which only exists after update k — the closed-form minimizer
(cubic root selection, interval clamping) runs between launches in XLA.
The warm phase has no such data dependence, which is exactly why it
collapses to one launch.

Padding: wrappers zero-pad X (and the lane-padded sketch St) up to TPU
tile multiples.  Zero padding is exact end-to-end here: pad rows/cols of
X stay identically zero through every update, the residual's pad block
is exactly I with zero coupling, and the chain's trace contributions
from pad rows vanish because St's pad rows are zero (same §7 argument as
pad-to-bucket, applied at tile granularity).  For the coupled family Y's
pad block evolves as a self-contained scalar multiple of I and is sliced
away.

Precision: operands fp32 or bf16; every dot accumulates fp32
(``preferred_element_type``); trace epilogues reduce the fp32
accumulator of R @ V before V rounds (§9).  ref.py carries op-for-op
oracles for the fused accumulation order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mxu

_SUBLANE = 16   # covers the bf16 (16, 128) min tile; fp32 needs only 8
_LANE = 128

FAMILIES = ("polar", "sign", "sqrt")


def _pad2(n: int, mult: int) -> int:
    return (-n) % mult


def _eye(n: int) -> jax.Array:
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.where(row == col, jnp.float32(1.0), jnp.float32(0.0))


def _residual32(x, y, family: str):
    """fp32 residual of the family: the I - <product> epilogue runs on the
    fp32 MXU accumulator; callers round once to the compute dtype."""
    if family == "polar":
        g = jax.lax.dot_general(x, x, (((0,), (0,)), ((), ())),
                                precision=mxu.precision(x),
                                preferred_element_type=jnp.float32)
    elif family == "sign":
        g = jnp.dot(x, x, precision=mxu.precision(x),
                    preferred_element_type=jnp.float32)
    else:  # coupled sqrt: R = I - Y X, re-symmetrized for stability
        g = jnp.dot(y, x, precision=mxu.precision(y, x),
                    preferred_element_type=jnp.float32)
    r32 = _eye(g.shape[0]) - g
    if family == "sqrt":
        r32 = 0.5 * (r32 + r32.T)
    return r32


def _horner32(x, x32, r, alpha32, coeffs, side: str):
    """fp32 Horner accumulator for X g_d(R; a) (side="right") or
    g_d(R; a) Y (side="left"); alpha32 is an fp32 scalar and the carried
    f_j * X epilogues never round — only each dot's operand does."""
    acc = alpha32 * x32
    for j in range(len(coeffs) - 1, -1, -1):
        lo = acc.astype(x.dtype)
        prec = mxu.precision(lo, r)
        prod = (jnp.dot(lo, r, precision=prec,
                        preferred_element_type=jnp.float32)
                if side == "right"
                else jnp.dot(r, lo, precision=prec,
                             preferred_element_type=jnp.float32))
        acc = prod + coeffs[j] * x32
    return acc


# ---------------------------------------------------------------------------
# (a) fused residual + sketched power-trace chain: one launch per bucket
# ---------------------------------------------------------------------------


def _res_chain_kernel(*refs, family, max_power, coupled):
    if coupled:
        x_ref, y_ref, st_ref, r_ref, t_ref = refs
        y = y_ref[0]
    else:
        x_ref, st_ref, r_ref, t_ref = refs
        y = None
    b = pl.program_id(0)
    x = x_ref[0]
    r = _residual32(x, y, family).astype(r_ref.dtype)
    r_ref[0] = r
    st = st_ref[...]
    st32 = st.astype(jnp.float32)
    v = st
    for i in range(max_power):
        vacc = jnp.dot(r, v, precision=mxu.precision(r, v),
                       preferred_element_type=jnp.float32)
        t_ref[b, i] = jnp.sum(st32 * vacc)
        v = vacc.astype(st.dtype)


@functools.partial(jax.jit,
                   static_argnames=("max_power", "family", "interpret"))
def residual_chain(X: jax.Array, St: jax.Array, max_power: int,
                   *, family: str = "polar", Y: jax.Array | None = None,
                   interpret: bool = False):
    """(R, t): the family residual of X (and Y) plus t_i = tr(S R^i S^T),
    i = 1..max_power, in ONE launch over the [B, ., .] bucket.

    X: [B, m, n] (polar) or [B, n, n] (sign / sqrt); Y: [B, n, n] for the
    coupled sqrt family; St: [n, p128] (sketch transposed, lane-padded).
    Returns R [B, n, n] in X.dtype and fp32 traces [B, max_power] (the
    i = 0 sketch-only trace is the caller's, as in ops.sketch_traces).
    """
    assert family in FAMILIES, family
    coupled = family == "sqrt"
    nb, m, n = X.shape
    p = St.shape[1]
    np_ = _pad2(n, _LANE)
    # square families: the residual lives on the full matrix, so both dims
    # pad to the lane multiple (m == n there)
    mp = np_ if family != "polar" else _pad2(m, _SUBLANE)
    Xp = jnp.pad(X, ((0, 0), (0, mp), (0, np_)))
    Stp = jnp.pad(St, ((0, np_), (0, 0)))
    N = n + np_
    M = Xp.shape[1]
    operands = [Xp]
    in_specs = [pl.BlockSpec((1, M, N), lambda b: (b, 0, 0))]
    if coupled:
        operands.append(jnp.pad(Y, ((0, 0), (0, np_), (0, np_))))
        in_specs.append(pl.BlockSpec((1, N, N), lambda b: (b, 0, 0)))
    operands.append(Stp)
    in_specs.append(pl.BlockSpec((N, p), lambda b: (0, 0)))
    R, t = pl.pallas_call(
        functools.partial(_res_chain_kernel, family=family,
                          max_power=max_power, coupled=coupled),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, N, N), lambda b: (b, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, N, N), X.dtype),
            jax.ShapeDtypeStruct((nb, max_power), jnp.float32),
        ],
        interpret=interpret,
        name="prism_residual_chain",
    )(*operands)
    return R[:, :n, :n], t


# ---------------------------------------------------------------------------
# (b) fused d-GEMM Horner application: one launch per bucket
# ---------------------------------------------------------------------------


def _apply_kernel(*refs, coeffs, coupled):
    if coupled:
        x_ref, y_ref, r_ref, a_ref, xo_ref, yo_ref = refs
    else:
        x_ref, r_ref, a_ref, xo_ref = refs
    b = pl.program_id(0)
    x = x_ref[0]
    r = r_ref[0]
    a = a_ref[b]
    acc = _horner32(x, x.astype(jnp.float32), r, a, coeffs, "right")
    xo_ref[0] = acc.astype(xo_ref.dtype)
    if coupled:
        y = y_ref[0]
        yacc = _horner32(y, y.astype(jnp.float32), r, a, coeffs, "left")
        yo_ref[0] = yacc.astype(yo_ref.dtype)


@functools.partial(jax.jit, static_argnames=("coeffs", "interpret"))
def apply_g(X: jax.Array, R: jax.Array, alpha: jax.Array,
            *, coeffs: tuple, Y: jax.Array | None = None,
            interpret: bool = False):
    """X g_d(R; alpha) — and, when Y is given (the coupled sqrt family),
    also g_d(R; alpha) Y — as ONE launch of d fused GEMMs per operand.

    X: [B, m, n]; R: [B, n, n]; alpha: [B] fp32 (stays fp32 in the
    epilogue); coeffs: ascending Taylor coefficients f_0..f_{d-1} of
    g_d (static).  Returns X' (or (X', Y')).
    """
    nb, m, n = X.shape
    coupled = Y is not None
    mp, np_ = _pad2(m, _SUBLANE), _pad2(n, _LANE)
    Xp = jnp.pad(X, ((0, 0), (0, mp), (0, np_)))
    Rp = jnp.pad(R, ((0, 0), (0, np_), (0, np_)))
    M, N = Xp.shape[1], n + np_
    alpha = alpha.astype(jnp.float32)
    operands = [Xp]
    in_specs = [pl.BlockSpec((1, M, N), lambda b: (b, 0, 0))]
    out_specs = [pl.BlockSpec((1, M, N), lambda b: (b, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((nb, M, N), X.dtype)]
    if coupled:
        operands.append(jnp.pad(Y, ((0, 0), (0, np_), (0, np_))))
        in_specs.append(pl.BlockSpec((1, N, N), lambda b: (b, 0, 0)))
        out_specs.append(pl.BlockSpec((1, N, N), lambda b: (b, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((nb, N, N), X.dtype))
    operands += [Rp, alpha]
    in_specs += [pl.BlockSpec((1, N, N), lambda b: (b, 0, 0)),
                 pl.BlockSpec(memory_space=pltpu.SMEM)]
    outs = pl.pallas_call(
        functools.partial(_apply_kernel, coeffs=coeffs, coupled=coupled),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="prism_apply_g",
    )(*operands)
    if coupled:
        return outs[0][:, :m, :n], outs[1][:, :n, :n]
    return outs[0][:, :m, :n]


# ---------------------------------------------------------------------------
# (c) fused multi-iteration warm tail: one launch per bucket
# ---------------------------------------------------------------------------


def _warm_kernel(*refs, family, coeffs, n_iters, coupled):
    if coupled:
        x_ref, y_ref, a_ref, xo_ref, yo_ref, xa, xb, ya, yb = refs
    else:
        x_ref, a_ref, xo_ref, xa, xb = refs
    it = pl.program_id(1)
    odd = (it % 2) == 1
    # iteration `it` reads the buffer iteration it-1 wrote ((it-1) % 2);
    # at it == 0 it reads the HBM input instead.  All candidate loads are
    # VMEM-resident; the selects keep the kernel branch-free (unvisited
    # buffers may hold garbage — select discards it).
    x = jnp.where(it == 0, x_ref[0], jnp.where(odd, xa[...], xb[...]))
    y = None
    if coupled:
        y = jnp.where(it == 0, y_ref[0], jnp.where(odd, ya[...], yb[...]))
    r = _residual32(x, y, family).astype(x.dtype)
    a = a_ref[it]
    new_x = _horner32(x, x.astype(jnp.float32), r, a, coeffs,
                      "right").astype(x.dtype)
    if coupled:
        new_y = _horner32(y, y.astype(jnp.float32), r, a, coeffs,
                          "left").astype(y.dtype)

    @pl.when(jnp.logical_not(odd))
    def _write_even():
        xa[...] = new_x
        if coupled:
            ya[...] = new_y

    @pl.when(odd)
    def _write_odd():
        xb[...] = new_x
        if coupled:
            yb[...] = new_y

    @pl.when(it == n_iters - 1)
    def _emit():
        xo_ref[0] = new_x
        if coupled:
            yo_ref[0] = new_y


@functools.partial(jax.jit,
                   static_argnames=("n_iters", "family", "coeffs",
                                    "interpret"))
def warm_tail(X: jax.Array, alphas: jax.Array, n_iters: int,
              *, family: str = "polar", coeffs: tuple,
              Y: jax.Array | None = None, interpret: bool = False):
    """``n_iters`` constant-alpha iterations of the family in ONE launch.

    X: [B, m, n] (polar; [B, n, n] for sign / sqrt); alphas: [n_iters]
    fp32, one per iteration (SMEM-resident — any static schedule fuses).
    X (and Y) ping-pong between two VMEM scratch buffers, so HBM sees one
    read and one write of each operand for the entire run.
    """
    assert family in FAMILIES, family
    coupled = family == "sqrt"
    nb, m, n = X.shape
    mp, np_ = _pad2(m, _SUBLANE), _pad2(n, _LANE)
    if family != "polar":
        mp = np_
    Xp = jnp.pad(X, ((0, 0), (0, mp), (0, np_)))
    M, N = Xp.shape[1], n + np_
    alphas = alphas.astype(jnp.float32)
    operands = [Xp]
    in_specs = [pl.BlockSpec((1, M, N), lambda b, it: (b, 0, 0))]
    out_specs = [pl.BlockSpec((1, M, N), lambda b, it: (b, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((nb, M, N), X.dtype)]
    scratch = [pltpu.VMEM((M, N), X.dtype), pltpu.VMEM((M, N), X.dtype)]
    if coupled:
        operands.append(jnp.pad(Y, ((0, 0), (0, np_), (0, np_))))
        in_specs.append(pl.BlockSpec((1, N, N), lambda b, it: (b, 0, 0)))
        out_specs.append(pl.BlockSpec((1, N, N), lambda b, it: (b, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((nb, N, N), X.dtype))
        scratch += [pltpu.VMEM((N, N), X.dtype),
                    pltpu.VMEM((N, N), X.dtype)]
    operands.append(alphas)
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    outs = pl.pallas_call(
        functools.partial(_warm_kernel, family=family, coeffs=coeffs,
                          n_iters=n_iters, coupled=coupled),
        grid=(nb, n_iters),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="prism_warm_tail",
    )(*operands)
    if coupled:
        return outs[0][:, :n, :n], outs[1][:, :n, :n]
    return outs[0][:, :m, :n]
