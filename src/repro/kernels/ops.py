"""jit'd public wrappers around the Pallas kernels.

Dispatch policy — the one place that decides how a chain product runs
(every matrix-function chain calls these wrappers unconditionally):
  * backend, from the platform: on a TPU the compiled Mosaic kernels
    run, always (a ``REPRO_KERNEL_MODE`` other than auto/native raises
    there).  Elsewhere the pure-jnp oracle in ref.py runs, or, under
    ``REPRO_KERNEL_MODE=interpret``, the kernel bodies in the Pallas
    interpreter for correctness validation; an interpret call with any
    operand above ``REPRO_INTERPRET_MAX_ELEMS`` elements (default 2**21;
    0 disables the cutoff) falls back to the oracle, so validation runs
    don't crawl through the interpreter on production-sized buckets.
  * tier, from the shape: a chain whose per-slice working set fits
    ``VMEM_BUDGET`` (``fused_fits``) runs the fused-iteration tier
    below, any other the batch-grid kernels; the sketch chain's
    whole-chain kernel obeys the same budget (``sketch_traces``).  The
    test is batch-size blind because the batch dim is the streamed grid
    dimension.

All wrappers accept leading batch dimensions, which are collapsed into the
single batch-grid dimension of the kernels (DESIGN.md §7): a whole
[B, m, n] parameter bucket is one launch, never a vmap of B 2-D launches.

Precision (DESIGN.md §9): every kernel takes operands in the caller's
compute dtype (fp32 or bf16) and accumulates fp32 on a VMEM scratch;
trace epilogues stay fp32 end-to-end.  The ref.py oracles reproduce the
same accumulation semantics, so dispatch mode never changes the contract.

Fused-iteration tier (DESIGN.md §10): ``residual_chain`` / ``apply_g`` /
``warm_tail`` collapse a fitted iteration to 2 launches and a whole
constant-alpha run to 1.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import fused_iter as _fused
from repro.kernels import gram as _gram
from repro.kernels import matmul_add as _mma
from repro.kernels import ref as _ref
from repro.kernels import sketch_traces as _sk

_LANE = 128  # TPU lane width: sketch dim padded up to this
_DEFAULT_INTERPRET_MAX_ELEMS = 1 << 21
# VMEM budget of the fused tier and the whole-chain sketch kernel
# (DESIGN.md §10): ~16 MiB/core minus headroom for the grid pipeline's
# double buffering.
VMEM_BUDGET = 12 << 20


def fused_vmem_bytes(mshape, dtype, *, coupled: bool = False,
                     sketch_pad: int = _LANE) -> int:
    """Modeled per-grid-step VMEM working set of the fused-iteration
    kernels for ONE [m, n] slice (DESIGN.md §10).

    Batch-size independent by construction: the batch dim is the streamed
    grid dimension, so VMEM holds one slice's state at a time.  Counts
    the warm tail's footprint — the largest of the three kernels:
    double-buffered in/out X blocks + the two ping-pong buffers (doubled
    for the coupled family's Y), R plus its fp32 residual accumulator,
    the fp32 Horner accumulator pair, and the chain's St/V lanes.
    """
    import numpy as np

    m, n = int(mshape[-2]), int(mshape[-1])
    item = np.dtype(dtype).itemsize
    M = m + (-m) % _fused._SUBLANE if not coupled and m != n else \
        m + (-m) % _LANE
    N = n + (-n) % _LANE
    per_mat = M * N * item
    mats = 6 * per_mat * (2 if coupled else 1)   # 4 in/out (dbl-buf) + 2 pp
    resid = N * N * (item + 4)                   # R + fp32 accumulator
    horner = 2 * M * N * 4                       # x32 + fp32 Horner acc
    chain = N * sketch_pad * (3 * item + 4)      # St, V, V' + fp32 acc
    return mats + resid + horner + chain


def fused_fits(mshape, dtype, *, coupled: bool = False) -> bool:
    """Trace-time fused-tier choice for a bucket of [m, n] matrices."""
    return fused_vmem_bytes(mshape, dtype, coupled=coupled) <= VMEM_BUDGET


def polar_flops(mshape, *, iters: int, degree: int = 2) -> int:
    """Modeled GEMM FLOPs of the cubic polar path on one [m, n] view.

    Per NS iteration (polar transposes to m >= n, Gram side n): the Gram
    residual X^T X (2 m n^2), the (degree-1) [n, n] Horner GEMMs
    (2 n^3 each) and the [m, n] x [n, n] apply (2 m n^2).  The sketch
    chain's O(n^2 p) is omitted — negligible by construction (§6).
    """
    m, n = int(mshape[-2]), int(mshape[-1])
    m, n = max(m, n), min(m, n)
    return iters * (4 * m * n * n + 2 * (degree - 1) * n ** 3)


def lowrank_polar_flops(mshape, l: int, *, iters: int, degree: int = 2,
                        power_iters: int = 1) -> int:
    """Modeled GEMM FLOPs of the §14 lowrank tier on one [m, n] view:
    sketch product + power iterations + project + lift (O(mnl) each),
    plus the two l-Gram-side NS chains (rangefinder polar on [m, l], the
    fitted subspace polar on [l, n])."""
    m, n = int(mshape[-2]), int(mshape[-1])
    m, n = max(m, n), min(m, n)
    l = int(l)
    products = (2 + 2 * power_iters) * 2 * m * n * l  # sketch+power+B+lift
    q_chain = polar_flops((m, l), iters=iters, degree=degree)
    sub_chain = polar_flops((n, l), iters=iters, degree=degree)
    return products + q_chain + sub_chain


def polar_hbm_bytes(mshape, dtype, *, iters: int) -> int:
    """Modeled HBM traffic of the cubic path: each iteration streams X
    twice (Gram + apply) and R twice (write + Horner read)."""
    import numpy as np

    m, n = int(mshape[-2]), int(mshape[-1])
    m, n = max(m, n), min(m, n)
    item = np.dtype(dtype).itemsize
    return iters * (2 * m * n + 2 * n * n) * item


def lowrank_polar_hbm_bytes(mshape, l: int, dtype, *, iters: int,
                            power_iters: int = 1) -> int:
    """Modeled HBM traffic of the §14 tier: M streams once per O(mnl)
    product; the chains stream their [m, l] / [l, n] iterates."""
    import numpy as np

    m, n = int(mshape[-2]), int(mshape[-1])
    m, n = max(m, n), min(m, n)
    item = np.dtype(dtype).itemsize
    products = (2 + 2 * power_iters) * (m * n + m * l + n * l) * item
    chains = iters * (2 * (m * l + l * l) + 2 * (n * l + l * l)) * item
    return products + chains


def _gd_coeffs(degree: int):
    """Ascending Taylor coefficients f_0..f_{d-1} of g_d (static floats)."""
    from repro.core import polynomials as poly

    return tuple(float(c) for c in poly.taylor_inv_sqrt(degree - 1))


def _interpret_cutoff() -> int:
    """Max per-operand element count the interpret path accepts; larger
    calls fall back to the ref oracle.  0 (or negative) disables the
    cutoff — benchmarks set that while launch-COUNTING under interpret
    mode, where kernels are only traced, never executed."""
    return int(os.environ.get("REPRO_INTERPRET_MAX_ELEMS",
                              _DEFAULT_INTERPRET_MAX_ELEMS))


def _mode(*operands) -> str:
    env = os.environ.get("REPRO_KERNEL_MODE", "auto")
    if jax.default_backend() == "tpu":
        # never run the oracle or the interpreter on the chip: a stray
        # environment variable must not turn the kernel path into jnp
        if env not in ("auto", "native"):
            raise RuntimeError(
                f"REPRO_KERNEL_MODE={env!r} on a TPU backend: the Pallas "
                f"kernels run natively there; unset it or use 'native'")
        return "native"
    mode = "ref" if env == "auto" else env
    if mode == "interpret":
        cutoff = _interpret_cutoff()
        if cutoff > 0 and any(a is not None and a.size > cutoff
                              for a in operands):
            return "ref"
    return mode  # "ref" | "interpret" | "native"


def _collapse(lead, *arrays):
    """Reshape shared leading batch dims of each array into one [B, ., .]."""
    size = 1
    for d in lead:
        size *= d
    out = []
    for a in arrays:
        if a is None:
            out.append(None)
        elif a.ndim > 2:
            out.append(a.reshape((size,) + a.shape[a.ndim - 2:]))
        else:  # unbatched operand broadcast against the batch
            out.append(jnp.broadcast_to(a, (size,) + a.shape))
    return out


def matmul_add(A, B, C=None, *, alpha: float = 1.0, beta: float = 0.0,
               bm: int = 256, bn: int = 256, bk: int = 256):
    """D = alpha * A @ B (+ beta * C), batched over leading dims."""
    mode = _mode(A, B, C)
    if mode == "ref":
        return _ref.matmul_add(A, B, C, alpha=alpha, beta=beta)
    interp = mode == "interpret"
    lead = A.shape[:-2]
    if not lead:
        return _mma.matmul_add(A, B, C, alpha=alpha, beta=beta,
                               bm=bm, bn=bn, bk=bk, interpret=interp)
    Ab, Bb, Cb = _collapse(lead, A, B, C)
    out = _mma.matmul_add(Ab, Bb, Cb, alpha=alpha, beta=beta,
                          bm=bm, bn=bn, bk=bk, interpret=interp)
    return out.reshape(lead + out.shape[1:])


def gram(X, *, alpha: float = 1.0, beta: float = -1.0,
         bn: int = 256, bk: int = 256):
    """R = alpha * I + beta * X^T X (symmetric syrk), batched."""
    mode = _mode(X)
    if mode == "ref":
        return _ref.gram(X, alpha=alpha, beta=beta)
    interp = mode == "interpret"
    lead = X.shape[:-2]
    (Xb,) = _collapse(lead, X) if lead else (X,)
    U = _gram.gram_upper(Xb, alpha=alpha, beta=beta, bn=bn, bk=bk,
                         interpret=interp)
    R = _gram.mirror_upper(U)
    return R.reshape(lead + R.shape[-2:]) if lead else R


def sym_matmul_add(A, B, C=None, *, alpha, beta: float = 0.0,
                   bn: int = 256, bk: int = 256):
    """D = alpha * A @ B (+ beta * C) for [..., n, n] operands whose
    product is symmetric — polynomials in one symmetric matrix, as the
    Gram side's power products R @ Q + f_j R are: the upper block
    triangle in one launch, mirrored to an exactly symmetric D.

    alpha: scalar or [...] matching the leading dims, fp32 (stays fp32
    in the epilogue).  The ref oracle is the full ``ref.matmul_add``.
    """
    lead = A.shape[:-2]
    a = jnp.broadcast_to(jnp.asarray(alpha, jnp.float32), lead)
    mode = _mode(A, B, C)
    if mode == "ref":
        return _ref.matmul_add(A, B, C, alpha=a[..., None, None], beta=beta)
    Ab, Bb, Cb = _collapse(lead, A, B, C) if lead else \
        (A[None], B[None], None if C is None else C[None])
    U = _gram.matmul_add_upper(Ab, Bb, a.reshape(Ab.shape[0]), Cb,
                               beta=beta, bn=bn, bk=bk,
                               interpret=mode == "interpret")
    return _gram.mirror_upper(U).reshape(A.shape)


def _chain_vmem_bytes(n: int, p: int, dtype, bn: int) -> int:
    """VMEM footprint of the whole-chain kernel: full St plus the two
    [N, p128] ping-pong buffers stay resident across powers, plus the
    double-buffered R tile and the fp32 trace accumulator."""
    bn = min(bn, n)
    N = n + (-n) % bn
    item = jnp.dtype(dtype).itemsize
    return 3 * N * p * item + 2 * bn * bn * item + bn * p * 4


def sketch_traces(R, S, max_power: int, *, bn: int = 256):
    """t_i = tr(S R^i S^T), i = 0..max_power; one fused chain launch.

    ``bn`` tiles both the rows and the contraction dim of the chain (they
    must coincide: V's row partition is reused as the contraction
    partition of the next power inside the single launch).

    VMEM guard (DESIGN.md §10): the whole-chain kernel keeps St and two
    V ping-pong buffers — O(n * 128) bytes — resident for the entire
    launch with no size bound.  When that footprint exceeds
    ``VMEM_BUDGET``, the chain falls back to a loop of bounded-footprint
    per-step ``sketch_step`` launches: max_power launches instead of one,
    but never an over-budget kernel.
    """
    mode = _mode(R)
    if mode == "ref":
        return _ref.sketch_traces(R, S, max_power)
    interp = mode == "interpret"
    p = S.shape[0]
    St = jnp.pad(S.T.astype(R.dtype), ((0, 0), (0, (-p) % _LANE)))
    lead = R.shape[:-2]
    (Rb,) = _collapse(lead, R) if lead else (R[None],)
    t0 = jnp.sum(St.astype(jnp.float32) * St.astype(jnp.float32))
    n = R.shape[-1]
    if _chain_vmem_bytes(n, St.shape[1], R.dtype, bn) <= VMEM_BUDGET:
        ts = _sk.sketch_chain(Rb, St, max_power, bn=bn, interpret=interp)
    else:
        V = jnp.broadcast_to(St, Rb.shape[:-2] + St.shape)
        steps = []
        for _ in range(max_power):
            V, t_i = _sk.sketch_step(Rb, V, St, bm=bn, bk=bn,
                                     interpret=interp)
            steps.append(t_i)
        ts = jnp.stack(steps, axis=-1)
    t = jnp.concatenate(
        [jnp.broadcast_to(t0, ts.shape[:-1] + (1,)), ts], axis=-1)
    return t.reshape(lead + (max_power + 1,))


# ---------------------------------------------------------------------------
# Fused-iteration tier (DESIGN.md §10): single-launch residual+chain,
# Horner application, and constant-alpha warm tails
# ---------------------------------------------------------------------------


def residual_chain(X, S, max_power: int, *, family: str = "polar", Y=None):
    """(R, t): the family residual AND the whole sketched power-trace
    chain in ONE launch — R never leaves VMEM before the traces are
    reduced (it reaches HBM once, as the output the Horner launch reads).

    X: [..., m, n]; S: [p, n] sketch; Y: the coupled sqrt family's second
    iterate.  Returns R [..., n, n] (X.dtype) and fp32 traces
    t [..., max_power + 1] for powers 0..max_power (t0 is sketch-only).
    """
    mode = _mode(X, Y)
    S32 = S.astype(jnp.float32)
    t0 = jnp.sum(S32 * S32)
    lead = X.shape[:-2]
    if mode == "ref":
        R, ts = _ref.residual_chain(X, S, max_power, family=family, Y=Y)
    else:
        interp = mode == "interpret"
        p = S.shape[0]
        St = jnp.pad(S.T.astype(X.dtype), ((0, 0), (0, (-p) % _LANE)))
        Xb, Yb = _collapse(lead, X, Y) if lead else \
            (X[None], None if Y is None else Y[None])
        Rb, ts = _fused.residual_chain(Xb, St, max_power, family=family,
                                       Y=Yb, interpret=interp)
        n = Rb.shape[-1]
        R = Rb.reshape(lead + (n, n))
        ts = ts.reshape(lead + (max_power,))
    t = jnp.concatenate(
        [jnp.broadcast_to(t0, ts.shape[:-1] + (1,)), ts], axis=-1)
    return R, t


def apply_g(X, R, alpha, *, degree: int, Y=None):
    """X g_d(R; alpha) (and g_d(R; alpha) Y when coupled) — the d Horner
    GEMMs in ONE launch with the accumulator resident in VMEM and the
    fitted fp32 alpha applied on the fp32 accumulator (never pre-rounded
    to the compute dtype; DESIGN.md §9/§10).

    alpha: scalar or [...] matching X's leading dims, fp32.
    """
    coeffs = _gd_coeffs(degree)
    mode = _mode(X, R, Y)
    if mode == "ref":
        return _ref.apply_g(X, R, alpha, coeffs=coeffs, Y=Y)
    interp = mode == "interpret"
    lead = X.shape[:-2]
    Xb, Rb, Yb = _collapse(lead, X, R, Y) if lead else \
        (X[None], R[None], None if Y is None else Y[None])
    a = jnp.broadcast_to(jnp.asarray(alpha, jnp.float32),
                         lead).reshape(Xb.shape[0])
    out = _fused.apply_g(Xb, Rb, a, coeffs=coeffs, Y=Yb, interpret=interp)
    if Y is None:
        return out.reshape(lead + out.shape[1:])
    return (out[0].reshape(lead + out[0].shape[1:]),
            out[1].reshape(lead + out[1].shape[1:]))


def warm_tail(X, alphas, *, degree: int, family: str = "polar", Y=None):
    """An entire run of constant-alpha iterations in ONE launch: X (and
    Y) ping-pong in VMEM, HBM sees one read + one write of each operand
    for the whole run (DESIGN.md §10).

    alphas: static sequence of per-iteration floats (the PRISM warm value
    u, or classical Taylor coefficients — any static schedule).
    """
    alphas = tuple(float(a) for a in alphas)
    coeffs = _gd_coeffs(degree)
    mode = _mode(X, Y)
    if mode == "ref":
        return _ref.warm_tail(X, alphas, coeffs=coeffs, family=family, Y=Y)
    interp = mode == "interpret"
    lead = X.shape[:-2]
    Xb, Yb = _collapse(lead, X, Y) if lead else \
        (X[None], None if Y is None else Y[None])
    arr = jnp.asarray(alphas, jnp.float32)
    out = _fused.warm_tail(Xb, arr, len(alphas), family=family,
                           coeffs=coeffs, Y=Yb, interpret=interp)
    if family == "sqrt":
        return (out[0].reshape(lead + out[0].shape[1:]),
                out[1].reshape(lead + out[1].shape[1:]))
    return out.reshape(lead + out.shape[1:])


def count_launches(fn, *args) -> int:
    """Pallas launches fn would issue at runtime, counted by tracing.

    Wraps the kernel wrapper functions (each contains exactly one
    pallas_call) and counts call sites during an abstract trace — the
    inner-jit compilation cache dedupes *traces*, not runtime launches,
    so counting wrappers is the accurate launch count.  Observability
    helper for tests and benchmarks (the launch-count contract of
    DESIGN.md §7).
    """
    targets = [(_gram, "gram_upper"), (_gram, "matmul_add_upper"),
               (_mma, "matmul_add"),
               (_sk, "sketch_chain"), (_sk, "sketch_step"),
               (_fused, "residual_chain"), (_fused, "apply_g"),
               (_fused, "warm_tail")]
    counter = {"n": 0}

    def wrap(f):
        def counting(*a, **k):
            counter["n"] += 1
            return f(*a, **k)
        return counting

    saved = [getattr(mod, name) for mod, name in targets]
    for mod, name in targets:
        setattr(mod, name, wrap(getattr(mod, name)))
    try:
        jax.make_jaxpr(fn)(*args)
    finally:
        for (mod, name), f in zip(targets, saved):
            setattr(mod, name, f)
    return counter["n"]
