"""Operation and byte counts of the polar chains, which the per-layer
metrics divide by.

Kept with the benchmark so that no change to the program can change
what a roofline is measured against (a model's own FLOPs per step are
its family's reference's ``train_step_flops``).  The counts follow
the cubic Newton-Schulz family of the PRISM paper (arXiv 2601.22137):
per iteration on an [m, n] matrix with m >= n, the Gram
residual X^T X (2 m n^2 FLOPs), the (degree - 1) Horner products on the
[n, n] side (2 n^3 each) and the [m, n] x [n, n] apply (2 m n^2).  The
sketched trace chain is O(n^2 p) and left out, so the counts are a
floor of the work.
"""
from __future__ import annotations

from typing import Iterable, Tuple

import jax.numpy as jnp


def polar_flops(mshape: Tuple[int, ...], *, iters: int,
                degree: int = 2) -> int:
    """GEMM FLOPs of ``iters`` polar iterations on one [m, n] matrix."""
    m, n = int(mshape[-2]), int(mshape[-1])
    m, n = max(m, n), min(m, n)
    return iters * (4 * m * n * n + 2 * (degree - 1) * n ** 3)


def polar_hbm_bytes(mshape: Tuple[int, ...], dtype, *, iters: int) -> int:
    """HBM bytes of ``iters`` polar iterations on one [m, n] matrix: each
    iteration reads the iterate twice (Gram and apply) and writes and
    reads the [n, n] residual once each."""
    m, n = int(mshape[-2]), int(mshape[-1])
    m, n = max(m, n), min(m, n)
    return iters * (2 * m * n + 2 * n * n) * jnp.dtype(dtype).itemsize


def polar_least_seconds(buckets: Iterable[Tuple[int, Tuple[int, int]]],
                        dtype, *, iters: int, degree: int,
                        peak_flops: float, peak_bytes: float):
    """Least time the chip could take for the bucketed chains: per bucket
    of ``count`` matrices of one shape, the larger of its FLOPs over the
    peak and its bytes over the bandwidth, summed over buckets.  Returns
    (seconds, flops, bytes, bound) where ``bound`` names the limit that
    applied to most of the time ("compute" or "memory")."""
    secs = flops = nbytes = 0.0
    compute_s = memory_s = 0.0
    for count, shape in buckets:
        f = count * polar_flops(shape, iters=iters, degree=degree)
        b = count * polar_hbm_bytes(shape, dtype, iters=iters)
        tf, tb = f / peak_flops, b / peak_bytes
        secs += max(tf, tb)
        if tf >= tb:
            compute_s += tf
        else:
            memory_s += tb
        flops += f
        nbytes += b
    return secs, flops, nbytes, ("compute" if compute_s >= memory_s
                                 else "memory")
