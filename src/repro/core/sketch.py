"""Randomized sketching primitives (PRISM Part II, step 5).

A Gaussian matrix S in R^{p x n} with i.i.d. N(0, 1/p) entries is an
oblivious subspace embedding; PRISM only needs the sketched power traces

    t_i = tr(S R^i S^T),  i = 0..max_power,

computed by the chained products V_i = R V_{i-1} with V_0 = S^T, so the
total cost is O(n^2 p max_power) — negligible next to the O(n^3) GEMMs of
one Newton-Schulz iteration.  The chain itself is a kernel
(kernels/ops.py::sketch_traces); this module draws S and holds the exact,
unsketched traces.

Note: the paper's Theorem 2 types the entries as N(1, 1/p); the OSE
literature it cites (Balabanov & Nouy 2019, Prop. 3.7) uses mean-zero
N(0, 1/p), which is what we implement (see DESIGN.md §6).

All functions broadcast over leading batch dimensions of R
(R: [..., n, n], S: [p, n]) so the PRISM engine can run vmapped/stacked
over scanned-layer parameter stacks without per-matrix dispatch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gaussian_sketch(key: jax.Array, p: int, n: int, dtype=jnp.float32) -> jax.Array:
    """S in R^{p x n} with i.i.d. N(0, 1/p) entries."""
    return jax.random.normal(key, (p, n), dtype=dtype) / jnp.sqrt(
        jnp.asarray(p, dtype=dtype))


def exact_power_traces(R: jax.Array, max_power: int) -> jax.Array:
    """Unsketched t_i = tr(R^i) (the paper's Eq. (3) objective); O(n^3).

    Used by tests and by the ``sketch_dim=0`` exact-fit mode.
    """
    n = R.shape[-1]
    eye = jnp.eye(n, dtype=R.dtype)
    P = jnp.broadcast_to(eye, R.shape)
    traces = [jnp.asarray(n, jnp.float32) * jnp.ones(R.shape[:-2], jnp.float32)]
    for _ in range(max_power):
        # fp32 accumulation + fp32 trace epilogue, powers rounded to the
        # compute dtype between steps (same policy as the sketched chain)
        Pacc = jnp.matmul(R, P, preferred_element_type=jnp.float32)
        traces.append(jnp.trace(Pacc, axis1=-2, axis2=-1))
        P = Pacc.astype(R.dtype)
    return jnp.stack(traces, axis=-1)
