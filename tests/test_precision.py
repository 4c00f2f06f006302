"""Dtype-parameterized numerics suite for the mixed-precision matrix-
function engine (DESIGN.md §9).

Every matfn family runs under the bf16 policy (bf16 compute / fp32
accumulate / fp32 fit) against the fp32 policy over the paper's spectrum
zoo — Gaussian, HTMP heavy-tail, near-rank-deficient, ill-conditioned —
asserting principled tolerances: a self-correcting iteration in bf16
converges to f(round_bf16(A)), so the relative Frobenius error is
O(u_bf16 * kappa_f) with u_bf16 = 2^-8 ~ 3.9e-3 and kappa_f the
conditioning of the family on the given spectrum (amplified for the
inverse families, ~1 for polar/sign).  Tolerances below are 2-3x the
measured errors under those bounds.

Also asserts the engine-level contracts: PRISM-fitted bf16 NS reaches the
fp32 residual target within +1 iteration (the fit absorbs bf16 residual
noise), bucketed Muon/Shampoo steps match across policies, launch counts
are dtype-independent, and the pad-trace correction stays exact under
bf16 compute.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import MatfnPrecision, OptimizerConfig, PrismConfig
from repro.core import matfn
from repro.core import newton_schulz as ns
from repro.core import random_matrices as rm
from repro.core import sketch
from repro.kernels import ops
from repro.optim import bucketing, make_optimizer

pytestmark = pytest.mark.tier1

SPECTRA = ["gaussian", "htmp", "near_rank_deficient", "ill_conditioned"]
U_BF16 = 2.0 ** -8

# relative-Frobenius parity tolerance per family: ~2-3x the measured
# bf16-vs-fp32 error over the spectrum zoo (see module docstring; the
# inverse families carry the kappa amplification of the cond<=100 SPDs)
TOL = {"polar": 0.08, "signm": 0.04, "sqrtm": 0.03, "inv_sqrtm": 0.06,
       "inv": 0.10, "inv_proot": 0.05}


def _base_matrix(kind: str, key, m: int, n: int):
    """[m, n] test matrix with the named singular spectrum, sigma_max ~ 1."""
    r = min(m, n)
    if kind == "gaussian":
        return rm.gaussian(key, m, n) / np.sqrt(m)
    if kind == "htmp":
        return rm.htmp(key, m, n, kappa=0.5)
    if kind == "near_rank_deficient":
        s = jnp.concatenate([jnp.linspace(1.0, 0.3, r - 3),
                             jnp.full((3,), 1e-2)])
        return rm.with_spectrum(key, m, n, s)
    assert kind == "ill_conditioned"
    return rm.log_uniform_spectrum(key, m, n, smin=5e-2)


def _spd_matrix(kind: str, key, n: int):
    """SPD companion: squared spectrum of the kind's base matrix, floored
    at cond = 100 — the inverse families' bf16 error scales with kappa,
    and past ~1/u_bf16 the comparison measures the spectrum, not the
    engine."""
    A = _base_matrix(kind, key, n, n)
    s = jnp.linalg.svd(A, compute_uv=False)
    eigs = jnp.clip(jnp.square(s) / s[0] ** 2, 1e-2, 1.0)
    return rm.spd_with_eigs(jax.random.fold_in(key, 7), n, eigs)


def _fro_rel(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-9)


def _run_family(family: str, kind: str, key, dtype: str):
    kk = jax.random.fold_in(key, SPECTRA.index(kind))
    cfg = PrismConfig(degree=2, iterations=10, warm_alpha_iters=1,
                      sketch_dim=8, dtype=dtype)
    if family == "polar":
        A = _base_matrix(kind, kk, 64, 48)
        return matfn.polar(A.astype(dtype), cfg=cfg, key=key)
    if family == "signm":
        B = _base_matrix(kind, kk, 48, 48)
        sym = 0.5 * (B + B.T)
        return matfn.signm(sym.astype(dtype), cfg=cfg, key=key)
    spd = _spd_matrix(kind, kk, 48)
    if family == "sqrtm":
        return matfn.sqrtm(spd.astype(dtype), cfg=cfg, key=key)[0]
    if family == "inv_sqrtm":
        return matfn.sqrtm(spd.astype(dtype), cfg=cfg, key=key)[1]
    if family == "inv":
        return matfn.inv(spd.astype(dtype), method="prism_chebyshev",
                         key=key, iters=25, dtype=jnp.dtype(dtype))
    assert family == "inv_proot"
    return matfn.inv_proot(spd.astype(dtype), p=4, method="prism", key=key,
                           iters=20, dtype=jnp.dtype(dtype))


@pytest.mark.parametrize("kind", SPECTRA)
@pytest.mark.parametrize("family", sorted(TOL))
def test_bf16_policy_parity(key, family, kind):
    """bf16-policy result vs fp32 policy, every family x spectrum."""
    f32 = _run_family(family, kind, key, "float32")
    f16 = _run_family(family, kind, key, "bfloat16")
    assert f16.dtype == jnp.bfloat16
    err = _fro_rel(f16, f32)
    assert err < TOL[family], (family, kind, err)


@pytest.mark.parametrize("kind", SPECTRA)
def test_bf16_polar_is_orthogonal(key, kind):
    """Quality, not just parity: the bf16 polar factor is orthogonal to
    ~u_bf16 resolution (||X^T X - I||_F / sqrt(n) at the rounding floor)."""
    X = np.asarray(_run_family("polar", kind, key, "bfloat16"), np.float32)
    n = X.shape[-1]
    ortho = np.linalg.norm(X.T @ X - np.eye(n)) / np.sqrt(n)
    assert ortho < 8 * U_BF16, (kind, ortho)


@pytest.mark.parametrize("kind", SPECTRA)
def test_bf16_prism_residual_within_one_iteration(key, kind):
    """The headline adaptivity contract: PRISM's fp32-pinned fit absorbs
    bf16 residual noise, so the bf16 chain reaches the fp32 residual
    target (5e-2 normalized — above the bf16 floor ~sqrt(n) u) within +1
    iteration of the fp32 chain, on every spectrum."""
    A = _base_matrix(kind, jax.random.fold_in(key, SPECTRA.index(kind)),
                     96, 64)
    target = 5e-2
    hits = {}
    for dt in ("float32", "bfloat16"):
        cfg = PrismConfig(degree=2, iterations=10, warm_alpha_iters=1,
                          sketch_dim=8, dtype=dt)
        _, info = ns.polar(A.astype(dt), cfg=cfg, method="prism", key=key,
                           return_info=True)
        # residual_fro[k] = ||R_k||_F BEFORE update k; normalized by
        # sqrt(n) so the target is a per-singular-value deviation
        r = np.asarray(info.residual_fro).reshape(-1) / np.sqrt(64)
        below = np.nonzero(r < target)[0]
        assert below.size, (kind, dt, r)
        hits[dt] = int(below[0])
    assert hits["bfloat16"] <= hits["float32"] + 1, (kind, hits)


def test_fit_is_fp32_regardless_of_compute(key):
    """MatfnPrecision pins the fit: alphas fitted from a bf16 residual are
    fp32 scalars and lie within the constraint interval; the fit of the
    bf16-rounded residual tracks the fp32 fit closely (the traces are
    fp32-accumulated, so the fit sees only O(u) input perturbation)."""
    from repro.core import polynomials as poly
    from repro.core import prism

    R = _base_matrix("gaussian", key, 64, 64)
    R = 0.15 * 0.5 * (R + R.T)
    apoly = poly.newton_schulz_residual(2)
    lo, hi = PrismConfig(degree=2).bounds
    a32 = prism.fit_alpha(R, apoly, lo, hi, key=key, sketch_dim=8)
    a16 = prism.fit_alpha(R.astype(jnp.bfloat16), apoly, lo, hi, key=key,
                          sketch_dim=8)
    assert a32.dtype == jnp.float32 and a16.dtype == jnp.float32
    assert lo <= float(a16) <= hi
    np.testing.assert_allclose(float(a16), float(a32), rtol=0.05, atol=0.02)


def test_pad_trace_correction_exact_under_bf16(key):
    """DESIGN.md §9: the §7 pad-trace correction stays exact in bf16 —
    zero padding is exact in any dtype, the pad block of R is exactly I,
    and the fp32-accumulated traces pick up exactly the fp32 sum of
    squared pad columns of the (bf16-rounded) sketch."""
    n, padn, p, maxp = 24, 32, 8, 10
    R = jax.random.normal(key, (n, n)) / (3 * np.sqrt(n))
    R = (0.5 * (R + R.T)).astype(jnp.bfloat16)
    Rp = jnp.eye(padn, dtype=jnp.bfloat16).at[:n, :n].set(R)
    S = sketch.gaussian_sketch(jax.random.fold_in(key, 1), p, padn,
                               dtype=jnp.bfloat16)
    t_pad = ops.sketch_traces(Rp, S, maxp)
    c = jnp.sum(jnp.square(S[:, n:].astype(jnp.float32)))
    t_real = ops.sketch_traces(R, S[:, :n], maxp)
    # fp32-tight: the only difference is fp32 summation order
    np.testing.assert_allclose(np.asarray(t_pad) - float(c),
                               np.asarray(t_real), rtol=1e-4, atol=1e-4)


# ------------------------------------------------------- optimizer level


def _tree(key):
    params = {"w1": jax.random.normal(key, (64, 32)),
              "w3": jax.random.normal(jax.random.fold_in(key, 2),
                                      (3, 48, 32)),
              "b": jax.random.normal(jax.random.fold_in(key, 4), (64,))}
    axes = {"w1": ("embed", "mlp"), "w3": ("layers", "embed", "mlp"),
            "b": ("embed",)}
    return params, axes


@pytest.mark.parametrize("name", ["muon", "shampoo"])
def test_bucketed_step_bf16_parity(key, name):
    """A full bucketed optimizer step under matfn_dtype="bfloat16" stays
    within the lr-scaled matfn tolerance of the fp32 step."""
    params, axes = _tree(key)
    grads = jax.tree.map(
        lambda p: jax.random.normal(jax.random.fold_in(key, 9), p.shape),
        params)
    outs = {}
    for dt in ("float32", "bfloat16"):
        ocfg = OptimizerConfig(
            name=name, learning_rate=0.05 if name == "muon" else 1e-3,
            matfn_dtype=dt, max_precond_dim=256,
            prism=PrismConfig(degree=2, iterations=6, warm_alpha_iters=1,
                              sketch_dim=8))
        opt = make_optimizer(ocfg, axes)
        outs[dt], _ = jax.jit(opt.update)(grads, opt.init(params), params,
                                          0, key)
    for k in params:
        err = _fro_rel(outs["bfloat16"][k], outs["float32"][k])
        assert err < 2e-3, (name, k, err)


def test_bf16_gather_stacks_in_bf16(key):
    """The bucket gather materializes directly in the compute dtype —
    the stacked array every chain GEMM reads is bf16, not fp32-then-cast."""
    views = [jax.random.normal(jax.random.fold_in(key, i), (16, 8))
             for i in range(3)]
    b = bucketing.plan_buckets([v.shape for v in views])[0]
    stacked = bucketing.gather_bucket(b, views, dtype=jnp.bfloat16)
    assert stacked.dtype == jnp.bfloat16 and stacked.shape == (3, 16, 8)
    # and fp32 gathers are untouched by the dtype plumbing
    assert bucketing.gather_bucket(b, views).dtype == jnp.float32


def test_cache_dtype_follows_policy(key):
    """precond_cache_dtype="auto" stores the staleness caches in the
    matfn compute dtype; explicit "float32" overrides; lax.cond branches
    agree in dtype either way (a dynamic-schedule step compiles)."""
    params, axes = _tree(key)
    grads = jax.tree.map(
        lambda p: jax.random.normal(jax.random.fold_in(key, 3), p.shape),
        params)
    for cache, want in [("auto", jnp.bfloat16), ("float32", jnp.float32)]:
        ocfg = OptimizerConfig(
            name="muon", precond_every=3, matfn_dtype="bfloat16",
            precond_cache_dtype=cache,
            prism=PrismConfig(degree=2, iterations=3, warm_alpha_iters=1,
                              sketch_dim=8))
        opt = make_optimizer(ocfg, axes)
        state = opt.init(params)
        assert state["leaves"]["w1"]["ortho"].dtype == want, cache
        _, s2 = jax.jit(opt.update)(grads, state, params, 0, key)
        assert s2["leaves"]["w1"]["ortho"].dtype == want, cache
    socfg = OptimizerConfig(name="shampoo", matfn_dtype="bfloat16",
                            max_precond_dim=256,
                            prism=PrismConfig(degree=2, iterations=6,
                                              sketch_dim=8))
    sopt = make_optimizer(socfg, axes)
    sstate = sopt.init(params)
    assert sstate["leaves"]["w1"]["Linv"].dtype == jnp.bfloat16
    _, ss2 = jax.jit(sopt.update)(grads, sstate, params, 0, key)
    assert ss2["leaves"]["w1"]["Linv"].dtype == jnp.bfloat16


def test_bf16_staleness_cache_schedule_invariant(key):
    """Stale steps serve the SAME (cache-rounded) polar the refresh step
    stored — the update direction is schedule-invariant under bf16
    caches, mirroring the fp32 contract of test_sharded_precond."""
    params, axes = _tree(key)
    ocfg = OptimizerConfig(name="muon", learning_rate=0.1,
                           weight_decay=0.0, precond_every=3,
                           matfn_dtype="bfloat16",
                           prism=PrismConfig(degree=2, iterations=3,
                                             warm_alpha_iters=1,
                                             sketch_dim=8))
    opt = make_optimizer(ocfg, axes)
    state = opt.init(params)
    upd = jax.jit(opt.update)
    p, deltas, orthos = params, [], []
    for t in range(3):
        g = jax.tree.map(
            lambda x: jax.random.normal(jax.random.fold_in(key, 50 + t),
                                        x.shape), p)
        p2, state = upd(g, state, p, t, jax.random.fold_in(key, t))
        deltas.append(np.asarray(p["w1"], np.float32)
                      - np.asarray(p2["w1"], np.float32))
        orthos.append(np.asarray(state["leaves"]["w1"]["ortho"]))
        p = p2
    assert np.array_equal(orthos[0], orthos[1])
    assert np.array_equal(orthos[1], orthos[2])
    np.testing.assert_allclose(deltas[0], deltas[1], atol=1e-6)
    np.testing.assert_allclose(deltas[1], deltas[2], atol=1e-6)


def test_launch_counts_dtype_independent(monkeypatch, key):
    """The launch-count contracts are precision-blind (bf16 changes tile
    CONTENTS, never dispatch structure): a fitted PRISM-NS iteration is 2
    launches on the fused tier (§10) and 2+d on the §7 batch-grid tier,
    whether the operands are fp32 or bf16.  The §7 tier is pinned by a
    VMEM budget that holds the sketch chain but no fused iteration."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    for budget, want in ((ops.VMEM_BUDGET, 2), (200_000, 4)):
        monkeypatch.setattr(ops, "VMEM_BUDGET", budget)
        counts = {}
        for dt in ("float32", "bfloat16"):
            cfg = PrismConfig(degree=2, iterations=1, warm_alpha_iters=0,
                              sketch_dim=8, dtype=dt)
            A = jnp.zeros((4, 64, 48), jnp.dtype(dt))
            counts[dt] = ops.count_launches(
                lambda A: matfn.polar(A, method="prism", cfg=cfg, key=key),
                A)
        assert counts["float32"] == counts["bfloat16"] == want, \
            (budget, counts)


def test_precision_policy_validation():
    """Accumulate/fit are pinned fp32 by construction."""
    p = PrismConfig(dtype="bfloat16").precision
    assert (p.compute, p.accumulate, p.fit) == \
        ("bfloat16", "float32", "float32")
    assert p.compute_dtype == jnp.bfloat16
    with pytest.raises(ValueError):
        MatfnPrecision(compute="bfloat16", accumulate="bfloat16")
    with pytest.raises(ValueError):
        MatfnPrecision(fit="bfloat16")
    ocfg = OptimizerConfig(matfn_dtype="bfloat16")
    assert ocfg.resolved_prism.dtype == "bfloat16"
    assert ocfg.cache_dtype == "bfloat16"
    assert OptimizerConfig().resolved_prism.dtype == "float32"
    assert OptimizerConfig(
        matfn_dtype="bfloat16",
        precond_cache_dtype="float32").cache_dtype == "float32"
