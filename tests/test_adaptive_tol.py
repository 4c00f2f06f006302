"""Sketched-residual adaptive early stopping (DESIGN.md §11).

Covers the convergence-certificate engine end to end: the est_r
certificate itself (exact, sketched, pad-corrected), the property that
an early exit never certifies a residual above tol (oracle ||R||_F
checks across families x dtypes x seeded spectra), bitwise stability of
frozen converged slices, tol-/dtype-blindness of the §10 launch
contracts, and the iters_used telemetry surfaced through bucketing into
the Muon/Shampoo state.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import OptimizerConfig, PrismConfig
from repro.core import matfn, prism, sketch
from repro.core import polynomials as poly
from repro.core import random_matrices as rm
from repro.kernels import ops
from repro.optim import bucketing, make_optimizer

pytestmark = pytest.mark.tier1


def _cfg(tol, dtype="float32", iters=14, warm=1, sketch_dim=8, **kw):
    return PrismConfig(degree=2, iterations=iters, warm_alpha_iters=warm,
                       sketch_dim=sketch_dim, dtype=dtype, tol=tol, **kw)


def _polar_residual(A, X):
    """Oracle ||I - X^T X||_F of the polar factor, per batch slice."""
    X = X.astype(jnp.float32)
    if A.shape[-2] < A.shape[-1]:
        X = jnp.swapaxes(X, -1, -2)
    G = jnp.swapaxes(X, -1, -2) @ X
    return jnp.linalg.norm(jnp.eye(X.shape[-1]) - G, axis=(-2, -1))


# ----------------------------------------------------------- the certificate

def test_est_r_exact_traces_equals_fro(key):
    """sketch_dim=0 / key=None: est_r is exactly ||R||_F (t_2 = tr R^2)."""
    R = jax.random.normal(key, (3, 24, 24)) / 24
    R = 0.5 * (R + jnp.swapaxes(R, -1, -2))
    apoly = poly.newton_schulz_residual(2)
    _, est = prism.fit_alpha(R, apoly, 0.375, 1.45, key=None,
                             return_est_r=True)
    np.testing.assert_allclose(np.asarray(est),
                               np.linalg.norm(np.asarray(R), axis=(1, 2)),
                               rtol=1e-5)


def test_est_r_sketched_unbiased(key):
    """The sketched certificate concentrates around ||R||_F (N(0,1/p)
    sketch => E[t_2] = tr R^2)."""
    R = jax.random.normal(key, (16, 16)) / 16
    R = 0.5 * (R + R.T)
    apoly = poly.newton_schulz_residual(2)
    ests = []
    for i in range(64):
        S = sketch.gaussian_sketch(jax.random.fold_in(key, i), 8, 16)
        t = ops.sketch_traces(R, S, poly.max_trace_power(apoly))
        _, est = prism.fit_alpha_from_traces(t, apoly, 0.375, 1.45,
                                             return_est_r=True)
        ests.append(float(est) ** 2)
    true = float(jnp.sum(R * R))
    assert abs(np.mean(ests) - true) < 0.2 * true, (np.mean(ests), true)


def test_est_r_pad_corrected(key):
    """For R_pad = diag(R, I) the n_real correction makes est_r estimate
    the REAL block's norm — the pad block's identity contribution to t_2
    is subtracted exactly (DESIGN.md §7/§11)."""
    n, npad = 20, 32
    R = jax.random.normal(key, (n, n)) / (3 * np.sqrt(n))
    R = 0.5 * (R + R.T)
    Rp = jnp.eye(npad).at[:n, :n].set(R)
    S = sketch.gaussian_sketch(jax.random.fold_in(key, 1), 8, npad)
    apoly = poly.newton_schulz_residual(2)
    t = ops.sketch_traces(Rp, S, poly.max_trace_power(apoly))
    _, est = prism.fit_alpha_from_traces(
        t, apoly, 0.375, 1.45, S=S,
        n_real=jnp.asarray(n, jnp.int32)[None], return_est_r=True)
    t_real = ops.sketch_traces(R, S[:, :n], poly.max_trace_power(apoly))
    _, est_real = prism.fit_alpha_from_traces(t_real, apoly, 0.375, 1.45,
                                              return_est_r=True)
    np.testing.assert_allclose(float(est[0]), float(est_real), rtol=1e-4)


# ----------------------------------- (a) early exit never certifies above tol

def _spectra(key, n):
    """Seeded instance zoo: well-conditioned to near-rank-deficient."""
    return {
        "gaussian": rm.gaussian(key, n, n),
        "log_uniform": rm.log_uniform_spectrum(jax.random.fold_in(key, 1),
                                               n, n, 1e-3),
        "near_rank_def": rm.log_uniform_spectrum(jax.random.fold_in(key, 2),
                                                 n, n, 1e-5),
    }


@pytest.mark.parametrize("dtype,tol,slack", [("float32", 2e-2, 1.3),
                                             ("bfloat16", 0.5, 1.3)])
@pytest.mark.parametrize("spectrum", ["gaussian", "log_uniform",
                                      "near_rank_def"])
def test_polar_certifies_below_tol(key, dtype, tol, slack, spectrum):
    """Certified slices really sit at/below tol (oracle check; the slack
    covers sketch variance at p=8 plus recompute rounding)."""
    A = _spectra(key, 48)[spectrum]
    cfg = _cfg(tol, dtype=dtype)
    X, used = matfn.polar(A, method="prism", cfg=cfg, key=key,
                          return_iters=True)
    res = float(_polar_residual(A, X))
    if int(used) < cfg.iterations:  # certified early => bound must hold
        assert res <= tol * slack, (spectrum, res, int(used))
    # adaptivity is real: the budget is generous enough to certify here
    assert int(used) < cfg.iterations, (spectrum, int(used), res)


def test_polar_exact_certificate_is_exact(key):
    """sketch_dim=0: the certificate IS ||R||_F, so the oracle bound
    holds with no sketch slack, for every slice of a mixed bucket."""
    A = jnp.stack(list(_spectra(key, 48).values()))
    cfg = _cfg(2e-2, sketch_dim=0)
    X, used = matfn.polar(A, method="prism", cfg=cfg, key=None,
                          return_iters=True)
    res = np.asarray(_polar_residual(A, X))
    early = np.asarray(used) < cfg.iterations
    assert early.all(), (np.asarray(used), res)
    np.testing.assert_array_less(res, 2e-2 * 1.02)


@pytest.mark.parametrize("dtype,tol,slack", [("float32", 2e-2, 1.3),
                                             ("bfloat16", 0.5, 1.3)])
def test_sqrtm_certifies_below_tol(key, dtype, tol, slack):
    S = rm.spd_with_eigs(key, 32, jnp.linspace(1e-3, 1.0, 32))
    cfg = _cfg(tol, dtype=dtype)
    (sq, isq), used = matfn.sqrtm(S, method="prism", cfg=cfg, key=key,
                                  return_iters=True)
    # oracle residual of the coupled iteration: ||I - Y X||_F on the
    # normalized problem == ||I - A^{-1/2} A^{1/2}||-style consistency
    c = float(jnp.linalg.norm(S))
    Xn = sq.astype(jnp.float32) / np.sqrt(c)
    Yn = isq.astype(jnp.float32) * np.sqrt(c)
    res = float(jnp.linalg.norm(jnp.eye(32) - Yn @ Xn))
    assert int(used) < cfg.iterations
    assert res <= tol * slack, (res, int(used))


def test_signm_certifies_below_tol(key):
    A = rm.spd_with_eigs(key, 32, jnp.linspace(0.05, 1.0, 32))
    cfg = _cfg(2e-2)
    X, used = matfn.signm(A, method="prism", cfg=cfg, key=key,
                          return_iters=True)
    # sign of SPD is I; oracle residual of the sign iteration is
    # ||I - X^2||_F
    X32 = X.astype(jnp.float32)
    res = float(jnp.linalg.norm(jnp.eye(32) - X32 @ X32))
    assert int(used) < cfg.iterations
    assert res <= 2e-2 * 1.3, (res, int(used))


def test_chebyshev_inv_certifies_below_tol(key):
    B = rm.spd_with_eigs(key, 32, jnp.linspace(0.05, 1.0, 32))
    inv, used = matfn.inv(B, method="prism_chebyshev", iters=40, key=key,
                          tol=1e-3, return_iters=True)
    # residual of the normalized chebyshev iterate: I - (A/c) (c X)
    res = float(jnp.linalg.norm(jnp.eye(32) - B @ inv))
    assert int(used) < 40
    assert res <= 1e-3 * 1.2, (res, int(used))


@pytest.mark.parametrize("p", [1, 2, 4])
def test_inverse_newton_certifies_below_tol(key, p):
    B = rm.spd_with_eigs(key, 32, jnp.linspace(0.05, 1.0, 32))
    out, used = matfn.inv_proot(B, p=p, iters=40, key=key, tol=1e-3,
                                return_iters=True)
    ref = matfn.inv_proot(B, p=p, method="eigh")
    rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
    assert int(used) < 40
    # est_r certifies ||I - M_k||_F = ||I - X^p A / c^p||; the relative
    # error of X against A^{-1/p} is within a small factor of it here
    assert rel <= 5e-3, (p, rel, int(used))


def test_budget_exhaustion_no_certificate(key):
    """An unreachable tol runs the whole budget and never freezes."""
    A = rm.log_uniform_spectrum(key, 32, 32, 1e-4)
    cfg = _cfg(1e-30, iters=4)
    X, used = matfn.polar(A, method="prism", cfg=cfg, key=key,
                          return_iters=True)
    assert int(used) == 4
    assert bool(jnp.all(jnp.isfinite(X)))


def test_instance_adaptive_counts(key):
    """The §11 headline: in one bucket, a well-conditioned instance
    certifies strictly earlier than a near-rank-deficient one."""
    A = jnp.stack([rm.gaussian(key, 48, 48),
                   rm.log_uniform_spectrum(jax.random.fold_in(key, 1),
                                           48, 48, 1e-5)])
    X, used = matfn.polar(A, method="prism", cfg=_cfg(2e-2), key=key,
                          return_iters=True)
    used = np.asarray(used)
    assert used[0] < used[1], used
    np.testing.assert_array_less(np.asarray(_polar_residual(A, X)),
                                 2e-2 * 1.3)


# ----------------------------------------- (b) frozen slices: bitwise-stable

def test_frozen_slice_bitwise_stable_polar(key):
    """Once a slice certifies, later loop iterations (driven by the
    bucket's stragglers) must not touch it: truncating the budget right
    after the fast slice freezes yields the BITWISE-identical output."""
    A = jnp.stack([rm.gaussian(key, 48, 48),
                   rm.log_uniform_spectrum(jax.random.fold_in(key, 1),
                                           48, 48, 1e-5)])
    X_full, used = matfn.polar(A, method="prism", cfg=_cfg(2e-2, iters=14),
                               key=key, return_iters=True)
    used = np.asarray(used)
    assert used[0] < used[1] <= 14
    # budget cut to just past the fast slice's certificate: the fast
    # slice's frozen iterate must be unchanged bit for bit
    cut = int(used[0]) + 1
    X_cut, used_cut = matfn.polar(A, method="prism",
                                  cfg=_cfg(2e-2, iters=cut), key=key,
                                  return_iters=True)
    assert int(np.asarray(used_cut)[0]) == int(used[0])
    np.testing.assert_array_equal(np.asarray(X_full[0]),
                                  np.asarray(X_cut[0]))


def test_frozen_slice_bitwise_stable_chebyshev(key):
    Bs = jnp.stack([rm.spd_with_eigs(key, 32, jnp.linspace(0.3, 1.0, 32)),
                    rm.spd_with_eigs(jax.random.fold_in(key, 1), 32,
                                     jnp.linspace(0.01, 1.0, 32))])
    inv_full, used = matfn.inv(Bs, iters=40, key=key, tol=1e-3,
                               return_iters=True)
    used = np.asarray(used)
    assert used[0] < used[1] <= 40
    cut = int(used[0]) + 1
    inv_cut, _ = matfn.inv(Bs, iters=cut, key=key, tol=1e-3,
                           return_iters=True)
    np.testing.assert_array_equal(np.asarray(inv_full[0]),
                                  np.asarray(inv_cut[0]))


def test_tol_none_bit_matches_pre_adaptive(key):
    """tol=None runs the static unrolled chains — and an adaptive run
    whose tol never certifies applies the identical sequence of updates
    (same per-iteration sketch keys, same alphas)."""
    A = rm.log_uniform_spectrum(key, 32, 32, 1e-4)
    X_static = matfn.polar(A, method="prism", cfg=_cfg(None, iters=4),
                           key=key)
    X_adapt, used = matfn.polar(A, method="prism",
                                cfg=_cfg(1e-30, iters=4), key=key,
                                return_iters=True)
    assert int(used) == 4
    # the unrolled chain and the while_loop body compile to different XLA
    # programs, which may associate each fp32 GEMM and epilogue
    # differently.  Per iteration an entry of the iterate (|x| <= 1 after
    # Frobenius normalization) passes through 4 rounded ops — residual
    # GEMM, Horner GEMM, alpha scale, apply GEMM — each worth <= 1 ulp,
    # and the cubic NS map's slope is <= 2 on [0, 1]: <= 8 eps per
    # iteration, accumulated over the 4 iterations.
    atol = 4 * 8 * float(np.finfo(np.float32).eps)
    np.testing.assert_allclose(np.asarray(X_static), np.asarray(X_adapt),
                               rtol=0, atol=atol)


# -------------------------- (c) launch contracts: tol- and dtype-blind (§10)

def _count(fn, *args):
    return ops.count_launches(fn, *args)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_launch_contract_adaptive_fused(monkeypatch, key, dtype):
    """Fused tier with an adaptive tol: ONE warm-tail launch plus the
    2-launch fitted body traced ONCE inside the while_loop — the §10
    per-iteration contract (fitted <= 2, warm tail == 1) is intact and
    the traced count is independent of B, budget, dtype and tol."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    for iters in (2, 5):
        for B in (1, 4):
            cfg = _cfg(1e-2, dtype=dtype, iters=iters, warm=1)
            n = _count(lambda A: matfn.polar(A, method="prism", cfg=cfg,
                                             key=key),
                       jnp.zeros((B, 64, 48), jnp.dtype(dtype)))
            assert n == 1 + 2, (iters, B, n)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_launch_contract_adaptive_unfused(monkeypatch, key, dtype):
    """§7 batch-grid tier (under a VMEM budget that holds the sketch
    chain but no fused iteration): warm tail 1+d launches, fitted body
    2+d traced once — tol- and dtype-blind."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    monkeypatch.setattr(ops, "VMEM_BUDGET", 200_000)
    d = 2
    for iters in (2, 5):
        cfg = _cfg(1e-2, dtype=dtype, iters=iters, warm=1)
        n = _count(lambda A: matfn.polar(A, method="prism", cfg=cfg,
                                         key=key),
                   jnp.zeros((4, 64, 48), jnp.dtype(dtype)))
        assert n == (1 + d) + (2 + d), (iters, n)


def test_launch_count_tol_blind(monkeypatch, key):
    """Same budget, tol on vs off: the §10 static plan issues 2 launches
    PER fitted iteration, the adaptive plan traces the body once — so
    the adaptive TRACED count never exceeds the static one, and both
    keep the per-iteration contract."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")

    def n_launches(tol):
        cfg = _cfg(tol, iters=3, warm=1)
        return _count(lambda A: matfn.polar(A, method="prism", cfg=cfg,
                                            key=key),
                      jnp.zeros((4, 64, 64)))

    assert n_launches(None) == 1 + 2 * 2   # warm tail + 2 static fits
    assert n_launches(1e-2) == 1 + 2       # warm tail + while body once


# ------------------------------------------------- telemetry: bucket + state

def test_polar_bucketed_with_iters(key):
    views = [jax.random.normal(jax.random.fold_in(key, i), s)
             for i, s in enumerate([(48, 32), (48, 32), (2, 64, 64)])]
    ocfg = OptimizerConfig(prism=_cfg(2e-2), matfn_tol=2e-2)
    outs, iters, statuses = bucketing.polar_bucketed(views, ocfg, key,
                                           with_iters=True)
    assert [i.shape for i in iters] == [(), (), (2,)]
    for v, o, it in zip(views, outs, iters):
        assert o.shape == v.shape
        assert int(np.max(np.asarray(it))) <= 14
        assert int(np.min(np.asarray(it))) >= 1


def test_polar_bucketed_padded_adaptive(key):
    """Pad-to-bucket + adaptive: certificates are pad-blind (n_real
    corrected), so real blocks still converge below tol."""
    views = [jax.random.normal(jax.random.fold_in(key, i), s)
             for i, s in enumerate([(64, 64), (64, 56)])]
    ocfg = OptimizerConfig(prism=_cfg(2e-2, iters=16, warm=2),
                           matfn_tol=2e-2, bucket_pad=True)
    outs, iters, statuses = bucketing.polar_bucketed(views, ocfg, key,
                                           with_iters=True)
    for v, o, it in zip(views, outs, iters):
        ref = matfn.polar(v, method="svd")
        err = float(jnp.linalg.norm(o - ref) / jnp.linalg.norm(ref))
        assert err < 5e-2, (v.shape, err, int(it))
        assert 1 <= int(it) <= 16


def test_muon_state_iters_telemetry(key):
    params = {"w1": jax.random.normal(key, (64, 32)),
              "w3": jax.random.normal(jax.random.fold_in(key, 2),
                                      (3, 48, 32)),
              "b": jax.random.normal(jax.random.fold_in(key, 4), (64,))}
    axes = {"w1": ("embed", "mlp"), "w3": ("layers", "embed", "mlp"),
            "b": ("embed",)}
    grads = jax.tree.map(
        lambda p: jax.random.normal(jax.random.fold_in(key, 9), p.shape),
        params)
    ocfg = OptimizerConfig(name="muon", matfn_tol=1e-2,
                           prism=_cfg(None, iters=10), precond_every=2)
    assert ocfg.matfn_telemetry
    opt = make_optimizer(ocfg, axes)
    state = opt.init(params)
    assert state["leaves"]["w1"]["iters"].shape == ()
    assert state["leaves"]["w3"]["iters"].shape == (3,)
    assert "iters" not in state["leaves"]["b"]
    _, s1 = jax.jit(opt.update)(grads, state, params, 0, key)
    it1 = np.asarray(s1["leaves"]["w3"]["iters"])
    assert (1 <= it1).all() and (it1 <= 10).all(), it1
    # stale step (count=1, precond_every=2): telemetry carried untouched
    _, s2 = jax.jit(opt.update)(grads, s1, params, 1, key)
    np.testing.assert_array_equal(np.asarray(s2["leaves"]["w3"]["iters"]),
                                  it1)


def test_shampoo_state_iters_telemetry(key):
    params = {"w1": jax.random.normal(key, (64, 32))}
    axes = {"w1": ("embed", "mlp")}
    grads = jax.tree.map(
        lambda p: jax.random.normal(jax.random.fold_in(key, 9), p.shape),
        params)
    ocfg = OptimizerConfig(name="shampoo", matfn_tol=1e-2,
                           prism=_cfg(None, iters=12), max_precond_dim=512)
    opt = make_optimizer(ocfg, axes)
    state = opt.init(params)
    assert state["leaves"]["w1"]["Linv_iters"].shape == ()
    _, s1 = jax.jit(opt.update)(grads, state, params, 0, key)
    for side in ("Linv_iters", "Rinv_iters"):
        it = int(s1["leaves"]["w1"][side])
        assert 1 <= it <= 12, (side, it)


def test_baseline_methods_telemetry_contract(key):
    """Fit-free methods honor return_iters uniformly (zeros — they
    certify nothing) instead of mis-unpacking or raising, and reject
    return_info (no iteration trajectory) loudly."""
    A = rm.spd_with_eigs(key, 16, jnp.linspace(0.1, 1.0, 16))
    ref = matfn.inv_sqrtm(A, method="eigh")
    out, it = matfn.inv_sqrtm(A, method="eigh", return_iters=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert out.shape == (16, 16) and int(it) == 0
    for fn, m in [(matfn.polar, "svd"), (matfn.signm, "eigh"),
                  (matfn.inv_proot, "eigh")]:
        args = (A, 2) if fn is matfn.inv_proot else (A,)
        o, it = fn(*args, method=m, return_iters=True)
        assert o.shape == (16, 16) and int(it) == 0, (m, o.shape)
        with pytest.raises(ValueError):
            fn(*args, method=m, return_info=True)
    o, it = matfn.inv(A, method="solve", return_iters=True)
    assert int(it) == 0
    (sq, isq), it = matfn.sqrtm(A, method="newton", return_iters=True)
    assert sq.shape == (16, 16) and int(it) == 0
    # fixed-schedule families flatten the combo to (out, info, iters)
    isq2, info, it2 = matfn.inv_sqrtm(A, method="polar_express",
                                      return_info=True, return_iters=True)
    assert isq2.shape == (16, 16) and it2.shape == () and int(it2) == 0
    X, _, it3 = matfn.polar(A, method="polar_express", return_info=True,
                            return_iters=True)
    assert X.shape == (16, 16) and int(it3) == 0


def test_no_telemetry_without_tol(key):
    ocfg = OptimizerConfig(name="muon", prism=_cfg(None))
    assert not ocfg.matfn_telemetry
    params = {"w1": jax.random.normal(key, (32, 16))}
    opt = make_optimizer(ocfg, {"w1": ("embed", "mlp")})
    assert "iters" not in opt.init(params)["leaves"]["w1"]
