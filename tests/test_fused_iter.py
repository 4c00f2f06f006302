"""Fused single-launch iteration tier (DESIGN.md §10).

Covers: seeded interpret-mode fuzz of the fused kernels against the
ref.py oracles over non-divisible (B, m, n) shapes x {fp32,
bf16-in/fp32-accum}; the launch-count contract (<= 2 launches per fitted
iteration, exactly 1 launch for a whole warm tail, independent of B, d,
warm length and dtype); fused-vs-unfused numerics at the dtype-principled
tolerances of tests/test_precision.py; the trace-time VMEM-budget tier
choice; the fp32-alpha epilogue invariant; and the sketch-chain VMEM
guard's per-step fallback.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import OptimizerConfig, PrismConfig
from repro.core import matfn
from repro.core import newton_schulz as ns
from repro.kernels import fused_iter as fi
from repro.kernels import ref
from repro.optim import bucketing

pytestmark = pytest.mark.tier1

DTYPES = [jnp.float32, jnp.bfloat16]
U_BF16 = 2.0 ** -8

# deterministic fuzz corpus: non-divisible (B, m, n) drawn once at import
# so every CI run sweeps the same shapes (rerunnable failures)
_FUZZ_RNG = np.random.default_rng(7)
FUZZ_SHAPES = [tuple(int(x) for x in (_FUZZ_RNG.integers(1, 4),
                                      _FUZZ_RNG.integers(8, 90),
                                      _FUZZ_RNG.integers(4, 70)))
               for _ in range(5)]


def _tol(dtype):
    return dict(rtol=2e-4, atol=2e-4) if dtype == jnp.float32 else \
        dict(rtol=5e-2, atol=5e-2)


def _coeffs(degree):
    from repro.core import polynomials as poly

    return tuple(float(c) for c in poly.taylor_inv_sqrt(degree - 1))


def _st(S, dtype):
    p = S.shape[0]
    return jnp.pad(S.T.astype(dtype), ((0, 0), (0, (-p) % 128)))


def _sym(key, B, n, dtype, scale=8.0):
    X = jax.random.normal(key, (B, n, n)) / scale
    return (0.5 * (X + jnp.swapaxes(X, -1, -2))).astype(dtype)


# ------------------------------------------------------------- kernel fuzz


@pytest.mark.parametrize("B,m,n", FUZZ_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fuzz_residual_chain_polar(key, B, m, n, dtype):
    """Fused residual+chain == ref oracle on non-divisible shapes."""
    kx, ks = jax.random.split(key)
    m, n = max(m, n), min(m, n)  # polar orientation
    X = (jax.random.normal(kx, (B, m, n)) / np.sqrt(m)).astype(dtype)
    p = 1 + n % 8
    S = (jax.random.normal(ks, (p, n)) / np.sqrt(p)).astype(dtype)
    R, t = fi.residual_chain(X, _st(S, dtype), 6, family="polar",
                             interpret=True)
    Rr, tr = ref.residual_chain(X, S, 6, family="polar")
    np.testing.assert_allclose(np.asarray(R, np.float32),
                               np.asarray(Rr, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(t), np.asarray(tr), **_tol(dtype))


@pytest.mark.parametrize("B,m,n", FUZZ_SHAPES[:3])
@pytest.mark.parametrize("family", ["sign", "sqrt"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_fuzz_residual_chain_square(key, B, m, n, family, dtype):
    del m  # square families
    kx, ks = jax.random.split(key)
    X = _sym(kx, B, n, dtype)
    Y = jnp.broadcast_to(jnp.eye(n, dtype=dtype), X.shape) \
        if family == "sqrt" else None
    S = (jax.random.normal(ks, (8, n)) / np.sqrt(8)).astype(dtype)
    R, t = fi.residual_chain(X, _st(S, dtype), 5, family=family, Y=Y,
                             interpret=True)
    Rr, tr = ref.residual_chain(X, S, 5, family=family, Y=Y)
    np.testing.assert_allclose(np.asarray(R, np.float32),
                               np.asarray(Rr, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(t), np.asarray(tr), **_tol(dtype))


@pytest.mark.parametrize("B,m,n", FUZZ_SHAPES)
@pytest.mark.parametrize("degree", [1, 2])
@pytest.mark.parametrize("dtype", DTYPES)
def test_fuzz_apply_g(key, B, m, n, degree, dtype):
    """Fused Horner == oracle; per-slice fp32 alphas enter unrounded."""
    kx, ka = jax.random.split(key)
    m, n = max(m, n), min(m, n)
    X = (jax.random.normal(kx, (B, m, n)) / np.sqrt(m)).astype(dtype)
    R = ref._residual(X, family="polar")
    a = jax.random.uniform(ka, (B,), jnp.float32, 0.4, 1.45)
    got = fi.apply_g(X, R, a, coeffs=_coeffs(degree), interpret=True)
    want = ref.apply_g(X, R, a, coeffs=_coeffs(degree))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", DTYPES)
def test_fuzz_apply_g_coupled(key, dtype):
    kx, ka = jax.random.split(key)
    X = _sym(kx, 2, 45, dtype)
    Y = _sym(jax.random.fold_in(kx, 1), 2, 45, dtype)
    R = ref._residual(X, Y, family="sqrt")
    a = jax.random.uniform(ka, (2,), jnp.float32, 0.4, 1.45)
    gx, gy = fi.apply_g(X, R, a, coeffs=_coeffs(2), Y=Y, interpret=True)
    wx, wy = ref.apply_g(X, R, a, coeffs=_coeffs(2), Y=Y)
    np.testing.assert_allclose(np.asarray(gx, np.float32),
                               np.asarray(wx, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(gy, np.float32),
                               np.asarray(wy, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,m,n", FUZZ_SHAPES[:3])
@pytest.mark.parametrize("family", ["polar", "sign", "sqrt"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_fuzz_warm_tail(key, B, m, n, family, dtype):
    """One multi-iteration launch == the per-iteration oracle loop."""
    alphas = (1.45, 1.2, 0.9)
    if family == "polar":
        m, n = max(m, n), min(m, n)
        # Frobenius-normalized like the chains' X_0: every singular value
        # <= 1 keeps the (1.45, 1.2, 0.9) schedule convergent (randn /
        # sqrt(m) reaches sigma ~ 2 on square shapes, where NS diverges)
        X = jax.random.normal(key, (B, m, n))
        X = (X / jnp.linalg.norm(X, axis=(-2, -1), keepdims=True)
             ).astype(dtype)
        Y = None
    else:
        X = _sym(key, B, n, dtype, scale=2 * np.sqrt(n))
        if family == "sqrt":
            X = (jnp.matmul(X, jnp.swapaxes(X, -1, -2),
                            preferred_element_type=jnp.float32)
                 + 0.4 * jnp.eye(n)).astype(dtype)
            Y = jnp.broadcast_to(jnp.eye(n, dtype=dtype), X.shape)
        else:
            Y = None
    arr = jnp.asarray(alphas, jnp.float32)
    got = fi.warm_tail(X, arr, len(alphas), family=family,
                       coeffs=_coeffs(2), Y=Y, interpret=True)
    want = ref.warm_tail(X, alphas, coeffs=_coeffs(2), family=family, Y=Y)
    if family == "sqrt":
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g, np.float32),
                                       np.asarray(w, np.float32),
                                       **_tol(dtype))
    else:
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **_tol(dtype))


# ------------------------------------------------- launch-count contracts


def _count(fn, *args):
    from repro.kernels import ops

    return ops.count_launches(fn, *args)


@pytest.mark.parametrize("degree", [1, 2])
@pytest.mark.parametrize("B", [1, 4, 16])
def test_fitted_iteration_two_launches(monkeypatch, key, degree, B):
    """The §10 contract: a fitted iteration is <= 2 launches — fused
    residual+chain, then the fused Horner — independent of B AND d (the
    §7 tier still scaled as 2+d)."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    cfg = PrismConfig(degree=degree, iterations=1, warm_alpha_iters=0,
                      sketch_dim=8)
    A = jnp.zeros((B, 64, 48))
    n = _count(lambda A: matfn.polar(A, method="prism", cfg=cfg, key=key),
               A)
    assert n == 2, (degree, B, n)


@pytest.mark.parametrize("warm", [1, 3, 6])
@pytest.mark.parametrize("degree", [1, 2])
def test_warm_tail_single_launch(monkeypatch, key, warm, degree):
    """The whole warm tail is EXACTLY one launch, independent of its
    length, of d, and of B."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    cfg = PrismConfig(degree=degree, iterations=warm,
                      warm_alpha_iters=warm, sketch_dim=8)
    for B in (1, 8):
        A = jnp.zeros((B, 64, 48))
        n = _count(lambda A: matfn.polar(A, method="prism", cfg=cfg,
                                         key=key), A)
        assert n == 1, (warm, degree, B, n)


def test_whole_call_launches(monkeypatch, key):
    """warm run + fitted tail: 1 + 2 * n_fitted launches; a classical
    (constant-alpha) chain is ONE launch end to end."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    A = jnp.zeros((4, 64, 64))
    cfg = PrismConfig(degree=2, iterations=5, warm_alpha_iters=2,
                      sketch_dim=8)
    n = _count(lambda A: matfn.polar(A, method="prism", cfg=cfg, key=key),
               A)
    assert n == 1 + 2 * 3, n
    ccfg = PrismConfig(degree=2, iterations=8)
    n = _count(lambda A: matfn.polar(A, method="newton_schulz", cfg=ccfg),
               A)
    assert n == 1, n


def test_launches_dtype_blind(monkeypatch, key):
    """bf16 changes tile contents, never the fused dispatch structure."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    counts = {}
    for dt in ("float32", "bfloat16"):
        cfg = PrismConfig(degree=2, iterations=4, warm_alpha_iters=2,
                          sketch_dim=8, dtype=dt)
        A = jnp.zeros((3, 64, 48), jnp.dtype(dt))
        counts[dt] = _count(
            lambda A: matfn.polar(A, method="prism", cfg=cfg, key=key), A)
    assert counts["float32"] == counts["bfloat16"] == 1 + 2 * 2, counts


def test_coupled_sqrt_launch_contract(monkeypatch, key):
    """The coupled family fuses both Horner applications into the second
    launch: fitted <= 2, warm tail == 1 (Shampoo's inverse-root path)."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    A = jnp.zeros((2, 48, 48)) + jnp.eye(48)
    cfg = PrismConfig(degree=2, iterations=4, warm_alpha_iters=2,
                      sketch_dim=8)
    n = _count(lambda A: matfn.sqrtm(A, cfg=cfg, key=key)[1], A)
    assert n == 1 + 2 * 2, n


# ------------------------------------------------------ tier + numerics


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_vs_unfused_polar(monkeypatch, key, dtype):
    """Fused and unfused tiers agree at the dtype-principled tolerances
    of tests/test_precision.py (fp32 fp-tight; bf16 O(u_bf16 kappa)).
    A zero VMEM budget takes the unfused tier at the same shape."""
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    A = jax.random.normal(key, (3, 72, 40)).astype(dtype)
    cfg = PrismConfig(degree=2, iterations=8, warm_alpha_iters=2,
                      sketch_dim=8, dtype=dtype)
    outs = {}
    for tier, budget in (("on", ops.VMEM_BUDGET), ("off", 0)):
        monkeypatch.setattr(ops, "VMEM_BUDGET", budget)
        outs[tier] = np.asarray(
            matfn.polar(A, method="prism", cfg=cfg, key=key), np.float32)
    err = np.linalg.norm(outs["on"] - outs["off"]) / \
        np.linalg.norm(outs["off"])
    assert err < (1e-5 if dtype == "float32" else 16 * U_BF16), err


def test_fused_pad_to_bucket_invariance(key):
    """The fused tier composes with §7 pad-to-bucket: the n_real trace
    correction flows through the fused chain's traces unchanged."""
    views = [jax.random.normal(jax.random.fold_in(key, i), s)
             for i, s in enumerate([(64, 64), (64, 56)])]
    ocfg = OptimizerConfig(prism=PrismConfig(degree=2, iterations=10,
                                             warm_alpha_iters=2,
                                             sketch_dim=8),
                           bucket_pad=True)
    outs = bucketing.polar_bucketed(views, ocfg, key)
    for v, o in zip(views, outs):
        want = matfn.polar(v, method="svd")
        np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                                   rtol=5e-3, atol=5e-3)


def test_tier_resolution_budget(monkeypatch):
    """fused_fits is a pure trace-time shape test against VMEM_BUDGET:
    B never enters, a tiny budget forces the §7 tier, and the planner's
    tier telemetry reads the same test for either orientation."""
    from repro.kernels import ops

    assert ops.fused_fits((64, 48), jnp.float32)
    assert not ops.fused_fits((4096, 4096), jnp.float32)
    b = bucketing.plan_buckets([(64, 48), (64, 48)])[0]
    cfg = OptimizerConfig()
    assert bucketing.resolve_tier(cfg, b.shape) == "fused"
    assert bucketing.resolve_tier(cfg, (48, 64)) == "fused"
    monkeypatch.setattr(ops, "VMEM_BUDGET", 1024)
    assert not ops.fused_fits((64, 48), jnp.float32)
    assert bucketing.resolve_tier(cfg, b.shape) == "grid"
    assert bucketing.resolve_tier(cfg, (48, 64)) == "grid"


def test_tier_auto_switches_launch_structure(monkeypatch, key):
    """Under a tiny budget the chain falls back to the §7 per-launch tier
    (2+d per fitted iteration); under the default budget it fuses."""
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    default = ops.VMEM_BUDGET
    cfg = PrismConfig(degree=2, iterations=1, warm_alpha_iters=0,
                      sketch_dim=8)
    A = jnp.zeros((2, 64, 48))

    def n_launches():
        return _count(
            lambda A: matfn.polar(A, method="prism", cfg=cfg, key=key), A)

    assert n_launches() == 2
    # 200 KB: the whole-chain kernel still fits (so the §7 tier keeps its
    # single-launch chain) but the fused iteration working set does not
    monkeypatch.setattr(ops, "VMEM_BUDGET", 200000)
    assert n_launches() == 2 + 2
    # 4 KB: the chain guard trips too — per-step fallback, max_power
    # launches for the chain alone (still bounded VMEM, never over budget)
    monkeypatch.setattr(ops, "VMEM_BUDGET", 4096)
    assert n_launches() == 1 + 10 + 2
    monkeypatch.setattr(ops, "VMEM_BUDGET", default)
    assert n_launches() == 2


# ------------------------------------------------ fp32 alpha epilogue (§9)


def test_alpha_enters_fp32(key):
    """The fitted fp32 alpha reaches the update unrounded: pre-rounding
    it to bf16 (the old `jnp.asarray(alpha, X.dtype)`) visibly changes
    the result, and the unfused path matches the fused oracle for d=1 (where
    the two accumulation orders coincide bit for bit)."""
    X = (jax.random.normal(key, (40, 32)) / 6).astype(jnp.bfloat16)
    R = ref._residual(X, family="polar")
    a = 4.0 / 3.0  # bf16 rounds it half an ulp away: a*X rounds visibly
    a16 = float(jnp.asarray(a, jnp.bfloat16))
    assert a16 != a
    got = ns.apply_g(X, R, a, 1, "right")
    pre_rounded = ns.apply_g(X, R, a16, 1, "right")
    oracle = ref.apply_g(X, R, a, coeffs=_coeffs(1))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(oracle, np.float32))
    assert not np.array_equal(np.asarray(got, np.float32),
                              np.asarray(pre_rounded, np.float32))


def test_alpha_fp32_noop_for_fp32(key):
    """For fp32 compute the fix is a no-op: alpha was already fp32."""
    X = jax.random.normal(key, (24, 16)) / 5
    R = ref._residual(X, family="polar")
    got = ns.apply_g(X, R, 0.87654321, 2, "right")
    want = ref.apply_g(X, R, 0.87654321, coeffs=_coeffs(2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


# --------------------------------------------- sketch-chain VMEM guard


def test_sketch_chain_vmem_guard(monkeypatch, key):
    """Over-budget chains fall back to the bounded per-step sketch_step
    loop — max_power launches instead of one, identical numerics."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    from repro.kernels import ops

    R = _sym(key, 2, 64, jnp.float32)
    S = jax.random.normal(jax.random.fold_in(key, 1), (8, 64)) / np.sqrt(8)
    maxp = 6
    want = ref.sketch_traces(R, S, maxp)

    assert _count(lambda R, S: ops.sketch_traces(R, S, maxp), R, S) == 1
    got = ops.sketch_traces(R, S, maxp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)

    monkeypatch.setattr(ops, "VMEM_BUDGET", 4096)  # chain needs ~100KB
    assert _count(lambda R, S: ops.sketch_traces(R, S, maxp), R, S) == maxp
    got = ops.sketch_traces(R, S, maxp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)

    # a fitted iteration's chain obeys the same guard: under the tiny
    # budget it runs unfused with the per-step chain — gram + max_power
    # steps + the Gram side's 2 launches
    cfg = PrismConfig(degree=2, iterations=1, warm_alpha_iters=0,
                      sketch_dim=8)
    n = _count(lambda A: matfn.polar(A, method="prism", cfg=cfg, key=key),
               jnp.zeros((2, 64, 48)))
    assert n == 1 + 10 + 2, n


def test_batched_sketch_step(key):
    """sketch_step grew the §7 batch grid: [B, n, p] chains in one
    launch per step, matching the 2-D contract per slice."""
    from repro.kernels import sketch_traces as sk_kernel

    R = _sym(key, 3, 40, jnp.float32)
    S = jax.random.normal(jax.random.fold_in(key, 1), (8, 40)) / np.sqrt(8)
    St = _st(S, jnp.float32)
    V = jnp.broadcast_to(St, (3,) + St.shape)
    Vb, tb = sk_kernel.sketch_step(R, V, St, bm=32, bk=32, interpret=True)
    for b in range(3):
        v2, t2 = sk_kernel.sketch_step(R[b], St, St, bm=32, bk=32,
                                       interpret=True)
        np.testing.assert_allclose(np.asarray(Vb[b]), np.asarray(v2),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(float(tb[b]), float(t2), rtol=2e-5)
