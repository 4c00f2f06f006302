"""The polar update on the Gram side (DESIGN.md §7).

``newton_schulz.apply_g`` forms Q = alpha R^d + ... + f_1 R as an
[n, n] matrix with the upper-block power product and applies it in one
product X Q + f_0 X, for the polar family at d >= 2; d = 1 and the sign
and coupled sqrt families keep Horner on the iterate.  Covers: the
power-product kernel (interpret mode) against its oracle, exact symmetry
of its mirrored output, the Gram-side order against the Horner oracle
on both paths, pad invariance of padded Gram sides, the trace-time
order counter, and the launch contract (1 + d launches per unfused warm
iteration; 19 for gpt2-paper's Muon buckets, 64 for Moonlight-16B-A3B's).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import OptimizerConfig, PrismConfig
from repro.configs import get_config
from repro.core import matfn
from repro.core import newton_schulz as ns
from repro.core import polynomials as poly
from repro.kernels import gram as gram_kernel
from repro.kernels import ops, ref
from repro.models import build
from repro.optim import bucketing
from repro.optim.muon import matrix_view_shapes

pytestmark = pytest.mark.tier1

U_BF16 = 2.0 ** -8
DTYPES = [jnp.float32, jnp.bfloat16]


def _coeffs(degree):
    return tuple(float(c) for c in poly.taylor_inv_sqrt(degree - 1))


def _sym(key, B, n):
    R = jax.random.normal(key, (B, n, n)) / np.sqrt(n)
    return 0.5 * (R + jnp.swapaxes(R, -1, -2))


def _fro_rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


# ------------------------------------------------- the power-product kernel


@pytest.mark.parametrize("B,n,bn", [(1, 64, 32), (3, 176, 128),
                                    (2, 96, 64)])
@pytest.mark.parametrize("with_c", [True, False])
@pytest.mark.parametrize("dtype", DTYPES)
def test_matmul_add_upper(key, B, n, bn, with_c, dtype):
    """alpha_b R Q + beta R on the upper block triangle, mirrored, equals
    the full product (n = 176 pads to two 128 tiles, as 1408 pads to six
    of 256), with a different fp32 alpha per slice; the mirrored result
    is symmetric bit for bit."""
    R = _sym(key, B, n)
    Q = 0.75 * jnp.matmul(R, R) - 0.5 * R  # a polynomial in R
    R, Q = R.astype(dtype), Q.astype(dtype)
    alpha = jnp.linspace(0.6, 1.9, B, dtype=jnp.float32)
    C = R if with_c else None
    U = gram_kernel.matmul_add_upper(R, Q, alpha, C, beta=0.375, bn=bn,
                                     bk=bn, interpret=True)
    got = np.asarray(gram_kernel.mirror_upper(U), np.float32)
    want = np.asarray(ref.matmul_add(R, Q, C, alpha=alpha[:, None, None],
                                     beta=0.375), np.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    np.testing.assert_array_equal(got, np.swapaxes(got, -1, -2))


def test_sym_matmul_add_dispatch(monkeypatch, key):
    """ops.sym_matmul_add: interpret kernel (leading dims collapsed, a
    scalar alpha broadcast) == the ref oracle, in one launch."""
    R = _sym(key, 6, 48).reshape(2, 3, 48, 48)
    want = ops.sym_matmul_add(R, R, R, alpha=1.25, beta=-0.5)
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    got = ops.sym_matmul_add(R, R, R, alpha=1.25, beta=-0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert ops.count_launches(
        lambda r: ops.sym_matmul_add(r, r, r, alpha=1.25, beta=-0.5),
        R) == 1


# ------------------------------------------- the Gram-side order, apply_g


def _iterate(key, layout, dtype):
    """A normalized polar iterate: tall, square, or a wide matrix
    transposed to the tall orientation polar iterates on."""
    shape = {"tall": (2, 48, 32), "square": (2, 32, 32),
             "transposed": (2, 24, 40)}[layout]
    A = jax.random.normal(key, shape)
    if layout == "transposed":
        A = jnp.swapaxes(A, -1, -2)
    A = A / jnp.linalg.norm(A, axis=(-2, -1), keepdims=True)
    return A.astype(dtype)


@pytest.mark.parametrize("mode", ["ref", "interpret"])
@pytest.mark.parametrize("layout", ["tall", "square", "transposed"])
@pytest.mark.parametrize("d", [2, 3])
@pytest.mark.parametrize("dtype", DTYPES)
def test_gram_side_matches_horner(monkeypatch, key, mode, layout, d, dtype):
    """X Q + f_0 X == the Horner oracle X g_d(R; alpha), per-slice fp32
    alpha: to fp32 rounding at fp32, to O(u_bf16) at bf16 (polar's
    kappa ~ 1 on a normalized iterate), with the products run by the
    oracles or by the kernel bodies."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", mode)
    X = _iterate(key, layout, dtype)
    R = ops.gram(X)
    alpha = jnp.asarray([0.87654321, 1.2345678], jnp.float32)
    before = ns.path_counts.copy()
    got = ns.apply_g(X, R, alpha, d, "right")
    assert ns.path_counts - before == {"gram_side": 1}
    want = ref.apply_g(X, R, alpha, coeffs=_coeffs(d))
    assert got.dtype == dtype
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-6, atol=2e-6)
    else:
        assert _fro_rel(got, want) < 2 * U_BF16


@pytest.mark.parametrize("mode", ["ref", "interpret"])
@pytest.mark.parametrize("wide", [False, True])
def test_polar_padded_gram_side_unchanged(monkeypatch, key, mode, wide):
    """Zero-padding the Gram side (n_real) leaves the real block's polar
    factor as the unpadded call computes it: the pad block of Q is a
    multiple of I and the pad columns of X stay zero.  A zero VMEM
    budget pins the unfused tier, where the Gram side runs."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", mode)
    monkeypatch.setattr(ops, "VMEM_BUDGET", 0)
    cfg = PrismConfig(degree=2, iterations=4, warm_alpha_iters=1,
                      sketch_dim=0)
    A = jax.random.normal(key, (2, 24, 40) if wide else (2, 40, 24))
    pad = ((0, 0), (0, 8), (0, 0)) if wide else ((0, 0), (0, 0), (0, 8))
    n_real = jnp.full((2,), 24, jnp.int32)
    full = np.asarray(matfn.polar(jnp.pad(A, pad), cfg=cfg, n_real=n_real))
    want = matfn.polar(A, cfg=cfg)
    got, rest = (full[:, :24], full[:, 24:]) if wide else \
        (full[..., :24], full[..., 24:])
    np.testing.assert_allclose(got, np.asarray(want), rtol=5e-5, atol=5e-5)
    assert not np.any(rest)


# ---------------------------------------------------- the order counter


@pytest.mark.parametrize("fn,degree,want", [
    ("polar", 2, {"gram_side": 3}),
    ("polar", 3, {"gram_side": 3}),
    ("polar", 1, {"horner": 3}),
    ("signm", 2, {"horner": 3}),
    ("sqrtm", 2, {"horner": 6}),   # X and Y each iteration
])
def test_polar_apply_counter(monkeypatch, key, fn, degree, want):
    """Which order each unfused update took, counted at trace time
    (classical Taylor alpha: PRISM's warm bounds stop at d = 2); a zero
    VMEM budget pins the unfused tier at this small shape."""
    monkeypatch.setattr(ops, "VMEM_BUDGET", 0)
    cfg = PrismConfig(degree=degree, iterations=3)
    A = _sym(key, 2, 32) + 2 * jnp.eye(32)
    before = ns.path_counts.copy()
    jax.eval_shape(lambda a: getattr(matfn, fn)(
        a, cfg=cfg, method="newton_schulz"), A)
    assert dict(ns.path_counts - before) == want


def test_fused_tier_is_not_counted(key):
    """The fused tier keeps its VMEM-resident Horner and bypasses
    apply_g altogether."""
    cfg = PrismConfig(degree=2, iterations=3, warm_alpha_iters=3)
    assert ops.fused_fits((64, 16), jnp.dtype("float32"))
    before = ns.path_counts.copy()
    jax.eval_shape(lambda a: matfn.polar(a, cfg=cfg),
                   jnp.zeros((30, 64, 16)))
    assert not ns.path_counts - before


# ----------------------------------------------------- launch contract


@pytest.mark.parametrize("degree", [2, 3])
def test_unfused_warm_iteration_launches(monkeypatch, degree):
    """An unfused warm iteration stays 1 + d launches: the Gram kernel,
    d - 1 power products and one apply (a zero VMEM budget pins the
    unfused tier)."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    monkeypatch.setattr(ops, "VMEM_BUDGET", 0)
    for iters in (1, 3):
        cfg = PrismConfig(degree=degree, iterations=iters)
        n = ops.count_launches(lambda a: matfn.polar(
            a, cfg=cfg, method="newton_schulz"), jnp.zeros((4, 64, 48)))
        assert n == iters * (1 + degree), (iters, n)


# the benchmark cells' Muon chain: three warm iterations, float32
_MUON = OptimizerConfig(name="muon", prism=PrismConfig(
    degree=2, iterations=3, warm_alpha_iters=3, sketch_dim=8))


def test_gpt2_paper_bucket_launches(monkeypatch, key):
    """gpt2-paper's Muon buckets as the benchmark's cells run them (three
    warm iterations, float32 kernels): the fused qkv-bias bucket in one
    launch, the two unfused buckets in 3 x 3 each — 19, as before the
    Gram-side order; and every unfused update takes that order."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    monkeypatch.setenv("REPRO_INTERPRET_MAX_ELEMS", "0")
    model = build(get_config("gpt2-paper"))
    shapes = matrix_view_shapes(model.param_shapes(), model.logical_axes())
    views = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    before = ns.path_counts.copy()
    n = ops.count_launches(
        lambda *vs: bucketing.polar_bucketed(list(vs), _MUON, key), *views)
    assert n == 19
    assert dict(ns.path_counts - before) == {"gram_side": 6}


def test_moonlight_bucket_launches(monkeypatch, key):
    """Moonlight-16B-A3B's Muon buckets at the benchmark cell's cut (5
    layers, a 20480-row vocabulary, 8 of 64 experts held): seven
    unfused buckets in 3 x 3 launches each, plus one fused launch for
    the routers' [4, 2048, 64] — 64."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    monkeypatch.setenv("REPRO_INTERPRET_MAX_ELEMS", "0")
    cfg = get_config("moonlight-16b-a3b")
    cfg = cfg.replace(num_layers=5, vocab_size=20480,
                      moe=dataclasses.replace(cfg.moe, ep_size=8))
    model = build(cfg)
    shapes = matrix_view_shapes(model.param_shapes(), model.logical_axes())
    views = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    before = ns.path_counts.copy()
    n = ops.count_launches(
        lambda *vs: bucketing.polar_bucketed(list(vs), _MUON, key), *views)
    assert n == 7 * 3 * 3 + 1
    assert dict(ns.path_counts - before) == {"gram_side": 7 * 3}
