"""Per-kernel allclose sweeps: Pallas (interpret mode) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import gram as gram_kernel
from repro.kernels import matmul_add as mma_kernel
from repro.kernels import ref
from repro.kernels import sketch_traces as sk_kernel

pytestmark = pytest.mark.tier1

SHAPES_MM = [
    (8, 8, 8),
    (128, 128, 128),
    (256, 128, 64),
    (100, 70, 130),   # non-divisible => padding path
    (512, 256, 384),
    (33, 257, 129),
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("m,k,n", SHAPES_MM)
@pytest.mark.parametrize("dtype", DTYPES)
def test_matmul_add_sweep(key, m, k, n, dtype):
    ka, kb, kc = jax.random.split(key, 3)
    A = jax.random.normal(ka, (m, k), jnp.float32).astype(dtype)
    B = jax.random.normal(kb, (k, n), jnp.float32).astype(dtype)
    C = jax.random.normal(kc, (m, n), jnp.float32).astype(dtype)
    got = mma_kernel.matmul_add(A, B, C, alpha=0.7, beta=-1.3,
                                bm=128, bn=128, bk=64, interpret=True)
    want = ref.matmul_add(A, B, C, alpha=0.7, beta=-1.3)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), **_tol(dtype))


def test_matmul_add_no_c(key):
    A = jax.random.normal(key, (96, 64))
    B = jax.random.normal(jax.random.fold_in(key, 1), (64, 72))
    got = mma_kernel.matmul_add(A, B, alpha=2.0, bm=64, bn=64, bk=32,
                                interpret=True)
    np.testing.assert_allclose(got, 2.0 * (A @ B), rtol=2e-5, atol=2e-5)


GRAM_SHAPES = [(64, 64), (128, 256), (256, 128), (200, 100), (96, 33)]


@pytest.mark.parametrize("m,n", GRAM_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_gram_sweep(key, m, n, dtype):
    X = jax.random.normal(key, (m, n), jnp.float32).astype(dtype)
    U = gram_kernel.gram_upper(X, alpha=1.0, beta=-1.0, bn=64, bk=64,
                               interpret=True)
    got = gram_kernel.mirror_upper(U)
    want = ref.gram(X, alpha=1.0, beta=-1.0)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), **_tol(dtype))


def test_gram_upper_blocks_correct(key):
    """Upper blocks are exact; lower blocks are undefined (never visited —
    that's the saved MXU work) and masked out by mirror_upper."""
    X = jax.random.normal(key, (64, 256))
    U = np.asarray(gram_kernel.gram_upper(X, alpha=0.0, beta=1.0, bn=64,
                                          bk=64, interpret=True))
    W = np.asarray(ref.gram(X, alpha=0.0, beta=1.0))
    for i in range(4):
        for j in range(i, 4):
            np.testing.assert_allclose(U[i * 64:(i + 1) * 64,
                                         j * 64:(j + 1) * 64],
                                       W[i * 64:(i + 1) * 64,
                                         j * 64:(j + 1) * 64],
                                       rtol=2e-5, atol=2e-5)


def test_unrank_upper_exhaustive():
    """Closed-form triangle unranking is exact for every nb up to 40."""
    import jax.numpy as jnp
    for nb in [1, 2, 3, 7, 16, 40]:
        ts = jnp.arange(nb * (nb + 1) // 2)
        i, j = gram_kernel._unrank_upper(ts, nb)
        want = np.asarray(np.triu_indices(nb))
        np.testing.assert_array_equal(np.asarray(i), want[0])
        np.testing.assert_array_equal(np.asarray(j), want[1])


@pytest.mark.parametrize("n,p,maxp", [(64, 8, 6), (200, 8, 10), (256, 16, 6),
                                      (130, 4, 4)])
def test_sketch_traces_sweep(key, n, p, maxp):
    kr, ks = jax.random.split(key)
    R = jax.random.normal(kr, (n, n)) / np.sqrt(n)
    R = 0.5 * (R + R.T)
    S = jax.random.normal(ks, (p, n)) / np.sqrt(p)
    St = jnp.pad(S.T, ((0, 0), (0, (-p) % 128)))
    V = St
    ts = [float(jnp.sum(St * St))]
    for _ in range(maxp):
        V, t = sk_kernel.sketch_step(R, V, St, bm=64, bk=64, interpret=True)
        ts.append(float(t))
    want = np.asarray(ref.sketch_traces(R, S, maxp))
    np.testing.assert_allclose(np.asarray(ts), want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("B,m,k,n", [(3, 64, 64, 64), (2, 100, 70, 130),
                                     (5, 33, 257, 129)])
def test_matmul_add_batch_grid(key, B, m, k, n):
    """The batch-grid kernel == a loop of 2-D oracle calls."""
    ka, kb, kc = jax.random.split(key, 3)
    A = jax.random.normal(ka, (B, m, k))
    Bm = jax.random.normal(kb, (B, k, n))
    C = jax.random.normal(kc, (B, m, n))
    got = mma_kernel.matmul_add(A, Bm, C, alpha=0.7, beta=-1.3,
                                bm=64, bn=64, bk=64, interpret=True)
    want = np.stack([np.asarray(ref.matmul_add(A[b], Bm[b], C[b],
                                               alpha=0.7, beta=-1.3))
                     for b in range(B)])
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,m,n", [(3, 96, 130), (2, 64, 64), (4, 200, 100)])
def test_gram_batch_grid(key, B, m, n):
    X = jax.random.normal(key, (B, m, n))
    U = gram_kernel.gram_upper(X, alpha=1.0, beta=-1.0, bn=64, bk=64,
                               interpret=True)
    got = gram_kernel.mirror_upper(U)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.gram(X)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,n,p,maxp,bn", [(3, 64, 8, 10, 64),
                                           (2, 130, 8, 5, 64),
                                           (1, 96, 16, 7, 32)])
def test_sketch_chain_single_launch(key, B, n, p, maxp, bn):
    """The fused whole-chain kernel == the per-step oracle chain."""
    kr, ks = jax.random.split(key)
    R = jax.random.normal(kr, (B, n, n)) / np.sqrt(n)
    R = 0.5 * (R + jnp.swapaxes(R, -1, -2))
    S = jax.random.normal(ks, (p, n)) / np.sqrt(p)
    St = jnp.pad(S.T, ((0, 0), (0, (-p) % 128)))
    got = sk_kernel.sketch_chain(R, St, maxp, bn=bn, interpret=True)
    want = np.asarray(ref.sketch_traces(R, S, maxp))[:, 1:]
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_sketch_chain_bf16(key):
    kr, ks = jax.random.split(key)
    R = (jax.random.normal(kr, (2, 64, 64)) / 8).astype(jnp.bfloat16)
    R = 0.5 * (R + jnp.swapaxes(R, -1, -2))
    S = (jax.random.normal(ks, (8, 64)) / np.sqrt(8)).astype(jnp.bfloat16)
    St = jnp.pad(S.T, ((0, 0), (0, 120)))
    got = sk_kernel.sketch_chain(R, St, 6, bn=64, interpret=True)
    want = np.asarray(ref.sketch_traces(R, S, 6))[:, 1:]
    np.testing.assert_allclose(np.asarray(got), want, rtol=5e-2, atol=5e-2)


# ----------------------------------------------------- randomized fuzz sweep

# deterministic fuzz corpus: non-divisible (B, m, k, n) drawn once at
# import so every CI run sweeps the same shapes (rerunnable failures)
_FUZZ_RNG = np.random.default_rng(0)
FUZZ_SHAPES = [tuple(int(x) for x in (_FUZZ_RNG.integers(1, 4),
                                      _FUZZ_RNG.integers(1, 100),
                                      _FUZZ_RNG.integers(1, 100),
                                      _FUZZ_RNG.integers(1, 100)))
               for _ in range(6)]


@pytest.mark.parametrize("B,m,k,n", FUZZ_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fuzz_matmul_add(key, B, m, k, n, dtype):
    """Interpret-mode kernel == oracle on random non-divisible shapes for
    both fp32 and bf16-in/fp32-accum operands."""
    ka, kb, kc = jax.random.split(key, 3)
    A = jax.random.normal(ka, (B, m, k), jnp.float32).astype(dtype)
    Bm = jax.random.normal(kb, (B, k, n), jnp.float32).astype(dtype)
    C = jax.random.normal(kc, (B, m, n), jnp.float32).astype(dtype)
    got = mma_kernel.matmul_add(A, Bm, C, alpha=0.5, beta=1.25,
                                bm=32, bn=32, bk=32, interpret=True)
    want = ref.matmul_add(A, Bm, C, alpha=0.5, beta=1.25)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), **_tol(dtype))


@pytest.mark.parametrize("B,m,k,n", FUZZ_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fuzz_gram(key, B, m, k, n, dtype):
    del n  # gram consumes (B, m, k) -> [B, k, k]
    X = jax.random.normal(key, (B, m, k), jnp.float32).astype(dtype)
    U = gram_kernel.gram_upper(X, alpha=1.0, beta=-1.0, bn=32, bk=32,
                               interpret=True)
    got = gram_kernel.mirror_upper(U)
    want = ref.gram(X, alpha=1.0, beta=-1.0)
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), **_tol(dtype))


@pytest.mark.parametrize("B,m,k,n", FUZZ_SHAPES[:3])
@pytest.mark.parametrize("dtype", DTYPES)
def test_fuzz_sketch_chain(key, B, m, k, n, dtype):
    del m  # the chain consumes a symmetric [B, k, k] residual
    kr, ks = jax.random.split(key)
    R = jax.random.normal(kr, (B, k, k)) / (2 * np.sqrt(max(k, 1)))
    R = (0.5 * (R + jnp.swapaxes(R, -1, -2))).astype(dtype)
    p = 1 + n % 8
    S = (jax.random.normal(ks, (p, k)) / np.sqrt(p)).astype(dtype)
    St = jnp.pad(S.T, ((0, 0), (0, (-p) % 128)))
    got = sk_kernel.sketch_chain(R, St, 5, bn=32, interpret=True)
    want = np.asarray(ref.sketch_traces(R, S, 5))[:, 1:]
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got), want, rtol=tol, atol=tol)


def test_fuzz_launch_count_dtype_parity(monkeypatch, key):
    """Contract: the bf16 path issues exactly the launches the fp32 path
    does on every fuzz shape — precision never changes dispatch."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    from repro.kernels import ops

    for B, m, k, n in FUZZ_SHAPES:
        counts = {}
        for dtype in DTYPES:
            A = jnp.zeros((B, m, k), dtype)
            Bm = jnp.zeros((B, k, n), dtype)
            X = jnp.zeros((B, m, k), dtype)
            counts[dtype] = ops.count_launches(
                lambda A, Bm, X: (ops.matmul_add(A, Bm),
                                  ops.gram(X)), A, Bm, X)
        assert counts[jnp.float32] == counts[jnp.bfloat16] == 2, \
            ((B, m, k, n), counts)


# ------------------------------------------------- interpret-mode size cutoff

def test_interpret_cutoff_falls_back_to_ref(monkeypatch, key):
    """ops._mode honors REPRO_INTERPRET_MAX_ELEMS: oversized operands
    fall back to the jnp oracle (0 launches) so CPU validation runs don't
    crawl; small ones still execute the kernel body; 0 disables."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    from repro.kernels import ops

    A = jax.random.normal(key, (64, 64))
    B = jax.random.normal(jax.random.fold_in(key, 1), (64, 64))

    monkeypatch.setenv("REPRO_INTERPRET_MAX_ELEMS", "1000")  # 4096 > 1000
    assert ops.count_launches(lambda a, b: ops.matmul_add(a, b), A, B) == 0
    got = ops.matmul_add(A, B)  # numerics identical through the fallback
    np.testing.assert_allclose(got, ref.matmul_add(A, B), rtol=2e-5,
                               atol=2e-5)

    monkeypatch.setenv("REPRO_INTERPRET_MAX_ELEMS", "100000")
    assert ops.count_launches(lambda a, b: ops.matmul_add(a, b), A, B) == 1

    monkeypatch.setenv("REPRO_INTERPRET_MAX_ELEMS", "0")  # disabled
    assert ops.count_launches(lambda a, b: ops.matmul_add(a, b), A, B) == 1

    monkeypatch.delenv("REPRO_INTERPRET_MAX_ELEMS")
    assert ops._interpret_cutoff() == ops._DEFAULT_INTERPRET_MAX_ELEMS


def test_interpret_cutoff_only_affects_interpret_mode(monkeypatch, key):
    """ref/native dispatch ignores the cutoff (it guards only the Python
    interpreter path)."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
    monkeypatch.setenv("REPRO_INTERPRET_MAX_ELEMS", "1")
    from repro.kernels import ops

    A = jax.random.normal(key, (32, 16))
    B = jax.random.normal(jax.random.fold_in(key, 1), (16, 8))
    np.testing.assert_allclose(ops.matmul_add(A, B), A @ B, rtol=2e-5,
                               atol=2e-5)


def test_ops_dispatch_ref_on_cpu(key):
    """ops.py must fall back to the jnp oracle on CPU by default."""
    from repro.kernels import ops

    A = jax.random.normal(key, (3, 32, 16))  # batched
    B = jax.random.normal(jax.random.fold_in(key, 1), (3, 16, 24))
    got = ops.matmul_add(A, B, alpha=1.0)
    np.testing.assert_allclose(got, A @ B, rtol=2e-5, atol=2e-5)
    R = jax.random.normal(key, (2, 48, 48)) / 7
    R = 0.5 * (R + jnp.swapaxes(R, -1, -2))
    S = jax.random.normal(key, (8, 48)) / np.sqrt(8)
    got = ops.sketch_traces(R, S, 4)
    want = ref.sketch_traces(R, S, 4)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_prism_with_interpret_kernels_end_to_end(key, monkeypatch):
    """PRISM polar with the kernel bodies (interpret) == with the ref.py
    oracles."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    from repro.config import PrismConfig
    from repro.core import matfn

    A = jax.random.normal(key, (96, 48))
    Xk = matfn.polar(A, method="prism",
                     cfg=PrismConfig(degree=2, sketch_dim=8),
                     key=key, iters=6)
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
    Xr = matfn.polar(A, method="prism",
                     cfg=PrismConfig(degree=2, sketch_dim=8),
                     key=key, iters=6)
    np.testing.assert_allclose(np.asarray(Xk), np.asarray(Xr),
                               rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("dtypes,want", [
    ((jnp.float32, jnp.float32), jax.lax.Precision.HIGHEST),
    ((jnp.bfloat16, jnp.float32), jax.lax.Precision.HIGHEST),
    ((jnp.bfloat16, jnp.bfloat16), None)])
def test_mxu_precision_keeps_fp32_contract(dtypes, want):
    """fp32 operands contract at full precision inside the kernels (Mosaic
    would round them to bf16 by default); bf16 ones need no override."""
    from repro.kernels import mxu

    assert mxu.precision(*[jnp.zeros((8, 128), d) for d in dtypes]) == want
