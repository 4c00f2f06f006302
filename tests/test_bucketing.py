"""Bucketed batched matrix-function engine: planning, parity, padding,
and the constant-launch-count contract (DESIGN.md §7)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import OptimizerConfig, PrismConfig
from repro.core import matfn, prism, sketch
from repro.core import polynomials as poly
from repro.kernels import ops
from repro.optim import base, bucketing, make_optimizer

pytestmark = pytest.mark.tier1

EXACT = PrismConfig(degree=2, iterations=6, warm_alpha_iters=1,
                    sketch_dim=0)  # exact traces: deterministic, key-free


# ------------------------------------------------------------------ planning

def test_plan_exact_groups():
    shapes = [(64, 32), (64, 32), (3, 64, 32), (32, 64), (128, 128)]
    buckets = bucketing.plan_buckets(shapes)
    got = {b.shape: b.size for b in buckets}
    # (64, 32) and (32, 64) must NOT merge (orientation preserved)
    assert got == {(64, 32): 5, (32, 64): 1, (128, 128): 1}
    by_shape = {b.shape: b for b in buckets}
    offs = [(e.index, e.offset, e.count) for e in by_shape[(64, 32)].entries]
    assert offs == [(0, 0, 1), (1, 1, 1), (2, 2, 3)]


def test_plan_pad_merges_within_slack():
    shapes = [(64, 64), (64, 60), (60, 64), (64, 16)]
    buckets = bucketing.plan_buckets(shapes, pad=True, pad_slack=0.25)
    got = {b.shape: b.size for b in buckets}
    # (64, 60) pads its Gram side (cols) up to (64, 64); (60, 64) would
    # need non-Gram-side (row) padding — refused; (64, 16) fits the side
    # rule but would be 4x area — refused by the slack bound
    assert got == {(64, 64): 2, (60, 64): 1, (64, 16): 1}
    assert bucketing.plan_buckets(shapes, pad=False)[0].padded is False


def test_gather_scatter_roundtrip(key):
    views = [jax.random.normal(jax.random.fold_in(key, i), s)
             for i, s in enumerate([(2, 8, 6), (8, 6), (7, 5)])]
    buckets = bucketing.plan_buckets([v.shape for v in views], pad=True,
                                     pad_slack=0.4)
    outs = [None] * len(views)
    for b in buckets:
        stacked = bucketing.gather_bucket(b, views)
        assert stacked.shape == (b.size,) + b.shape
        bucketing.scatter_bucket(b, stacked, outs)
    for v, o in zip(views, outs):
        np.testing.assert_array_equal(np.asarray(v), np.asarray(o))


# ------------------------------------------------------------------- parity

def _tree(key):
    params = {
        "w1": jax.random.normal(key, (64, 32)),
        "w2": jax.random.normal(jax.random.fold_in(key, 1), (64, 32)),
        "w3": jax.random.normal(jax.random.fold_in(key, 2), (3, 48, 32)),
        "w4": jax.random.normal(jax.random.fold_in(key, 3), (32, 48)),
        "b": jax.random.normal(jax.random.fold_in(key, 4), (64,)),
    }
    axes = {"w1": ("embed", "mlp"), "w2": ("embed", "mlp"),
            "w3": ("layers", "embed", "mlp"), "w4": ("mlp", "embed"),
            "b": ("embed",)}
    return params, axes


@pytest.mark.parametrize("name", ["muon", "shampoo"])
def test_bucketed_matches_per_leaf(key, name):
    """Bucketed update == per-leaf update on a mixed-shape tree (exact
    alpha fit, so the two dispatch strategies are the same math)."""
    params, axes = _tree(key)
    grads = jax.tree.map(
        lambda p: jax.random.normal(jax.random.fold_in(key, 9), p.shape),
        params)
    outs = {}
    for bucketed in (True, False):
        ocfg = OptimizerConfig(
            name=name, learning_rate=0.02 if name == "muon" else 1e-3,
            prism=EXACT, bucketed=bucketed, max_precond_dim=512)
        opt = make_optimizer(ocfg, axes)
        new_p, _ = jax.jit(opt.update)(grads, opt.init(params), params, 0,
                                       key)
        outs[bucketed] = new_p
    for k in params:
        np.testing.assert_allclose(np.asarray(outs[True][k]),
                                   np.asarray(outs[False][k]),
                                   rtol=2e-5, atol=2e-5)


def test_bucketed_sketched_still_orthogonalizes(key):
    """With a real (shared-sketch) fit the bucketed Muon update direction
    is still orthogonal per leaf."""
    params, axes = _tree(key)
    grads = jax.tree.map(
        lambda p: jax.random.normal(jax.random.fold_in(key, 5), p.shape),
        params)
    ocfg = OptimizerConfig(
        name="muon", learning_rate=0.1, weight_decay=0.0,
        prism=PrismConfig(degree=2, iterations=8, sketch_dim=8))
    opt = make_optimizer(ocfg, axes)
    new_p, _ = opt.update(grads, opt.init(params), params, 0, key)
    upd = (np.asarray(params["w1"], np.float32)
           - np.asarray(new_p["w1"], np.float32)) / 0.1
    utu = upd.T @ upd / max(1.0, 64 / 32)
    np.testing.assert_allclose(utu, np.eye(32), atol=5e-2)


# ------------------------------------------------------------ pad-to-bucket

def test_pad_trace_correction_identity(key):
    """tr(S R_pad^i S^T) - c == tr(S_a R^i S_a^T) exactly, where R_pad =
    diag(R, I) and c = sum of ||S[:, j]||^2 over pad columns — the n_real
    correction fit_alpha applies."""
    n, padn, p, maxp = 24, 32, 8, 10
    R = jax.random.normal(key, (n, n)) / (3 * np.sqrt(n))
    R = 0.5 * (R + R.T)
    Rp = jnp.eye(padn).at[:n, :n].set(R)
    S = sketch.gaussian_sketch(jax.random.fold_in(key, 1), p, padn)
    t_pad = ops.sketch_traces(Rp, S, maxp)
    c = float(jnp.sum(jnp.square(S[:, n:])))
    t_real = ops.sketch_traces(R, S[:, :n], maxp)
    np.testing.assert_allclose(np.asarray(t_pad) - c, np.asarray(t_real),
                               rtol=1e-4, atol=1e-4)


def test_pad_invariance_exact_fit(key):
    """Padded rows/cols do not perturb the polar factor of the real block:
    the padded-bucket result equals the unpadded per-leaf result."""
    views = [jax.random.normal(jax.random.fold_in(key, i), s)
             for i, s in enumerate([(64, 64), (64, 60), (60, 64),
                                    (2, 64, 64)])]
    ocfg = OptimizerConfig(prism=EXACT, bucket_pad=True)
    buckets = bucketing.plan_buckets([v.shape for v in views], pad=True,
                                     pad_slack=0.25)
    # (64, 60) merges into the padded (64, 64) bucket; (60, 64) would
    # need non-Gram-side padding and stays its own exact bucket
    sizes = {b.shape: (b.size, b.padded) for b in buckets}
    assert sizes == {(64, 64): (4, True), (60, 64): (1, False)}
    outs = bucketing.polar_bucketed(views, ocfg, key)
    for v, o in zip(views, outs):
        ref = matfn.polar(v, method="prism", cfg=EXACT, key=None)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   rtol=5e-5, atol=5e-5)


def test_pad_invariance_sketched(key):
    """Sketched fit with the n_real correction: padded-bucket polar still
    converges to the true orthogonal factor of the real block."""
    views = [jax.random.normal(jax.random.fold_in(key, i), s)
             for i, s in enumerate([(64, 64), (64, 56)])]
    ocfg = OptimizerConfig(prism=PrismConfig(degree=2, iterations=10,
                                             warm_alpha_iters=2,
                                             sketch_dim=8),
                           bucket_pad=True)
    outs = bucketing.polar_bucketed(views, ocfg, key)
    for v, o in zip(views, outs):
        ref = matfn.polar(v, method="svd")
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   rtol=5e-3, atol=5e-3)


# --------------------------------------------------- launch-count contract

def _count_pallas_launches(fn, *args):
    return ops.count_launches(fn, *args)


# a VMEM budget that holds the whole sketch chain of a 64-wide slice but
# no fused iteration: the §7 batch-grid tier, as the chip runs it at its
# large buckets
UNFUSED_BUDGET = 200_000


@pytest.mark.parametrize("degree", [1, 2])
def test_constant_launch_count(monkeypatch, key, degree):
    """One fitted PRISM-NS iteration over a [B, n, n] bucket issues a
    constant number of Pallas launches, independent of B and of the sketch
    chain length max_power = 4d+2: with the fused tier (the default for
    VMEM-fitting buckets, DESIGN.md §10) exactly 2 — residual+chain, then
    the fused Horner — independent of d as well; the §7 batch-grid tier
    (under a budget no fused iteration fits) keeps its 2 + d contract."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    cfg = PrismConfig(degree=degree, iterations=1, warm_alpha_iters=0,
                      sketch_dim=8)
    for budget, want in ((ops.VMEM_BUDGET, 2), (UNFUSED_BUDGET, 2 + degree)):
        monkeypatch.setattr(ops, "VMEM_BUDGET", budget)
        counts = []
        for B in (1, 4, 16):
            A = jnp.zeros((B, 64, 48))
            counts.append(_count_pallas_launches(
                lambda A: matfn.polar(A, method="prism", cfg=cfg, key=key),
                A))
        assert counts == [want] * 3, (budget, counts)


def test_trainer_skip_step_zero_matfn_launches(monkeypatch, key):
    """The staleness contract (DESIGN.md §8): a FULL trainer step compiled
    with the static skip variant (refresh=False) issues ZERO matrix-
    function kernel launches — the cached orthogonalized views serve the
    update — while the refresh variant issues the bucketed counts."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    from repro.configs import get_smoke_config
    from repro.data import DataConfig, make_batch_fn
    from repro.models import build
    from repro.train.state import make_train_step, master_params

    cfg = get_smoke_config("gpt2-paper").replace(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, d_ff=128)
    model = build(cfg)
    ocfg = OptimizerConfig(
        name="muon", precond_every=4,
        prism=PrismConfig(degree=2, iterations=2, warm_alpha_iters=1,
                          sketch_dim=8))
    opt = make_optimizer(ocfg, model.logical_axes())
    step_fn = make_train_step(model, opt, ocfg)
    params = master_params(model.init(key))
    state = opt.init(params)
    batch = make_batch_fn(cfg, DataConfig(vocab_size=cfg.vocab_size,
                                          seq_len=16, global_batch=2,
                                          markov_rank=8))(jnp.asarray(0))
    step = jnp.asarray(0, jnp.int32)
    n_skip = _count_pallas_launches(
        lambda p, s, b: step_fn(p, s, b, step, False), params, state, batch)
    n_refresh = _count_pallas_launches(
        lambda p, s, b: step_fn(p, s, b, step, True), params, state, batch)
    assert n_skip == 0, n_skip
    assert n_refresh > 0, n_refresh


def test_fitted_iteration_launches_scale_with_iters_only(monkeypatch, key):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    def n_launches(iters, warm):
        cfg = PrismConfig(degree=2, iterations=iters, warm_alpha_iters=warm,
                          sketch_dim=8)
        return _count_pallas_launches(
            lambda A: matfn.polar(A, method="prism", cfg=cfg, key=key),
            jnp.zeros((8, 64, 64)))
    # fused tier (§10): 2 per fitted iteration, 1 for the whole warm tail
    assert n_launches(3, 0) == 6
    assert n_launches(3, 1) == 1 + 2 * 2
    assert n_launches(3, 3) == 1
    # §7 batch-grid tier: fitted 2+d, warm skips the chain (1+d)
    monkeypatch.setattr(ops, "VMEM_BUDGET", UNFUSED_BUDGET)
    assert n_launches(3, 0) == 12
    assert n_launches(3, 1) == 11
