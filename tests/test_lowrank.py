"""Lowrank sketched orthogonalization tier (DESIGN.md §14): rangefinder
and subspace-polar numerics against the SVD top-k oracle, trace-time tier
planning, Muon routing of embedding/LM-head leaves, and the §12
zero-matfn-launch contract with the tier enabled."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import OptimizerConfig, PrismConfig
from repro.core import lowrank
from repro.optim import base, bucketing, make_optimizer

_PCFG = PrismConfig(degree=2, iterations=30, warm_alpha_iters=2,
                    sketch_dim=8, tol=1e-6)


def _rank_l_matrix(key, m, n, l):
    """rank(A) == l with a well-separated spectrum: every sketched
    direction is genuine, so polar_lowrank must match the oracle to
    NS-convergence precision (see the module docstring's caveat)."""
    return jax.random.normal(key, (m, l)) @ \
        jax.random.normal(jax.random.fold_in(key, 1), (l, n))


# --------------------------------------------------------------- numerics

def test_rangefinder_orthonormal_and_captures_range(key):
    A = _rank_l_matrix(key, 96, 24, 4)
    Q = lowrank.rangefinder(A, 8, key, cfg=_PCFG)
    assert Q.shape == (96, 8)
    np.testing.assert_allclose(np.asarray(Q.T @ Q), np.eye(8), atol=1e-4)
    # range capture: projecting onto span(Q) preserves A
    np.testing.assert_allclose(np.asarray(Q @ (Q.T @ A)), np.asarray(A),
                               atol=1e-3)


def test_polar_lowrank_matches_svd_topk_oracle(key):
    l = 8
    A = _rank_l_matrix(key, 96, 24, l)
    O = lowrank.polar_lowrank(A, rank=4, oversample=4, cfg=_PCFG, key=key)
    oracle = lowrank.svd_topk(A, l)
    np.testing.assert_allclose(np.asarray(O), np.asarray(oracle),
                               atol=1e-4)


def test_polar_lowrank_wide_and_batched(key):
    """Orientation equivariance (wide views transpose through) and
    broadcasting over lead dims, with the §11 iters telemetry."""
    l = 8
    A = jnp.stack([_rank_l_matrix(jax.random.fold_in(key, i), 24, 96, l)
                   for i in range(3)])
    O, iters = lowrank.polar_lowrank(A, rank=4, oversample=4, cfg=_PCFG,
                                     key=key, return_iters=True)
    assert O.shape == (3, 24, 96) and iters.shape == (3,)
    assert int(iters.min()) >= 1
    for i in range(3):
        np.testing.assert_allclose(np.asarray(O[i]),
                                   np.asarray(lowrank.svd_topk(A[i], l)),
                                   atol=1e-4)


def test_power_iters_sharpen_subspace_capture(key):
    """On a decaying spectrum the power-refined basis aligns the top-k
    block with the oracle orders of magnitude tighter than the plain
    sketch."""
    m, n, k = 256, 64, 16
    U, _ = jnp.linalg.qr(jax.random.normal(key, (m, n)))
    V, _ = jnp.linalg.qr(jax.random.normal(jax.random.fold_in(key, 2),
                                           (n, n)))
    s = jnp.concatenate([jnp.linspace(10.0, 5.0, k),
                         0.05 * jnp.ones(n - k)])
    A = (U * s) @ V.T
    Pk = np.asarray(U[:, :k] @ U[:, :k].T)
    oracle = np.asarray(lowrank.svd_topk(A, k + 8))

    def topk_err(power_iters):
        O = lowrank.polar_lowrank(A, rank=k, oversample=8, cfg=_PCFG,
                                  key=key, power_iters=power_iters)
        return np.linalg.norm(Pk @ (np.asarray(O) - oracle)) / \
            np.linalg.norm(Pk @ oracle)

    e0, e1 = topk_err(0), topk_err(1)
    assert e1 < 1e-4, (e0, e1)
    assert e1 < e0 / 10, (e0, e1)


# ---------------------------------------------------------------- planner

def _ocfg(**kw):
    kw.setdefault("lowrank_rank", 16)
    kw.setdefault("lowrank_oversample", 8)
    kw.setdefault("prism", PrismConfig(degree=2, iterations=6,
                                       warm_alpha_iters=1, sketch_dim=8))
    return OptimizerConfig(name="muon", matfn_tol=1e-4, **kw)


def test_planner_tier_selection():
    cfg = _ocfg(lowrank_max_dim=1024)
    # over max_dim -> lowrank, l = rank + oversample
    assert bucketing.resolve_lowrank_tier(cfg, (768, 50257)) == 24
    assert bucketing.resolve_tier(cfg, (768, 50257)) == "lowrank"
    # small square -> cubic tiers: fused, since the slice fits VMEM
    assert bucketing.resolve_lowrank_tier(cfg, (64, 64)) is None
    assert bucketing.resolve_tier(cfg, (64, 64)) == "fused"
    # aspect-ratio trigger below max_dim (256 == 4.0 * 64)
    assert bucketing.resolve_tier(cfg, (64, 256)) == "lowrank"
    assert bucketing.resolve_tier(cfg, (64, 255)) == "fused"
    # a cubic slice too large for the fused tier takes the batch grid
    assert bucketing.resolve_tier(_ocfg(lowrank_rank=0),
                                  (2048, 2048)) == "grid"


def test_planner_degrades_to_exact_tiers():
    # disabled by default
    assert bucketing.resolve_lowrank_tier(
        _ocfg(lowrank_rank=0), (768, 50257)) is None
    # l >= min(m, n): no strict subspace -> cubic
    assert bucketing.resolve_lowrank_tier(
        _ocfg(lowrank_rank=60, lowrank_max_dim=128), (64, 512)) is None
    # non-NS matfn family: the subspace chain needs the NS polar
    cfg = OptimizerConfig(name="muon", matfn_method="polar_express")
    assert bucketing.resolve_lowrank_tier(cfg, (768, 50257)) is None
    # modeled-FLOPs win guard: mild aspect + l near min dim loses
    cfg = _ocfg(lowrank_rank=48, lowrank_oversample=8, lowrank_max_dim=64,
                lowrank_aspect=1.5)
    assert bucketing.resolve_lowrank_tier(cfg, (128, 64)) is None


def test_lowrank_flops_model_beats_cubic_at_4x_aspect():
    from repro.kernels import ops as kops

    for n in (64, 256, 1024):
        m = 4 * n
        lo = kops.lowrank_polar_flops((m, n), 24, iters=7)
        cu = kops.polar_flops((m, n), iters=7)
        assert lo < cu, (n, lo, cu)
        assert kops.lowrank_polar_hbm_bytes(
            (m, n), 24, jnp.dtype(jnp.bfloat16), iters=7) < \
            kops.polar_hbm_bytes((m, n), jnp.dtype(jnp.bfloat16), iters=7)


def test_config_validation():
    with pytest.raises(ValueError):
        OptimizerConfig(lowrank_rank=-1)
    with pytest.raises(ValueError):
        OptimizerConfig(lowrank_aspect=0.5)
    with pytest.raises(ValueError):
        OptimizerConfig(lowrank_rank=16, matfn_method="polar_express")


# -------------------------------------------------------- bucketed engine

def test_bucketed_engine_routes_lowrank(key):
    """polar_bucketed dispatches a triggering bucket through the sketched
    path — result matches a direct polar_lowrank call — while the
    non-triggering bucket keeps the exact cubic result."""
    cfg = _ocfg(lowrank_rank=4, lowrank_oversample=4, lowrank_max_dim=64)
    views = [_rank_l_matrix(key, 96, 24, 8),            # aspect 4: lowrank
             jax.random.normal(jax.random.fold_in(key, 9), (24, 24))]
    outs, iters, statuses = bucketing.polar_bucketed(views, cfg, key,
                                           with_iters=True)
    direct = lowrank.polar_lowrank(
        views[0], 4, 4, cfg=cfg.resolved_prism,
        key=jax.random.fold_in(key, 1), method="prism")
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(direct),
                               rtol=2e-5, atol=2e-5)
    from repro.core import matfn
    exact = matfn.polar(views[1], method="prism", cfg=cfg.resolved_prism,
                        key=jax.random.fold_in(key, 0))
    np.testing.assert_allclose(np.asarray(outs[1]), np.asarray(exact),
                               rtol=2e-5, atol=2e-5)
    assert iters[0].shape == () and int(iters[0]) >= 1


# --------------------------------------------------------- muon routing

def _muon_setup(ocfg, arch="gpt2-paper"):
    from repro.configs import get_smoke_config
    from repro.models import build

    cfg = get_smoke_config(arch)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = make_optimizer(ocfg, model.logical_axes())
    return cfg, model, params, opt


def test_muon_routes_embedding_through_lowrank():
    """With the tier enabled the vocab leaves leave the AdamW fallback:
    their state carries momentum + the planner-resolved tier telemetry
    (lowrank for the smoke model's (64, 256) embedding view), and a step
    produces finite, loss-reducing updates."""
    ocfg = _ocfg(learning_rate=0.02, lowrank_max_dim=1024,
                 prism=PrismConfig(degree=2, iterations=6,
                                   warm_alpha_iters=1, sketch_dim=8))
    cfg, model, params, opt = _muon_setup(ocfg)
    state = opt.init(params)
    emb = state["leaves"]["embed"]
    assert "nu" not in emb and "mom" in emb          # Muon, not AdamW
    assert int(emb["tier"]) == bucketing.TIER_CODES["lowrank"]
    assert int(state["leaves"]["head"]["tier"]) == \
        bucketing.TIER_CODES["lowrank"]
    # square-ish views stay on the cubic tiers (fused at this small width)
    assert int(state["leaves"]["layers"]["mlp"]["w_up"]["tier"]) == \
        bucketing.TIER_CODES["fused"]

    from repro.data import DataConfig, make_batch_fn
    batch_fn = make_batch_fn(cfg, DataConfig(vocab_size=cfg.vocab_size,
                                             seq_len=32, global_batch=8,
                                             markov_rank=8))

    @jax.jit
    def step_fn(p, s, t):
        batch = batch_fn(t)
        (loss, _), grads = jax.value_and_grad(
            lambda q: model.loss(q, batch), has_aux=True)(p)
        grads, _ = base.clip_by_global_norm(grads, 1.0)
        p, s = opt.update(grads, s, p, t, jax.random.PRNGKey(7))
        return p, s, loss

    losses = []
    for t in range(6):
        params, state, loss = step_fn(params, state, jnp.asarray(t))
        losses.append(float(loss))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    # the update applied to the embedding is nonzero (it trains)
    assert float(jnp.abs(state["leaves"]["embed"]["mom"]).max()) > 0


def test_muon_without_lowrank_keeps_adamw_fallback():
    ocfg = OptimizerConfig(name="muon",
                           prism=PrismConfig(degree=2, iterations=3,
                                             warm_alpha_iters=1,
                                             sketch_dim=8))
    _, _, params, opt = _muon_setup(ocfg)
    state = opt.init(params)
    emb = state["leaves"]["embed"]
    assert "nu" in emb and "tier" not in emb


def test_lowrank_stale_cache_and_async_state():
    """§12/§9 composition: lowrank-routed leaves carry the LIFTED
    full-view ortho caches (cache dtype, pending twin included), so the
    staleness and async planes treat the tier like any other."""
    ocfg = _ocfg(learning_rate=0.02, precond_every=4, precond_async=True,
                 precond_cache_dtype="bfloat16",
                 prism=PrismConfig(degree=2, iterations=3,
                                   warm_alpha_iters=1, sketch_dim=8))
    _, _, params, opt = _muon_setup(ocfg)
    state = opt.init(params)
    emb = state["leaves"]["embed"]
    assert emb["ortho"].shape == (64, 256)            # lifted view shape
    assert emb["ortho"].dtype == jnp.bfloat16
    assert emb["ortho_p"].shape == (64, 256)
    # the refresh plane fills the pending cache through the lowrank tier
    parts = base.install_pending(
        state, opt.refresh(state, jax.random.PRNGKey(1)), at_step=0)
    pend = parts["leaves"]["embed"]["ortho_p"]
    assert bool(jnp.all(jnp.isfinite(pend.astype(jnp.float32))))


def test_steady_state_zero_launches_with_lowrank(monkeypatch):
    """The §12 contract survives the §14 tier: an async trainer step
    with embedding leaves routed lowrank compiles with ZERO matfn kernel
    launches; the refresh program carries them all."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    from repro.configs import get_smoke_config
    from repro.data import DataConfig, make_batch_fn
    from repro.kernels import ops
    from repro.models import build
    from repro.train.state import make_train_step, master_params

    key = jax.random.PRNGKey(2)
    cfg = get_smoke_config("gpt2-paper").replace(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, d_ff=128)
    model = build(cfg)
    ocfg = _ocfg(lowrank_rank=8, lowrank_oversample=4,
                 lowrank_max_dim=128, precond_every=4, precond_async=True,
                 prism=PrismConfig(degree=2, iterations=2,
                                   warm_alpha_iters=1, sketch_dim=8))
    opt = make_optimizer(ocfg, model.logical_axes())
    step_fn = make_train_step(model, opt, ocfg)
    params = master_params(model.init(key))
    state = opt.init(params)
    assert int(state["leaves"]["embed"]["tier"]) == \
        bucketing.TIER_CODES["lowrank"]
    batch = make_batch_fn(cfg, DataConfig(vocab_size=cfg.vocab_size,
                                          seq_len=16, global_batch=2,
                                          markov_rank=8))(jnp.asarray(0))
    step = jnp.asarray(0, jnp.int32)
    n = ops.count_launches(
        lambda p, st, b: step_fn(p, st, b, step, False), params, state,
        batch)
    assert n == 0, n
    n_refresh = ops.count_launches(lambda s: opt.refresh(s, key), state)
    assert n_refresh > 0, n_refresh
