"""Async preconditioner service (DESIGN.md §12).

Covers the refresh-plane contract end to end: the shared refresh-period
helper, async-vs-blocking parity at swap boundaries (asyncness changes
scheduling, never values), the zero-matfn-launch steady-state contract,
the drift trigger on an adversarial spectrum shift, sharding rules for
the pending twins, and sharded double-buffer parity on the 8-device CI
mesh (subprocess, same pattern as test_sharded_precond.py).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import OptimizerConfig, PrismConfig, TrainConfig
from repro.optim import base, make_optimizer


# ------------------------------------------------- resolve_refresh_period

def test_resolve_refresh_period():
    muon = OptimizerConfig(name="muon", precond_every=6)
    assert base.resolve_refresh_period(muon) == 6
    # shampoo honors its legacy knob too: period is the max of the two
    sham = OptimizerConfig(name="shampoo", precond_every=3,
                           precondition_every=10)
    assert base.resolve_refresh_period(sham) == 10
    sham2 = OptimizerConfig(name="shampoo", precond_every=12,
                            precondition_every=5)
    assert base.resolve_refresh_period(sham2) == 12
    # name override for configs reused across optimizers
    assert base.resolve_refresh_period(sham, name="muon") == 3
    # floor at 1
    assert base.resolve_refresh_period(
        OptimizerConfig(name="muon", precond_every=0)) == 1


def test_config_validation():
    with pytest.raises(ValueError, match="precond_every"):
        OptimizerConfig(name="muon", precond_async=True, precond_every=1)
    with pytest.raises(ValueError, match="matfn_tol"):
        OptimizerConfig(name="muon", precond_every=4,
                        precond_drift_slack=2.0)
    cfg = OptimizerConfig(name="muon", precond_every=4, precond_async=True,
                          matfn_tol=1e-2, precond_drift_slack=3.0)
    assert cfg.drift_threshold == pytest.approx(2e-2)
    # slack <= 1 clamps to an always-fire threshold of 0
    cfg0 = OptimizerConfig(name="muon", precond_every=4, precond_async=True,
                           matfn_tol=1e-2, precond_drift_slack=0.5)
    assert cfg0.drift_threshold == 0.0
    # trigger disabled entirely without slack
    assert OptimizerConfig(name="muon", precond_every=4,
                           precond_async=True).drift_threshold is None


# ----------------------------------------------------------- fixtures

def _tree(key):
    params = {"w1": jax.random.normal(key, (64, 32)),
              "w3": jax.random.normal(jax.random.fold_in(key, 2),
                                      (3, 48, 32)),
              "b": jax.random.normal(jax.random.fold_in(key, 4), (64,))}
    axes = {"w1": ("embed", "mlp"), "w3": ("layers", "embed", "mlp"),
            "b": ("embed",)}
    return params, axes


def _grad_stream(key, params, t, scale=0.1):
    k = jax.random.fold_in(key, 1000 + t)
    return jax.tree.map(
        lambda p: scale * jax.random.normal(
            jax.random.fold_in(k, p.size), p.shape), params)


def _async_cfg(name, **kw):
    kw.setdefault("learning_rate", 0.05)
    kw.setdefault("precond_every", 3)
    kw.setdefault("prism", PrismConfig(degree=2, iterations=4,
                                       warm_alpha_iters=1, sketch_dim=8))
    kw.setdefault("precond_swap_delay", 1)
    return OptimizerConfig(name=name, precond_async=True, **kw)


# ------------------------------------------------- async == blocking

@pytest.mark.parametrize("name", ["muon", "shampoo"])
def test_async_matches_blocking_at_swap_boundaries(name):
    """Dispatching the refresh asynchronously changes SCHEDULING, never
    values: a blocking reference that runs the identical refresh program
    synchronously (block_until_ready before the step) and the async
    service produce bit-identical params at every step — including the
    swap-boundary steps where the pending buffer becomes active."""
    key = jax.random.PRNGKey(0)
    params, axes = _tree(key)
    cfg = _async_cfg(name)
    opt = make_optimizer(cfg, axes)
    step = jax.jit(opt.update, static_argnums=(5,))
    refresh = jax.jit(opt.refresh)

    def run(blocking):
        svc = base.AsyncPrecondService(opt, cfg, refresh_jit=refresh)
        p, s = params, opt.init(params)
        swaps = []
        for t in range(8):
            drift = float(base.precond_drift(s))
            s = svc.step_begin(
                s, t, jax.random.fold_in(jax.random.PRNGKey(7), t),
                drift=drift)
            if blocking:
                jax.block_until_ready(s)  # refresh forced to finish
            before = int(s["pending_at"])
            g = _grad_stream(key, params, t)
            p, s = step(g, s, p, t, jax.random.PRNGKey(t), False)
            if before != base.NO_PENDING and \
                    int(s["pending_at"]) == base.NO_PENDING:
                swaps.append(t)
        return p, s, swaps

    p_async, s_async, swaps_a = run(blocking=False)
    p_block, s_block, swaps_b = run(blocking=True)
    assert swaps_a == swaps_b and len(swaps_a) >= 2, (swaps_a, swaps_b)
    for a, b in zip(jax.tree.leaves(p_async), jax.tree.leaves(p_block)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s_async), jax.tree.leaves(s_block)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", ["muon", "shampoo"])
def test_swap_serves_pending_buffer(name):
    """Before the swap the update consumes the ACTIVE buffer untouched;
    on the swap step the pending buffer (and its telemetry twin) becomes
    active and pending_at clears."""
    key = jax.random.PRNGKey(1)
    params, axes = _tree(key)
    cfg = _async_cfg(name, precond_swap_delay=2, precond_every=8)
    opt = make_optimizer(cfg, axes)
    p, s = params, opt.init(params)
    svc = base.AsyncPrecondService(opt, cfg)
    cache_key = "ortho" if name == "muon" else "Linv"

    def caches(state):
        slots, _ = base._flat_slots(state["leaves"])
        return [np.asarray(sl[cache_key]) for sl in slots
                if cache_key in sl]

    # bootstrap dispatch back-dates pending_at, so the first step swaps
    # immediately (it waits on its own preconditioner, like a blocking
    # first step would)
    s = svc.step_begin(s, 0, key, drift=0.0)
    assert int(s["pending_at"]) == -cfg.precond_swap_delay
    g = _grad_stream(key, params, 0)
    p, s = opt.update(g, s, p, 0, jax.random.PRNGKey(0), refresh=False)
    assert int(s["pending_at"]) == base.NO_PENDING
    for t in (1, 2):
        g = _grad_stream(key, params, t)
        p, s = opt.update(g, s, p, t, jax.random.PRNGKey(t), refresh=False)
    active_before = caches(s)
    # dispatch at t=3 (install_pending directly — the raw refresh-plane
    # mechanics, no service scheduling in the way): for the next
    # swap_delay steps the ACTIVE cache must stay bit-identical (no
    # in-step recompute, no early swap)
    s = base.install_pending(s, opt.refresh(s, jax.random.fold_in(key, 1)),
                             at_step=3)
    assert int(s["pending_at"]) == 3
    pend_vals = [np.asarray(sl[cache_key + "_p"]) for sl in
                 base._flat_slots(s["leaves"])[0] if cache_key + "_p" in sl]
    for t in (3, 4):
        g = _grad_stream(key, params, t)
        p, s = opt.update(g, s, p, t, jax.random.PRNGKey(t), refresh=False)
    # t=3: count 3 < 3+2 -> no swap; t=4: count 4 < 5 -> no swap
    assert int(s["pending_at"]) == 3
    for a, b in zip(caches(s), active_before):
        np.testing.assert_array_equal(a, b)
    # t=5: count 5 >= 3+2 -> swap; active now equals the dispatched
    # pending buffer exactly
    g = _grad_stream(key, params, 5)
    p, s = opt.update(g, s, p, 5, jax.random.PRNGKey(5), refresh=False)
    assert int(s["pending_at"]) == base.NO_PENDING
    for a, b in zip(caches(s), pend_vals):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------- zero-launch contract

def test_steady_state_zero_matfn_launches(monkeypatch):
    key = jax.random.PRNGKey(2)
    """The §12 contract: a FULL async trainer step — swap cond included —
    compiles with ZERO matrix-function kernel launches; all matfn work
    lives in the separately jitted refresh program."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    from repro.configs import get_smoke_config
    from repro.data import DataConfig, make_batch_fn
    from repro.kernels import ops
    from repro.models import build
    from repro.train.state import make_train_step, master_params

    cfg = get_smoke_config("gpt2-paper").replace(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, d_ff=128)
    model = build(cfg)
    ocfg = OptimizerConfig(
        name="muon", precond_every=4, precond_async=True,
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
    # steady-state step: zero launches, even with a swap pending
    state_pending = base.install_pending(
        state, opt.refresh(state, key), at_step=0)
    for s in (state, state_pending):
        n = ops.count_launches(
            lambda p, st, b: step_fn(p, st, b, step, False), params, s,
            batch)
        assert n == 0, n
    # ...while the refresh program itself carries all the matfn launches
    n_refresh = ops.count_launches(lambda s: opt.refresh(s, key), state)
    assert n_refresh > 0, n_refresh


# ------------------------------------------------------- drift trigger

def test_drift_trigger_fires_on_spectrum_shift():
    """Stationary stream: after warmup the drift proxy stays under the
    threshold and the clock (set far out) never fires — no refreshes.
    Then an adversarial spectrum shift (gradients re-drawn 30x larger in
    a rotated basis) drives the momentum — hence the drift proxy — up,
    and the trigger dispatches within a few steps."""
    key = jax.random.PRNGKey(3)
    params, axes = _tree(key)
    # momentum=0.5: the momentum reaches its fixed point within the
    # warmup window (0.5^12 ~ 2e-4), so the stationary phase is a clean
    # no-trigger baseline
    cfg = _async_cfg("muon", precond_every=1000, matfn_tol=1e-2,
                     precond_drift_slack=1.5, momentum=0.5)
    assert cfg.drift_threshold == pytest.approx(5e-3)
    opt = make_optimizer(cfg, axes)
    svc = base.AsyncPrecondService(opt, cfg)
    step = jax.jit(opt.update, static_argnums=(5,))
    p, s = params, opt.init(params)

    def one(t, scale, shift=False):
        nonlocal p, s
        drift = float(base.precond_drift(s))
        s = svc.step_begin(s, t, jax.random.fold_in(key, t), drift=drift)
        g = _grad_stream(key, params, 0 if not shift else t, scale=scale)
        p, s = step(g, s, p, t, jax.random.PRNGKey(42), False)

    # warmup: bootstrap + the early refreshes while rnorm settles
    for t in range(12):
        one(t, 0.1)
    settled = dict(svc.counters)
    # stationary phase: identical gradient every step -> momentum is at
    # its fixed point, dnorm accrues ~0 -> no triggers
    for t in range(12, 24):
        one(t, 0.1)
    assert svc.counters["refreshes"] == settled["refreshes"], \
        (settled, svc.counters)
    assert svc.counters["clock_triggered"] == 0
    # adversarial shift: fresh large gradients every step
    for t in range(24, 30):
        one(t, 3.0, shift=True)
    assert svc.counters["drift_triggered"] > settled["drift_triggered"], \
        (settled, svc.counters)
    assert svc.counters["clock_triggered"] == 0
    assert svc.matfn_telemetry["last_drift"] >= 0.0


# ------------------------------------------------- sharding rules

def test_pending_twin_shardings_match_active():
    """opt_state_shardings gives every pending twin the SAME sharding as
    its active buffer (the swap is then a local per-shard select) and
    replicates the pending_at scalar."""
    from jax.sharding import AxisType
    from repro.launch.sharding import replicated
    from repro.train.state import opt_state_shardings

    key = jax.random.PRNGKey(4)
    params, axes = _tree(key)
    cfg = _async_cfg("muon", matfn_tol=1e-2, precond_drift_slack=2.0)
    opt = make_optimizer(cfg, axes)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    shapes = jax.eval_shape(lambda: params)
    pshard = jax.tree.map(lambda _: replicated(mesh), params)
    sh = opt_state_shardings(mesh, opt, shapes, pshard)
    assert sh["pending_at"] == replicated(mesh)
    assert sh["count"] == replicated(mesh)
    for slot in base._flat_slots(sh["leaves"])[0]:
        if "ortho" in slot:
            assert slot["ortho_p"] == slot["ortho"]
            assert slot["dnorm"] == replicated(mesh)
            assert slot["rnorm"] == replicated(mesh)


SHARDED_SCRIPT = textwrap.dedent("""
    import os
    # pin CPU BEFORE jax imports: with libtpu in the image an unset
    # JAX_PLATFORMS makes jax probe the TPU metadata server for minutes
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.config import OptimizerConfig, PrismConfig
    from jax.sharding import AxisType
    from repro.optim import base, make_optimizer
    from repro.sharding_ctx import activation_sharding

    key = jax.random.PRNGKey(0)
    params = {"w1": jax.random.normal(key, (64, 32)),
              "w3": jax.random.normal(jax.random.fold_in(key, 2),
                                      (3, 48, 32)),
              "b": jax.random.normal(jax.random.fold_in(key, 4), (64,))}
    axes = {"w1": ("embed", "mlp"), "w3": ("layers", "embed", "mlp"),
            "b": ("embed",)}
    cfg = OptimizerConfig(name="muon", learning_rate=0.05,
                          precond_every=3, precond_async=True,
                          precond_swap_delay=1,
                          prism=PrismConfig(degree=2, iterations=4,
                                            warm_alpha_iters=1,
                                            sketch_dim=8))
    opt = make_optimizer(cfg, axes)

    def run(mesh_ctx):
        svc = base.AsyncPrecondService(opt, cfg)
        step = jax.jit(opt.update, static_argnums=(5,))
        p, s = params, opt.init(params)
        with mesh_ctx() if mesh_ctx else _null():
            for t in range(7):
                s = svc.step_begin(
                    s, t, jax.random.fold_in(jax.random.PRNGKey(7), t),
                    drift=float(base.precond_drift(s)))
                g = jax.tree.map(
                    lambda q: 0.1 * jax.random.normal(
                        jax.random.fold_in(jax.random.fold_in(key, t),
                                           q.size), q.shape), params)
                p, s = step(g, s, p, t, jax.random.PRNGKey(t), False)
        return p, svc

    from contextlib import contextmanager
    @contextmanager
    def _null():
        yield

    p_ref, _ = run(None)
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)

    @contextmanager
    def sharded():
        with mesh, activation_sharding(
                mesh, {"opt_layers": "model", "opt_rows": "data"}):
            yield

    p_sh, svc = run(sharded)
    assert svc.counters["refreshes"] >= 3, svc.counters
    for k in params:
        np.testing.assert_allclose(np.asarray(p_ref[k]),
                                   np.asarray(p_sh[k]),
                                   rtol=2e-5, atol=2e-5)
    print("ASYNC_SHARDED_OK")
""")


def test_sharded_double_buffer_parity_8dev():
    """Async double-buffered Muon under the 8-device (data, model) mesh
    equals the replicated run: pending twins shard like their active
    halves, the swap is a local select, and the sharded refresh program
    produces the same polars (§8 parity through the §12 plane)."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT],
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))),
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert "ASYNC_SHARDED_OK" in out.stdout, \
        out.stdout[-2000:] + out.stderr[-3000:]


# ------------------------------------------------------- trainer smoke

def test_trainer_async_run(tmp_path):
    """End-to-end async training: loss finite and decreasing, the service
    refreshes on its schedule, and precond_drift rides in the metrics."""
    from repro.configs import get_smoke_config
    from repro.data import DataConfig
    from repro.models import build
    from repro.train import Trainer

    cfg = get_smoke_config("gpt2-paper")
    model = build(cfg)
    ocfg = OptimizerConfig(
        name="muon", learning_rate=0.02, precond_every=3,
        precond_async=True, precond_swap_delay=1,
        prism=PrismConfig(degree=2, iterations=3, warm_alpha_iters=3,
                          sketch_dim=8))
    tcfg = TrainConfig(steps=10, checkpoint_dir=str(tmp_path),
                       checkpoint_every=5, log_every=100,
                       async_checkpoint=False)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                      global_batch=4, markov_rank=8)
    seen = {}
    tr = Trainer(model, ocfg, tcfg, dcfg)
    _, opt_state, losses = tr.run(
        on_metrics=lambda t, m: seen.update(m))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    assert "precond_drift" in seen
    tele = tr.matfn_telemetry
    assert tele["bootstrap"] == 1 and tele["refreshes"] >= 3, tele
    # checkpoints exclude the pending payloads
    from repro import checkpoint as ckpt
    step = ckpt.latest_step(str(tmp_path))
    data = np.load(os.path.join(str(tmp_path), f"step_{step:08d}",
                                "tree.npz"))
    assert not any(base.PENDING_STATE_KEYS.intersection(k.split("|"))
                   for k in data.files)
    # ...but pending_at itself IS saved (it is cleared on restore)
    assert any(k.endswith("pending_at") for k in data.files)
