"""Numerics guardian (DESIGN.md §15): adversarial spectra, quarantine,
skip-step rollback, validated async install, checkpoint integrity.

The containment invariant under test: FINITE INPUT -> FINITE OUTPUT for
every matfn family, no matter how hostile the spectrum — a slice that
cannot converge exits with a truthful status code (MAXITER/QUARANTINED)
and a bounded iterate instead of poisoning the caller.  The guards add
ZERO matrix-function launches (the §10/§12 contracts are guard-blind).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import OptimizerConfig, PrismConfig
from repro.core import matfn, prism
from repro.core import random_matrices as rm
from repro.optim import base, make_optimizer

pytestmark = pytest.mark.tier1

VALID = {int(prism.STATUS_OK), int(prism.STATUS_MAXITER),
         int(prism.STATUS_QUARANTINED)}


def _cfg(tol, dtype="float32", iters=10, warm=1, **kw):
    return PrismConfig(degree=2, iterations=iters, warm_alpha_iters=warm,
                       sketch_dim=8, dtype=dtype, tol=tol, **kw)


def _finite(x) -> bool:
    return bool(np.all(np.isfinite(np.asarray(x, np.float32))))


# --------------------------- adversarial spectra x families x dtypes

def _spectrum(key, name: str, n: int = 32, spd: bool = False):
    """Hostile test matrices: exact zero (no signal), a rank-1 spike
    (maximally singular with one huge direction), and kappa ~ 1e8
    (at/under fp32's certification floor)."""
    if name == "zero":
        return jnp.zeros((n, n))
    if name == "rank1_spike":
        sig = jnp.zeros((n,)).at[0].set(1e4)
        A = rm.with_spectrum(key, n, n, sig)
    else:  # kappa1e8
        A = rm.log_uniform_spectrum(key, n, n, 1e-8)
    if spd:
        A = A @ A.T / 2 + 1e-30 * jnp.eye(n)
    return A


SPECTRA = ("zero", "rank1_spike", "kappa1e8")
DTYPES = ("float32", "bfloat16")


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("name", SPECTRA)
def test_polar_containment(key, name, dtype):
    A = _spectrum(key, name)
    X, it, st = matfn.polar(A, method="prism", cfg=_cfg(1e-2, dtype),
                            key=key, return_iters=True,
                            return_status=True)
    assert _finite(X), (name, dtype)
    assert st.dtype == jnp.int8 and int(st) in VALID
    assert 0 <= int(it) <= 10
    if name == "zero":
        # no signal can never certify — the guardian must say so
        assert int(st) != int(prism.STATUS_OK)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("name", SPECTRA)
def test_chebyshev_inv_containment(key, name, dtype):
    A = _spectrum(key, name, spd=True)
    X, it, st = matfn.inv(A, iters=10, key=key, tol=1e-2,
                          dtype=jnp.dtype(dtype), return_iters=True,
                          return_status=True)
    assert _finite(X), (name, dtype)
    assert st.dtype == jnp.int8 and int(st) in VALID
    if name in ("zero", "rank1_spike"):
        # singular input: inversion must NOT report success
        assert int(st) != int(prism.STATUS_OK)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("name", SPECTRA)
def test_inverse_newton_containment(key, name, dtype):
    A = _spectrum(key, name, spd=True)
    X, it, st = matfn.inv_proot(A, p=4, iters=10, key=key, tol=1e-2,
                                dtype=jnp.dtype(dtype),
                                return_iters=True, return_status=True)
    assert _finite(X), (name, dtype)
    assert st.dtype == jnp.int8 and int(st) in VALID


def test_healthy_input_certifies_ok(key):
    """Control: a benign spectrum certifies with STATUS_OK in every
    family — the guards never flag healthy work."""
    A = rm.log_uniform_spectrum(key, 32, 32, 1e-1)
    S = A @ A.T / 2 + 0.1 * jnp.eye(32)
    _, _, st_p = matfn.polar(A, method="prism", cfg=_cfg(1e-2, iters=14),
                             key=key, return_iters=True,
                             return_status=True)
    _, _, st_i = matfn.inv(S, iters=30, key=key, tol=1e-2,
                           return_iters=True, return_status=True)
    _, _, st_n = matfn.inv_proot(S, p=4, iters=30, key=key, tol=1e-2,
                                 return_iters=True, return_status=True)
    assert int(st_p) == int(st_i) == int(st_n) == int(prism.STATUS_OK)


# ------------------------------------------------------- quarantine

def test_forced_divergence_quarantines(key):
    """alpha pinned to 50 makes every fitted NS step diverge: the
    detector must quarantine (status 2) and hand back a FINITE iterate
    (the pre-divergence snapshot) instead of the exploded one."""
    A = rm.log_uniform_spectrum(key, 32, 32, 1e-2)
    X, it, st = matfn.polar(A, method="prism",
                            cfg=_cfg(1e-6, iters=8,
                                     alpha_bounds=(50.0, 50.0)),
                            key=key, return_iters=True,
                            return_status=True)
    assert int(st) == int(prism.STATUS_QUARANTINED)
    assert _finite(X)


def test_quarantine_is_per_slice(key):
    """Batched run, one hostile slice: containment is per-slice — the
    healthy slice still certifies STATUS_OK and converges to the true
    polar factor (oracle residual), unpolluted by its neighbour."""
    good = rm.log_uniform_spectrum(key, 32, 32, 1e-1)
    bad = jnp.zeros((32, 32))  # can never certify
    Xb, _, stb = matfn.polar(jnp.stack([good, bad]), method="prism",
                             cfg=_cfg(1e-2, iters=14), key=key,
                             return_iters=True, return_status=True)
    assert int(stb[0]) == int(prism.STATUS_OK)
    assert int(stb[1]) != int(prism.STATUS_OK)
    assert _finite(Xb)
    G = np.asarray(Xb[0].T @ Xb[0])
    assert np.linalg.norm(np.eye(32) - G) < 5e-2


# ------------------------------------------- launch contracts (guards on)

def _count(fn, *args):
    from repro.kernels import ops

    return ops.count_launches(fn, *args)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_launch_contract_with_status(monkeypatch, key, dtype):
    """The divergence detector rides the existing certificate: asking
    for the status changes the traced launch count by ZERO (fused tier:
    warm tail 1 + fitted body 2, same as without the guard)."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    cfg = _cfg(1e-2, dtype=dtype, iters=5)
    A = jnp.zeros((4, 64, 48), jnp.dtype(dtype))
    n_plain = _count(lambda a: matfn.polar(a, method="prism", cfg=cfg,
                                           key=key), A)
    n_status = _count(lambda a: matfn.polar(a, method="prism", cfg=cfg,
                                            key=key, return_iters=True,
                                            return_status=True), A)
    assert n_status == n_plain == 1 + 2


def test_skip_step_adds_zero_matfn_launches(monkeypatch, key):
    """The §15 skip-step guard is a per-buffer select: the wrapped
    optimizer's steady-state update compiles with the SAME launch count
    as the bare one (zero matrix-function launches, §12 contract)."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "interpret")
    params = {"w": jax.random.normal(key, (64, 32))}
    axes = {"w": ("embed", "mlp")}
    grads = jax.tree.map(jnp.ones_like, params)
    counts = {}
    for skip in (False, True):
        ocfg = OptimizerConfig(
            name="muon", precond_every=4, skip_nonfinite=skip,
            prism=PrismConfig(degree=2, iterations=2, warm_alpha_iters=1,
                              sketch_dim=8))
        opt = make_optimizer(ocfg, axes)
        state = opt.init(params)
        counts[skip] = _count(
            lambda g, s, p: opt.update(g, s, p, 1, key, refresh=False),
            grads, state, params)
    assert counts[True] == counts[False] == 0, counts


# ---------------------------------------------------- skip-step guard

def _tiny_opt(skip=True):
    ocfg = OptimizerConfig(name="muon", matfn_tol=1e-2,
                           skip_nonfinite=skip,
                           prism=_cfg(1e-2, iters=4))
    params = {"w": jax.random.normal(jax.random.PRNGKey(7), (32, 16)),
              "b": jnp.ones((16,))}
    axes = {"w": ("embed", "mlp"), "b": ("mlp",)}
    return make_optimizer(ocfg, axes), params


def test_skip_step_rolls_back_bitwise(key):
    opt, params = _tiny_opt()
    state = opt.init(params)
    g_good = jax.tree.map(jnp.ones_like, params)
    p1, s1 = opt.update(g_good, state, params, 0, key)
    g_bad = jax.tree.map(lambda g: g * jnp.nan, g_good)
    p2, s2 = opt.update(g_bad, s1, p1, 1, key)
    # params AND every state buffer identical to the pre-step iterate
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(s2["bad_steps"]) == 1
    assert int(s2["count"]) == int(s1["count"])  # clock holds on a skip
    # ...and the run continues: the next good step applies normally
    p3, s3 = opt.update(g_good, s2, p2, 2, key)
    assert int(s3["bad_steps"]) == 1
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(p3)))


def test_skip_step_catches_inf_gradient(key):
    opt, params = _tiny_opt()
    state = opt.init(params)
    g_inf = jax.tree.map(
        lambda p: jnp.full_like(p, jnp.inf), params)
    p1, s1 = opt.update(g_inf, state, params, 0, key)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(s1["bad_steps"]) == 1
    assert all(_finite(l) for l in jax.tree.leaves(s1))


def test_clip_passes_nonfinite_through_unscaled():
    """A non-finite global norm must NOT zero (inf => scale 0) or NaN
    the gradients — the skip-step guard downstream needs to SEE the
    poison to count it."""
    g = {"a": jnp.array([1.0, jnp.inf]), "b": jnp.ones((2,))}
    clipped, gn = base.clip_by_global_norm(g, 1.0)
    assert not np.isfinite(float(gn))
    np.testing.assert_array_equal(np.asarray(clipped["b"]),
                                  np.ones((2,)))
    g0 = {"a": jnp.zeros((3,))}
    c0, gn0 = base.clip_by_global_norm(g0, 1.0)
    assert float(gn0) == 0.0 and _finite(c0["a"])


# ------------------------------------- validated async install (§15)

def _poisonable_service():
    ocfg = OptimizerConfig(
        name="muon", matfn_tol=1e-2, precond_every=8,
        precond_async=True, precond_swap_delay=1, precond_max_retries=2,
        precond_drift_slack=2.0,  # drift trigger armed (threshold 1e-2)
        prism=_cfg(1e-2, iters=3))
    params = {"w": jax.random.normal(jax.random.PRNGKey(3), (32, 16))}
    opt = make_optimizer(ocfg, {"w": ("embed", "mlp")})
    svc = base.AsyncPrecondService(opt, ocfg)
    real = svc._refresh
    poison = {"on": False}

    def maybe_poisoned(state, k):
        p = real(state, k)
        if poison["on"]:
            p = jax.tree.map(
                lambda x: x * jnp.nan
                if jnp.issubdtype(x.dtype, jnp.floating) else x, p)
        return p

    svc._refresh = maybe_poisoned
    return opt, svc, opt.init(params), poison


def _active_leaves(state):
    """Non-pending state leaves: the discarded twin's payload stays in
    the inert ``*_p`` buffers (pending_at = NO_PENDING keeps the swap
    from ever consuming it), so only the ACTIVE plane must stay clean."""
    flat = jax.tree_util.tree_flatten_with_path(state)[0]
    return [l for path, l in flat
            if not any(str(getattr(p, "key", "")) in
                       base.PENDING_STATE_KEYS for p in path)]


def test_async_poisoned_buffer_never_installs():
    """A non-finite refresh result is discarded before the swap: the
    pending plane is marked stale and a backoff retry is scheduled."""
    opt, svc, state, poison = _poisonable_service()
    poison["on"] = True
    state = svc.step_begin(state, 0, jax.random.PRNGKey(0))
    # bootstrap validates immediately (its swap fires this very step)
    assert svc.counters["discarded"] == 1 and svc.failures == 1
    assert int(state["pending_at"]) == base.NO_PENDING
    assert all(_finite(l) for l in _active_leaves(state))


def test_async_retry_backoff_then_degrade():
    """Consecutive failures: discard -> backoff retry -> after
    max_retries the slot DEGRADES (loud counter, no retry storm) and
    keeps serving the active buffer; the next clean refresh recovers."""
    opt, svc, state, poison = _poisonable_service()
    key = jax.random.PRNGKey(0)
    # one clean bootstrap first: active buffers installed and swapped
    state = svc.step_begin(state, 0, key)
    grads = {"w": jnp.ones((32, 16))}
    params = {"w": jnp.zeros((32, 16))}
    params, state = opt.update(grads, state, params, 0, key,
                               refresh=False)
    assert svc.counters["refreshes"] == 1
    poison["on"] = True
    seen = []
    for t in range(1, 16):
        state = svc.step_begin(state, t, jax.random.fold_in(key, t),
                               drift=1e9)  # drift demands a refresh
        seen.append(svc.counters.copy())
        params, state = opt.update(grads, state, params, t,
                                   jax.random.fold_in(key, t),
                                   refresh=False)
        if svc.counters["degraded"]:
            break
    assert svc.counters["discarded"] == 2  # initial attempt + 1 retry
    assert svc.counters["retries"] >= 1
    assert svc.counters["degraded"] == 1
    # degraded: in-flight pending dropped, active plane still finite
    assert int(state["pending_at"]) == base.NO_PENDING
    assert all(_finite(l) for l in _active_leaves(state))
    # recovery: the next trigger dispatches a clean buffer that installs
    poison["on"] = False
    t0 = t + 1
    for t in range(t0, t0 + 4):
        state = svc.step_begin(state, t, jax.random.fold_in(key, t),
                               drift=1e9)
        if int(state["pending_at"]) != base.NO_PENDING:
            break
    assert int(state["pending_at"]) != base.NO_PENDING
    assert svc.counters["discarded"] == 2  # clean install, no new discard


# ------------------------------------------- checkpoint integrity (§15)

def _tree(x=0.0):
    return {"w": np.full((4, 3), 1.0 + x, np.float32),
            "n": np.arange(5) + int(x)}


def test_checkpoint_crc_detects_bit_rot(tmp_path):
    from repro import checkpoint as ckpt
    from repro.train.chaos import corrupt_checkpoint

    d = str(tmp_path)
    ckpt.save(d, 2, _tree(0.0))
    ckpt.save(d, 4, _tree(1.0))
    assert ckpt.verify_step(d, 4)
    corrupt_checkpoint(d, 4)
    assert not ckpt.verify_step(d, 4)
    assert ckpt.verify_step(d, 2)
    # newest-valid fallback: restore(None) lands on step 2...
    step, out = ckpt.restore(d, _tree())
    assert step == 2
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  _tree(0.0)["w"])
    # ...but an EXPLICITLY requested corrupt step must raise
    with pytest.raises(ValueError, match="crc32"):
        ckpt.restore(d, _tree(), step=4)


def test_checkpoint_without_manifest_is_not_complete(tmp_path):
    from repro import checkpoint as ckpt
    from repro.checkpoint.checkpoint import _complete_steps

    d = str(tmp_path)
    ckpt.save(d, 2, _tree())
    os.remove(os.path.join(d, "step_00000002", "MANIFEST"))
    assert _complete_steps(d) == []
    assert ckpt.latest_step(d) is None


def test_checkpoint_all_corrupt_raises(tmp_path):
    from repro import checkpoint as ckpt
    from repro.train.chaos import corrupt_checkpoint

    d = str(tmp_path)
    ckpt.save(d, 2, _tree())
    corrupt_checkpoint(d, 2)
    with pytest.raises(FileNotFoundError, match="uncorrupted"):
        ckpt.restore(d, _tree())
