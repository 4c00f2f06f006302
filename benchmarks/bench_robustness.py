"""Robustness guardians: guard overhead + recovery accounting (§15).

Two experiments on the Muon hot path:

1. **Guard overhead.**  The §15 guards are selects riding existing
   chains, so they must be launch-neutral: the divergence detector adds
   ZERO launches to the adaptive matfn plan (status is read from the
   certificate the loop already computes), and the skip-step wrapper
   adds ZERO matrix-function launches to the async steady-state step
   (which stays at the §12 contract's zero).  Wall-clock overhead of the
   wrapped steady step is reported alongside (a few fused reductions +
   one select per buffer).

2. **Recovery accounting.**  A gradient stream with NaN bursts injected
   every few steps: ``bad_steps`` must count EXACTLY the injected steps
   (no false positives on the healthy steps, none missed) with the final
   params/state finite.  A poisoned refresh stream drives the validated
   async install through its discard -> backoff retry -> degrade -> clean
   recovery ladder; the counters land in the baseline so a telemetry
   regression is visible in review.

Writes the committed baseline BENCH_robustness.json
(benchmarks/validate_bench.py enforces the invariants above on every
PR).
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

from benchmarks.common import emit, pick, smoke, time_call
from repro.config import OptimizerConfig, PrismConfig
from repro.optim import base, make_optimizer

OUT = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                   "BENCH_robustness.json")

CELLS = [(256, 4), (512, 2)]
SMOKE_CELLS = [(128, 2)]
PERIOD = 4


def _make(n: int, layers: int, **kw):
    prism = PrismConfig(degree=2, iterations=3, warm_alpha_iters=1,
                        sketch_dim=8, tol=1e-2)
    kw.setdefault("precond_every", PERIOD)
    kw.setdefault("matfn_tol", 1e-2)
    cfg = OptimizerConfig(name="muon", learning_rate=0.02, prism=prism,
                          **kw)
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (layers, n, n)),
              "o": jax.random.normal(jax.random.fold_in(key, 1),
                                     (n, 2 * n)),
              "b": jnp.zeros((n,))}
    axes = {"w": ("layers", "embed", "mlp"), "o": ("embed", "mlp"),
            "b": ("embed",)}
    return cfg, make_optimizer(cfg, axes), params


def _grads(params, key, poison: bool = False):
    g = jax.tree.map(
        lambda p: 0.1 * jax.random.normal(
            jax.random.fold_in(key, p.size), p.shape), params)
    if poison:
        g = jax.tree.map(lambda x: x * jnp.nan, g)
    return g


def _guard_overhead(n: int, layers: int) -> dict:
    """Steady-state step cost + launch counts, guards off vs on."""
    key = jax.random.PRNGKey(1)
    cells = {}
    for skip in (False, True):
        cfg, opt, params = _make(n, layers, precond_async=True,
                                 skip_nonfinite=skip)
        state = opt.init(params)
        g = _grads(params, key)
        step = jax.jit(opt.update, static_argnums=(5,))
        cells[skip] = 1e3 * time_call(
            lambda: step(g, state, params, 0, key, False))
    cell = {
        "n": n, "layers": layers, "period": PERIOD,
        "steady_ms_bare": cells[False], "steady_ms_guarded": cells[True],
        "overhead_pct": 100.0 * (cells[True] / max(cells[False], 1e-9)
                                 - 1.0),
    }
    if os.environ.get("REPRO_KERNEL_MODE") != "ref":
        prev = os.environ.get("REPRO_KERNEL_MODE")
        prev_cut = os.environ.get("REPRO_INTERPRET_MAX_ELEMS")
        os.environ["REPRO_KERNEL_MODE"] = "interpret"
        os.environ["REPRO_INTERPRET_MAX_ELEMS"] = "0"
        try:
            from repro.core import matfn
            from repro.kernels import ops

            params_k = None
            for skip in (False, True):
                kcfg, kopt, params_k = _make(n, layers,
                                             precond_async=True,
                                             skip_nonfinite=skip)
                kstate = kopt.init(params_k)
                gk = _grads(params_k, key)
                tag = "guarded" if skip else "bare"
                cell[f"steady_matfn_launches_{tag}"] = ops.count_launches(
                    lambda gg, s: kopt.update(gg, s, params_k, 0, key,
                                              refresh=False), gk, kstate)
            # matfn-level: the status read is launch-free too
            mcfg = PrismConfig(degree=2, iterations=3, warm_alpha_iters=1,
                               sketch_dim=8, tol=1e-2)
            A = jnp.zeros((4, n, n))
            cell["matfn_launches_plain"] = ops.count_launches(
                lambda a: matfn.polar(a, method="prism", cfg=mcfg,
                                      key=key), A)
            cell["matfn_launches_status"] = ops.count_launches(
                lambda a: matfn.polar(a, method="prism", cfg=mcfg,
                                      key=key, return_iters=True,
                                      return_status=True), A)
        finally:
            for var, old in [("REPRO_KERNEL_MODE", prev),
                             ("REPRO_INTERPRET_MAX_ELEMS", prev_cut)]:
                if old is None:
                    os.environ.pop(var, None)
                else:
                    os.environ[var] = old
    emit(f"robustness_steady_n{n}_L{layers}",
         cell["steady_ms_guarded"] * 1000,
         overhead_pct=round(cell["overhead_pct"], 2),
         launches=cell.get("steady_matfn_launches_guarded", "skipped"))
    return cell


def _recovery_experiment() -> dict:
    """NaN bursts through the skip-step guard + a poisoned refresh
    stream through the validated async install."""
    n, layers = pick((128, 2), (64, 2))
    steps = pick(24, 12)
    inject_every = 4
    key = jax.random.PRNGKey(2)
    cfg, opt, params = _make(n, layers, skip_nonfinite=True)
    state = opt.init(params)
    step = jax.jit(opt.update, static_argnums=(5,))
    p = params
    injected = 0
    for t in range(steps):
        poison = t % inject_every == 2
        injected += int(poison)
        g = _grads(p, jax.random.fold_in(key, t), poison=poison)
        p, state = step(g, state, p, t, jax.random.fold_in(key, t),
                        None)
    finite = all(
        bool(jnp.all(jnp.isfinite(l.astype(jnp.float32))))
        for l in jax.tree.leaves(p) + jax.tree.leaves(state))
    out = {
        "steps": steps, "injected": injected,
        "bad_steps": int(state["bad_steps"]),
        "final_finite": bool(finite),
    }

    # validated async install: fail -> retry -> degrade -> recover
    acfg, aopt, aparams = _make(n, layers, precond_async=True,
                                precond_swap_delay=1,
                                precond_max_retries=2,
                                precond_drift_slack=2.0)
    svc = base.AsyncPrecondService(aopt, acfg)
    astep = jax.jit(aopt.update, static_argnums=(5,))
    real = svc._refresh
    poison_box = {"on": False}
    svc._refresh = lambda s, k: (
        jax.tree.map(lambda x: x * jnp.nan
                     if jnp.issubdtype(x.dtype, jnp.floating) else x,
                     real(s, k)) if poison_box["on"] else real(s, k))
    astate = aopt.init(aparams)
    ap = aparams
    recovered_at = None
    for t in range(20):
        poison_box["on"] = 1 <= t <= 8 and svc.counters["degraded"] == 0
        astate = svc.step_begin(astate, t, jax.random.fold_in(key, t),
                                drift=1e9)
        if not poison_box["on"] and t > 1 and recovered_at is None \
                and int(astate["pending_at"]) != base.NO_PENDING:
            recovered_at = t
        g = _grads(ap, jax.random.fold_in(key, 100 + t))
        ap, astate = astep(g, astate, ap, t,
                           jax.random.fold_in(key, t), False)
    out.update({
        "discarded": svc.counters["discarded"],
        "retries": svc.counters["retries"],
        "degraded": svc.counters["degraded"],
        "recovered_install": recovered_at is not None,
    })
    emit("robustness_recovery", 0.0,
         bad_steps=out["bad_steps"], injected=out["injected"],
         discarded=out["discarded"], degraded=out["degraded"])
    return out


def run(write_json: bool = True) -> None:
    cells = [_guard_overhead(n, L)
             for n, L in pick(CELLS, SMOKE_CELLS)]
    recovery = _recovery_experiment()
    if not (write_json and not smoke()):
        return
    doc = {
        "benchmark": "robustness",
        "backend": jax.default_backend(),
        "period": PERIOD,
        "notes": [
            "guards are selects riding existing chains: launch-neutral "
            "by construction (schema-enforced)",
            "steady_ms_* are jit-warmed medians on this container's "
            "CPU; overhead_pct is the skip-step wrapper's cost",
            "recovery: bad_steps must equal injected NaN bursts; the "
            "async ladder is discard -> retry -> degrade -> recover",
        ],
        "results": cells,
        "recovery": recovery,
    }
    with open(OUT, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# wrote {OUT}", flush=True)


if __name__ == "__main__":
    run()
