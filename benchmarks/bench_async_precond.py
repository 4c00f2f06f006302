"""Async preconditioner service: steady-state cost + schedule quality
(DESIGN.md §12).

Two experiments on a Muon workload (a stack of layer weight matrices —
the transformer hot path):

1. **Step-cost decomposition.**  Times the async steady-state step (the
   ONLY compiled step variant under ``precond_async`` — swap cond
   included, zero matfn work), the standalone refresh program, and the
   legacy blocking refresh step (in-step chains).  The async service
   hides the whole refresh cost behind forward/backward, so the modeled
   async step time is the steady time; the blocking baseline pays
   ``steady + refresh`` every ``precond_every``-th step.  Launch counts
   (traced with the kernel path, skipped under REPRO_KERNEL_MODE=ref)
   document the §12 contract: ``blocking_launches_steady == 0`` — all
   matfn launches live in the refresh program.

2. **Drift-triggered vs fixed-clock schedule at an equal residual
   target.**  A piecewise-stationary gradient stream (segments of a
   fixed base gradient + small noise, spectrum shift at each boundary)
   drives two async services: a fixed clock with period K, and the drift
   trigger with the SAME certificate target (threshold set to the clock
   schedule's realized max drift) under a 10x-looser ceiling.  The drift
   schedule concentrates refreshes right after the shifts and skips the
   stationary stretches — fewer refreshes at the same max staleness
   residual (schema-enforced in BENCH_async_precond.json).

Writes the committed baseline BENCH_async_precond.json so later PRs
have a perf trajectory.
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
                   "BENCH_async_precond.json")

CELLS = [(256, 4), (512, 2)]       # (n, stacked layers)
SMOKE_CELLS = [(128, 2)]           # subset-scale: same row names
PERIOD = 4


def _make(n: int, layers: int, **kw) -> tuple:
    prism = PrismConfig(degree=2, iterations=3, warm_alpha_iters=1,
                        sketch_dim=8)
    kw.setdefault("precond_every", PERIOD)
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


def _grads(params, key):
    return jax.tree.map(
        lambda p: 0.1 * jax.random.normal(
            jax.random.fold_in(key, p.size), p.shape), params)


def _step_costs(n: int, layers: int) -> dict:
    key = jax.random.PRNGKey(1)
    # async: the steady step (refresh=False static) and the refresh plane
    acfg, aopt, params = _make(n, layers, precond_async=True)
    astate = aopt.init(params)
    g = _grads(params, key)
    step = jax.jit(aopt.update, static_argnums=(5,))
    steady_ms = 1e3 * time_call(
        lambda: step(g, astate, params, 0, key, False))
    refresh = jax.jit(aopt.refresh)
    refresh_ms = 1e3 * time_call(lambda: refresh(astate, key))
    # blocking baseline: the in-step refresh variant (refresh=True)
    scfg, sopt, _ = _make(n, layers)
    sstate = sopt.init(params)
    sstep = jax.jit(sopt.update, static_argnums=(5,))
    blocking_ms = 1e3 * time_call(
        lambda: sstep(g, sstate, params, 0, key, True))
    cell = {
        "n": n, "layers": layers, "period": PERIOD,
        "steady_ms": steady_ms, "refresh_ms": refresh_ms,
        "blocking_step_ms": blocking_ms,
        # the async service hides the refresh behind fwd/bwd: modeled
        # refresh-step speedup and the K-amortized mean-step speedup
        "speedup_refresh_step": blocking_ms / max(steady_ms, 1e-9),
        "speedup_amortized": (steady_ms + (blocking_ms - steady_ms)
                              / PERIOD) / max(steady_ms, 1e-9),
    }
    if os.environ.get("REPRO_KERNEL_MODE") != "ref":
        # counting only traces the kernel wrappers (never executes a
        # body), so pin interpret mode with the size cutoff disabled —
        # under the default "auto"/"ref" CPU mode no kernel is ever
        # dispatched and every count would be a vacuous 0
        prev = os.environ.get("REPRO_KERNEL_MODE")
        prev_cut = os.environ.get("REPRO_INTERPRET_MAX_ELEMS")
        os.environ["REPRO_KERNEL_MODE"] = "interpret"
        os.environ["REPRO_INTERPRET_MAX_ELEMS"] = "0"
        try:
            from repro.kernels import ops

            kacfg, kaopt, _ = _make(n, layers, precond_async=True)
            kastate = kaopt.init(params)
            pending = base.install_pending(
                kastate, kaopt.refresh(kastate, key), at_step=0)
            # §12 contract: zero matfn launches in the steady step, with
            # AND without a pending swap in flight
            cell["blocking_launches_steady"] = max(
                ops.count_launches(
                    lambda gg, s: kaopt.update(gg, s, params, 0, key,
                                               refresh=False), g, kastate),
                ops.count_launches(
                    lambda gg, s: kaopt.update(gg, s, params, 0, key,
                                               refresh=False), g, pending))
            cell["launches_refresh"] = ops.count_launches(
                lambda s: kaopt.refresh(s, key), kastate)
            kscfg, ksopt, _ = _make(n, layers)
            cell["launches_blocking_step"] = ops.count_launches(
                lambda gg, s: ksopt.update(gg, s, params, 0, key,
                                           refresh=True),
                g, ksopt.init(params))
        finally:
            for var, old in [("REPRO_KERNEL_MODE", prev),
                             ("REPRO_INTERPRET_MAX_ELEMS", prev_cut)]:
                if old is None:
                    os.environ.pop(var, None)
                else:
                    os.environ[var] = old
    emit(f"async_steady_n{n}_L{layers}", steady_ms * 1000,
         refresh_ms=round(refresh_ms, 3),
         blocking_ms=round(blocking_ms, 3),
         speedup_refresh_step=round(cell["speedup_refresh_step"], 2),
         launches_steady=cell.get("blocking_launches_steady", "skipped"))
    return cell


def _run_schedule(cfg, opt, params, steps: int, segment: int):
    """Drive one async service over the piecewise-stationary stream;
    returns (refreshes, max consumed drift)."""
    key = jax.random.PRNGKey(2)
    svc = base.AsyncPrecondService(opt, cfg)
    step = jax.jit(opt.update, static_argnums=(5,))
    p, s = params, opt.init(params)
    drift_max = 0.0
    for t in range(steps):
        drift = float(base.precond_drift(s))
        if t >= 2 * segment:
            # measure steady-state staleness only: skip the warmup
            # segments where rnorm is still settling from zero
            drift_max = max(drift_max, drift)
        s = svc.step_begin(s, t, jax.random.fold_in(key, t), drift=drift)
        base_key = jax.random.fold_in(key, 10_000 + t // segment)
        g = jax.tree.map(
            lambda q: 0.1 * jax.random.normal(
                jax.random.fold_in(base_key, q.size), q.shape)
            + 0.005 * jax.random.normal(
                jax.random.fold_in(jax.random.fold_in(key, t), q.size),
                q.shape), params)
        p, s = step(g, s, p, t, jax.random.PRNGKey(7), False)
    return svc.counters, drift_max


def _schedule_experiment() -> dict:
    n, layers = pick((128, 2), (64, 2))
    steps = pick(120, 40)
    segment = pick(30, 10)
    K = 6
    tol = 1e-3
    # fixed clock at period K (trigger disabled)
    ccfg, copt, params = _make(n, layers, precond_async=True,
                               precond_every=K, matfn_tol=tol,
                               momentum=0.5)
    clock, drift_max_clock = _run_schedule(ccfg, copt, params, steps,
                                           segment)
    # drift trigger at the SAME certificate target (threshold = the
    # clock schedule's realized max drift) under a 10x-looser ceiling
    slack = 1.0 + drift_max_clock / tol
    dcfg, dopt, _ = _make(n, layers, precond_async=True,
                          precond_every=10 * K, matfn_tol=tol,
                          precond_drift_slack=slack, momentum=0.5)
    drift, drift_max_drift = _run_schedule(dcfg, dopt, params, steps,
                                           segment)
    out = {
        "n": n, "layers": layers, "steps": steps, "segment": segment,
        "period_clock": K, "ceiling_drift": 10 * K,
        "drift_threshold": dcfg.drift_threshold,
        "refreshes_clock": clock["refreshes"],
        "refreshes_drift": drift["refreshes"],
        "drift_triggered": drift["drift_triggered"],
        "clock_triggered_in_drift_run": drift["clock_triggered"],
        "drift_max_clock": drift_max_clock,
        "drift_max_drift": drift_max_drift,
    }
    emit("async_schedule", 0.0,
         refreshes_clock=clock["refreshes"],
         refreshes_drift=drift["refreshes"],
         drift_max_clock=round(drift_max_clock, 5),
         drift_max_drift=round(drift_max_drift, 5))
    return out


def run(write_json: bool = True) -> None:
    cells = [_step_costs(n, L) for n, L in pick(CELLS, SMOKE_CELLS)]
    sched = _schedule_experiment()
    if not (write_json and not smoke()):
        return
    out = {
        "benchmark": "async_precond",
        "backend": jax.default_backend(),
        "period": PERIOD,
        "notes": [
            "steady_ms: the async steady-state step (the only compiled "
            "variant under precond_async) — zero matfn launches, swap "
            "cond included",
            "refresh_ms: the standalone jitted refresh program the "
            "service overlaps with fwd/bwd",
            "blocking_step_ms: the legacy in-step refresh variant "
            "(refresh=True) the async plane replaces",
            "CPU wall clock understates the async win: on an "
            "accelerator the refresh overlaps compute instead of "
            "timesharing host cores",
            "schedule: drift trigger vs fixed clock on a piecewise-"
            "stationary stream at an equal max-staleness target",
        ],
        "results": cells,
        "schedule": sched,
    }
    with open(OUT, "w") as f:
        json.dump(out, f, indent=1)
    print(f"# wrote {OUT}", flush=True)
