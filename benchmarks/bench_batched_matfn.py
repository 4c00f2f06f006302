"""Per-leaf loop vs shape-bucketed batched PRISM polar (DESIGN.md §7),
with a dtype axis for the mixed-precision engine (DESIGN.md §9).

The workload models Muon over a stack of B same-shape layer weight
matrices (the transformer hot path): the per-leaf engine calls
``matfn.polar`` once per matrix inside one jit (B unrolled chains), the
bucketed engine stacks the leaves and runs ONE batched chain.

Reported per (n, B) cell:
  * wall clock per optimizer-step-equivalent call (ref-mode jnp GEMMs —
    the honest CPU number; on TPU the same dispatch structure holds),
    for BOTH matfn dtypes: ``bucketed_ms`` (fp32) and
    ``bucketed_bf16_ms`` (bf16 compute / fp32 accumulate),
  * the modeled HBM bytes one fitted PRISM-NS iteration streams over the
    bucket per dtype (``hbm_bytes_fp32`` / ``hbm_bytes_bf16``) — the
    accelerator-transferable number: bf16 operands halve chain traffic
    while accumulators/traces stay fp32.  On CPU, XLA emulates bf16 via
    fp32 upcasts, so bf16 wall clock is expected NEUTRAL-to-slower here
    (``bf16_speedup`` documents it); the HBM model is the TPU claim,
  * compile time of the first call (B unrolled chains vs one),
  * Pallas launches per step (counted by tracing with
    REPRO_KERNEL_MODE=interpret and the interpret-size cutoff disabled —
    counting only traces), on the tier the shape picks (``fused_fits_*``):
    per-leaf scales with B, bucketed stays constant, and the count is
    dtype-independent,
  * the §10 fused-iteration tier's modeled HBM bytes
    (``hbm_bytes_fused_*`` per fitted iteration, ``hbm_bytes_warm_tail_*``
    per whole tail) next to the §7 numbers.

The ``adaptive`` section (DESIGN.md §11) documents instance-adaptive
iteration counts: at one shared residual target ``tol``, a
well-conditioned Gaussian bucket certifies in strictly fewer mean
iterations (``iters_mean``) than the fixed count a certificate-free
engine must provision for the same target — which is what the
ill-conditioned bucket's slowest member needed (``iters_max_ill``, the
fixed-iters baseline).  ``resid_max*`` record the oracle residuals so
"equal residual targets" is checkable in the committed baseline.

Writes the committed baseline BENCH_batched_matfn.json so later PRs have
a perf trajectory.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, pick, smoke
from repro.config import OptimizerConfig, PrismConfig
from repro.core import matfn
from repro.optim import bucketing

SIZES = [256, 1024]
BATCHES = [1, 8, 32]
# smoke sweeps are SUBSETS of the full grids, so every smoke CSV row has
# a same-named full-run/baseline counterpart
SMOKE_SIZES = [256]
SMOKE_BATCHES = [1, 8]
OUT = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                   "BENCH_batched_matfn.json")


def _prism_cfg(n: int, dtype: str = "float32") -> PrismConfig:
    return PrismConfig(degree=2, iterations=3 if n <= 256 else 2,
                       warm_alpha_iters=1, sketch_dim=8, dtype=dtype)


def _engines(n: int, dtype: str = "float32"):
    cfg = _prism_cfg(n, dtype)

    def per_leaf(views, key):
        return [matfn.polar(v, method="prism", cfg=cfg,
                            key=jax.random.fold_in(key, i))
                for i, v in enumerate(views)]

    def bucketed(views, key):
        ocfg = OptimizerConfig(prism=cfg, matfn_dtype=dtype)
        return bucketing.polar_bucketed(views, ocfg, key)

    return per_leaf, bucketed


def hbm_bytes_per_iter(n: int, B: int, dtype: str, degree: int = 2,
                       sketch_pad: int = 128) -> int:
    """Modeled HBM bytes one fitted PRISM-NS iteration streams for a
    [B, n, n] bucket in the given compute dtype (DESIGN.md §9), on the
    §7 batch-grid tier.

    gram reads X once and writes R; the fused sketch chain re-reads R
    once per power (V stays in VMEM); each of the d Horner GEMMs reads
    (acc, R, X) and writes acc.  Traces/alphas are O(p) fp32 scalars —
    negligible and dtype-pinned, so they are omitted: operand bytes are
    the whole story, which is exactly why bf16 halves the number.
    """
    item = 2 if dtype == "bfloat16" else 4
    mats = B * n * n
    max_power = 4 * degree + 2
    gram = 2 * mats                      # read X, write R
    chain = max_power * mats + B * n * sketch_pad  # R per power + St once
    horner = degree * 4 * mats           # read acc, R, X; write acc
    return item * (gram + chain + horner)


def hbm_bytes_per_iter_fused(n: int, B: int, dtype: str,
                             sketch_pad: int = 128) -> int:
    """Modeled HBM bytes one FITTED iteration streams on the fused tier
    (DESIGN.md §10): launch 1 reads X and St and writes R (the chain's V
    never leaves VMEM — R is formed and consumed in-launch, so the
    max_power re-reads of the §7 model vanish); launch 2 reads X and R
    and writes X.  Independent of degree (the Horner accumulator stays
    in VMEM) and of max_power.
    """
    item = 2 if dtype == "bfloat16" else 4
    mats = B * n * n
    return item * (5 * mats + B * n * sketch_pad)


def hbm_bytes_warm_tail(n: int, B: int, dtype: str) -> int:
    """Modeled HBM bytes of an ENTIRE fused warm tail: one read + one
    write of X, however many iterations it spans (§10).  The §7 tier
    streams ~2(1+d) matrices per warm iteration instead."""
    item = 2 if dtype == "bfloat16" else 4
    return item * 2 * B * n * n


def _count_launches(fn, views, key) -> int:
    from repro.kernels import ops

    return ops.count_launches(lambda vs: fn(vs, key), views)


# adaptive-section sweep (small n: the ill-conditioned bucket runs its
# full budget of ref-mode O(n^3) iterations on CPU)
ADAPTIVE_SIZES = [64, 256]
SMOKE_ADAPTIVE_SIZES = [64]
ADAPTIVE_B = 8
ADAPTIVE_TOL = 2e-2
ADAPTIVE_BUDGET = 16


def run_adaptive(key):
    """Instance-adaptive iteration counts (DESIGN.md §11): one residual
    target, two spectra — Gaussian certifies early, near-rank-deficient
    sets the fixed-iters baseline a certificate-free engine would run."""
    import numpy as np

    from repro.core import random_matrices as rm

    rows = []
    for n in pick(ADAPTIVE_SIZES, SMOKE_ADAPTIVE_SIZES):
        acfg = PrismConfig(degree=2, iterations=ADAPTIVE_BUDGET,
                           warm_alpha_iters=1, sketch_dim=8,
                           tol=ADAPTIVE_TOL)
        gauss = jnp.stack([rm.gaussian(jax.random.fold_in(key, 300 + i),
                                       n, n) for i in range(ADAPTIVE_B)])
        ill = jnp.stack([rm.log_uniform_spectrum(
            jax.random.fold_in(key, 400 + i), n, n, 1e-4)
            for i in range(ADAPTIVE_B)])

        def resid(A, X):
            G = jnp.swapaxes(X, -1, -2) @ X
            return jnp.linalg.norm(jnp.eye(n) - G, axis=(-2, -1))

        Xg, it_g = matfn.polar(gauss, method="prism", cfg=acfg, key=key,
                               return_iters=True)
        Xi, it_i = matfn.polar(ill, method="prism", cfg=acfg, key=key,
                               return_iters=True)
        it_g, it_i = np.asarray(it_g), np.asarray(it_i)
        row = {"n": n, "B": ADAPTIVE_B, "tol": ADAPTIVE_TOL,
               "iters_budget": ADAPTIVE_BUDGET,
               "iters_mean": round(float(it_g.mean()), 2),
               "iters_max": int(it_g.max()),
               "iters_mean_ill": round(float(it_i.mean()), 2),
               "iters_max_ill": int(it_i.max()),
               "resid_max": round(float(jnp.max(resid(gauss, Xg))), 4),
               "resid_max_ill": round(float(jnp.max(resid(ill, Xi))), 4)}
        rows.append(row)
        emit(f"batched_matfn_adaptive_n{n}", row["iters_mean"],
             iters_mean=row["iters_mean"], iters_max=row["iters_max"],
             iters_mean_ill=row["iters_mean_ill"],
             iters_max_ill=row["iters_max_ill"],
             iters_budget=ADAPTIVE_BUDGET)
    return rows


def run(write_json: bool = True):
    key = jax.random.PRNGKey(0)
    results = []
    # CI smoke runs pinned to REPRO_KERNEL_MODE=ref: skip the interpret-
    # mode launch-count pass there so the benchmark never touches the
    # Pallas interpreter on runners where its Python cost dominates (the
    # count is a dispatch-structure invariant, asserted by
    # tests/test_bucketing.py on every CI run anyway)
    count_launches = os.environ.get("REPRO_KERNEL_MODE") != "ref"
    write_json = write_json and not smoke()
    for n in pick(SIZES, SMOKE_SIZES):
        for B in pick(BATCHES, SMOKE_BATCHES):
            views = [jax.random.normal(jax.random.fold_in(key, 100 + i),
                                       (n, n)) for i in range(B)]
            cell = {"n": n, "B": B,
                    "iterations": _prism_cfg(n).iterations}
            # --- launch counts (kernel dispatch structure, trace only;
            # the interpret-size cutoff is disabled because counting
            # never executes a kernel body — see kernels/ops.py)
            if count_launches:
                prev = os.environ.get("REPRO_KERNEL_MODE")
                prev_cut = os.environ.get("REPRO_INTERPRET_MAX_ELEMS")
                os.environ["REPRO_KERNEL_MODE"] = "interpret"
                os.environ["REPRO_INTERPRET_MAX_ELEMS"] = "0"
                try:
                    pl_k, bu_k = _engines(n)
                    cell["launches_per_leaf"] = _count_launches(pl_k, views,
                                                                key)
                    cell["launches_bucketed"] = _count_launches(bu_k, views,
                                                                key)
                    # dtype-independence of the launch contract: the bf16
                    # engine must trace the SAME launch structure
                    _, bu16 = _engines(n, dtype="bfloat16")
                    cell["launches_bucketed_bf16"] = _count_launches(
                        bu16, views, key)
                finally:
                    for var, old in [("REPRO_KERNEL_MODE", prev),
                                     ("REPRO_INTERPRET_MAX_ELEMS",
                                      prev_cut)]:
                        if old is None:
                            os.environ.pop(var, None)
                        else:
                            os.environ[var] = old
            # --- wall clock + compile (ref mode jnp); the dtype axis
            # adds the bf16-policy bucketed engine
            per_leaf, bucketed = _engines(n)
            _, bucketed16 = _engines(n, dtype="bfloat16")
            for name, fn in [("per_leaf", per_leaf),
                             ("bucketed", bucketed),
                             ("bucketed_bf16", bucketed16)]:
                jfn = jax.jit(lambda vs, fn=fn: fn(vs, key))
                t0 = time.perf_counter()
                jax.block_until_ready(jfn(views))
                cell[f"{name}_compile_s"] = round(
                    time.perf_counter() - t0, 3)
                # min over repeats: robust to scheduler noise on a small
                # shared host (median still jitters at the 100ms scale)
                reps = 7 if n <= 256 else 2
                ts = []
                for _ in range(reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(jfn(views))
                    ts.append(time.perf_counter() - t0)
                cell[f"{name}_ms"] = round(1e3 * min(ts), 2)
            cell["speedup"] = round(
                cell["per_leaf_ms"] / max(cell["bucketed_ms"], 1e-9), 3)
            cell["bf16_speedup"] = round(
                cell["bucketed_ms"] / max(cell["bucketed_bf16_ms"], 1e-9),
                3)
            cell["hbm_bytes_fp32"] = hbm_bytes_per_iter(n, B, "float32")
            cell["hbm_bytes_bf16"] = hbm_bytes_per_iter(n, B, "bfloat16")
            # §10 fused-tier modeled HBM: per fitted iteration and per
            # whole warm tail, plus the tier this shape takes
            from repro.kernels import ops as kops

            cell["hbm_bytes_fused_fp32"] = hbm_bytes_per_iter_fused(
                n, B, "float32")
            cell["hbm_bytes_fused_bf16"] = hbm_bytes_per_iter_fused(
                n, B, "bfloat16")
            cell["hbm_bytes_warm_tail_fp32"] = hbm_bytes_warm_tail(
                n, B, "float32")
            cell["hbm_bytes_warm_tail_bf16"] = hbm_bytes_warm_tail(
                n, B, "bfloat16")
            # the tier is dtype-dependent (bf16 halves the working set),
            # so record both
            cell["fused_fits_fp32"] = bool(kops.fused_fits((n, n),
                                                           "float32"))
            cell["fused_fits_bf16"] = bool(kops.fused_fits((n, n),
                                                           "bfloat16"))
            results.append(cell)
            extra = ({"launches_per_leaf": cell["launches_per_leaf"],
                      "launches_bucketed": cell["launches_bucketed"],
                      "launches_bucketed_bf16":
                          cell["launches_bucketed_bf16"]}
                     if count_launches else {})
            emit(f"batched_matfn_n{n}_B{B}", 1e3 * cell["bucketed_ms"],
                 per_leaf_ms=cell["per_leaf_ms"],
                 bucketed_ms=cell["bucketed_ms"],
                 bucketed_bf16_ms=cell["bucketed_bf16_ms"],
                 speedup=cell["speedup"],
                 bf16_speedup=cell["bf16_speedup"], **extra)
    adaptive = run_adaptive(key)
    out = {"benchmark": "bucketed batched PRISM polar vs per-leaf loop",
           "backend": jax.default_backend(),
           "prism": {"degree": 2, "warm_alpha_iters": 1, "sketch_dim": 8},
           "dtypes": ["float32", "bfloat16"],
           "adaptive": adaptive,
           "notes": [
               "wall clock is the CPU ref-mode (pure-jnp) number; the "
               "bucketed win is in the dispatch-bound regime (many small "
               "leaves) and in compile time (one chain vs B).",
               "large-n CPU cells are flop-bound and XLA-CPU schedules a "
               "batched dot_general slightly worse than a loop of 2-D "
               "GEMMs, so speedup < 1 there is a host artifact; on the "
               "TPU the same cells collapse B per-leaf chains of Pallas "
               "launches to one (see launches_per_leaf/launches_bucketed).",
               "dtype axis (DESIGN.md §9): bucketed_bf16_ms runs the "
               "bf16-compute/fp32-accumulate policy.  XLA-CPU emulates "
               "bf16 through fp32 upcasts, so CPU bf16 wall clock is "
               "neutral-to-slower BY DESIGN (bf16_speedup ~<= 1 here is "
               "expected, not a regression); the accelerator claim is "
               "hbm_bytes_bf16 = hbm_bytes_fp32 / 2 at identical launch "
               "counts (launches_bucketed_bf16 == launches_bucketed).",
               "fused axis (DESIGN.md §10): the launch counts trace "
               "the tier the shape picks, fused_fits_{fp32,bf16} (bf16 "
               "halves the working set, so it can fuse where fp32 "
               "cannot): on the fused tier 1 launch for the warm tail + "
               "2 per fitted iteration, vs warm*(1+d) + fitted*(2+d) on "
               "the §7 tier.  hbm_bytes_fused_* model one fitted "
               "iteration (5 matrices + the sketch vs 2 + (4d+2) + 4d on "
               "§7 — degree- and max_power-independent because R and "
               "the Horner accumulator never leave VMEM); "
               "hbm_bytes_warm_tail_* model an ENTIRE warm tail (one "
               "read + one write of X).",
               "adaptive axis (DESIGN.md §11): at one residual target "
               "tol, the Gaussian bucket's iters_mean must sit strictly "
               "below iters_max_ill — the fixed iteration count a "
               "certificate-free engine provisions for the same target "
               "(set by the near-rank-deficient straggler).  resid_max* "
               "prove both buckets met the target; launch contracts are "
               "tol-blind (tests/test_adaptive_tol.py).",
           ],
           "results": results}
    if write_json:
        with open(OUT, "w") as f:
            json.dump(out, f, indent=1)
        print(f"# wrote {OUT}", flush=True)
    return out


if __name__ == "__main__":
    run()
