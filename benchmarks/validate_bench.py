"""Schema validation for committed BENCH_*.json baselines.

Run by CI's smoke step (and by ``benchmarks/run.py --smoke``) so a
benchmark edit that drifts from its committed baseline's shape — a
renamed field, a dropped dtype axis, a non-numeric cell — fails the PR
instead of silently rotting the perf trajectory.  Hand-rolled checks
(no jsonschema dependency in the container): a schema here is a dict of
``field -> predicate`` for the top level and for each results row, plus
cross-field invariants.
"""
from __future__ import annotations

import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _is_num(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool)


def _nonneg(x) -> bool:
    return _is_num(x) and x >= 0


def _pos_int(x) -> bool:
    return isinstance(x, int) and not isinstance(x, bool) and x > 0


def _str_list(x) -> bool:
    return isinstance(x, list) and x and all(isinstance(s, str) for s in x)


BATCHED_MATFN_TOP = {
    "benchmark": lambda x: isinstance(x, str) and x,
    "backend": lambda x: isinstance(x, str) and x,
    "prism": lambda x: isinstance(x, dict),
    "dtypes": lambda x: _str_list(x) and "float32" in x and "bfloat16" in x,
    "notes": _str_list,
    "results": lambda x: isinstance(x, list) and x,
    # §11 adaptive early stopping: instance-adaptive iteration counts
    "adaptive": lambda x: isinstance(x, list) and x,
}

ADAPTIVE_ROW = {
    "n": _pos_int,
    "B": _pos_int,
    "tol": lambda x: _is_num(x) and x > 0,
    "iters_budget": _pos_int,
    "iters_mean": lambda x: _is_num(x) and x >= 1,
    "iters_max": _pos_int,
    "iters_mean_ill": lambda x: _is_num(x) and x >= 1,
    "iters_max_ill": _pos_int,
    "resid_max": _nonneg,
    "resid_max_ill": _nonneg,
}


def _check_adaptive_row(row: dict, where: str):
    errs = []
    for field, ok in ADAPTIVE_ROW.items():
        if field not in row:
            errs.append(f"{where}: missing field {field!r}")
        elif not ok(row[field]):
            errs.append(f"{where}: bad value {field}={row[field]!r}")
    if errs:
        return errs
    # §11 invariants.  The headline: at an equal residual target, the
    # well-conditioned bucket's MEAN certified count must sit strictly
    # below the fixed-iters baseline — the count a certificate-free
    # engine provisions, i.e. what the ill-conditioned straggler needed.
    if not row["iters_mean"] < row["iters_max_ill"]:
        errs.append(f"{where}: iters_mean must be strictly below the "
                    f"fixed-iters baseline iters_max_ill "
                    f"({row['iters_mean']} vs {row['iters_max_ill']})")
    if row["iters_mean"] > row["iters_max"]:
        errs.append(f"{where}: iters_mean > iters_max")
    if row["iters_mean_ill"] > row["iters_max_ill"]:
        errs.append(f"{where}: iters_mean_ill > iters_max_ill")
    for f in ("iters_max", "iters_max_ill"):
        if row[f] > row["iters_budget"]:
            errs.append(f"{where}: {f} exceeds the iteration budget")
    # "equal residual targets": both buckets actually met tol (modest
    # slack for the p=8 sketch certificate's variance)
    for f in ("resid_max", "resid_max_ill"):
        if row[f] > 1.5 * row["tol"]:
            errs.append(f"{where}: {f}={row[f]} above the tol target "
                        f"{row['tol']}")
    return errs

BATCHED_MATFN_ROW = {
    "n": _pos_int,
    "B": _pos_int,
    "iterations": _pos_int,
    "per_leaf_ms": _nonneg,
    "bucketed_ms": _nonneg,
    "bucketed_bf16_ms": _nonneg,
    "per_leaf_compile_s": _nonneg,
    "bucketed_compile_s": _nonneg,
    "bucketed_bf16_compile_s": _nonneg,
    "speedup": _nonneg,
    "bf16_speedup": _nonneg,
    "hbm_bytes_fp32": _pos_int,
    "hbm_bytes_bf16": _pos_int,
    # the committed baseline must carry the §7/§9 dispatch contract:
    # regenerating under REPRO_KERNEL_MODE=ref skips launch counting and
    # is rejected here — rerun without it
    "launches_per_leaf": _pos_int,
    "launches_bucketed": _pos_int,
    "launches_bucketed_bf16": _pos_int,
    # §10 fused-iteration tier axis
    "hbm_bytes_fused_fp32": _pos_int,
    "hbm_bytes_fused_bf16": _pos_int,
    "hbm_bytes_warm_tail_fp32": _pos_int,
    "hbm_bytes_warm_tail_bf16": _pos_int,
    "fused_fits_fp32": lambda x: isinstance(x, bool),
    "fused_fits_bf16": lambda x: isinstance(x, bool),
}


def _check_batched_matfn_row(row: dict, where: str):
    errs = []
    for field, ok in BATCHED_MATFN_ROW.items():
        if field not in row:
            errs.append(f"{where}: missing field {field!r}")
        elif not ok(row[field]):
            errs.append(f"{where}: bad value {field}={row[field]!r}")
    # §9 invariants: bf16 halves HBM bytes, launch counts dtype-blind
    if _is_num(row.get("hbm_bytes_fp32")) and \
            _is_num(row.get("hbm_bytes_bf16")) and \
            row["hbm_bytes_bf16"] * 2 != row["hbm_bytes_fp32"]:
        errs.append(f"{where}: hbm_bytes_bf16 must be half of fp32 "
                    f"({row['hbm_bytes_bf16']} vs {row['hbm_bytes_fp32']})")
    if "launches_bucketed" in row and \
            row.get("launches_bucketed_bf16") != row["launches_bucketed"]:
        errs.append(f"{where}: launch counts are dtype-dependent: "
                    f"{row.get('launches_bucketed_bf16')} != "
                    f"{row['launches_bucketed']}")
    # §10 invariants: the fused tier halves nothing by dtype games — bf16
    # still exactly halves bytes, and the fused tier strictly beats the
    # §7 tier on modeled HBM
    if _is_num(row.get("hbm_bytes_fused_fp32")) and \
            _is_num(row.get("hbm_bytes_fused_bf16")) and \
            row["hbm_bytes_fused_bf16"] * 2 != row["hbm_bytes_fused_fp32"]:
        errs.append(f"{where}: hbm_bytes_fused_bf16 must be half of fp32")
    if _is_num(row.get("hbm_bytes_fused_fp32")) and \
            _is_num(row.get("hbm_bytes_fp32")) and \
            not row["hbm_bytes_fused_fp32"] < row["hbm_bytes_fp32"]:
        errs.append(f"{where}: hbm_bytes_fused_fp32 must beat the §7 "
                    f"model")
    return errs


def validate_batched_matfn(doc: dict, name: str):
    errs = []
    for field, ok in BATCHED_MATFN_TOP.items():
        if field not in doc:
            errs.append(f"{name}: missing top-level field {field!r}")
        elif not ok(doc[field]):
            errs.append(f"{name}: bad top-level {field}={doc[field]!r}")
    for i, row in enumerate(doc.get("results") or []):
        if not isinstance(row, dict):
            errs.append(f"{name}: results[{i}] is not an object")
            continue
        errs.extend(_check_batched_matfn_row(row, f"{name}: results[{i}]"))
    for i, row in enumerate(doc.get("adaptive") or []):
        if not isinstance(row, dict):
            errs.append(f"{name}: adaptive[{i}] is not an object")
            continue
        errs.extend(_check_adaptive_row(row, f"{name}: adaptive[{i}]"))
    return errs


ASYNC_PRECOND_TOP = {
    "benchmark": lambda x: x == "async_precond",
    "backend": lambda x: isinstance(x, str) and x,
    "period": _pos_int,
    "notes": _str_list,
    "results": lambda x: isinstance(x, list) and x,
    "schedule": lambda x: isinstance(x, dict),
}

ASYNC_PRECOND_ROW = {
    "n": _pos_int,
    "layers": _pos_int,
    "period": _pos_int,
    "steady_ms": _nonneg,
    "refresh_ms": _nonneg,
    "blocking_step_ms": _nonneg,
    "speedup_refresh_step": _nonneg,
    "speedup_amortized": _nonneg,
    # the §12 contract: the async steady step compiles with ZERO matfn
    # launches — all of them live in the refresh program.  Regenerating
    # under REPRO_KERNEL_MODE=ref skips counting and is rejected here.
    "blocking_launches_steady": lambda x: x == 0,
    "launches_refresh": _pos_int,
    "launches_blocking_step": _pos_int,
}

ASYNC_PRECOND_SCHEDULE = {
    "steps": _pos_int,
    "segment": _pos_int,
    "period_clock": _pos_int,
    "ceiling_drift": _pos_int,
    "drift_threshold": lambda x: _is_num(x) and x > 0,
    "refreshes_clock": _pos_int,
    "refreshes_drift": _pos_int,
    "drift_triggered": lambda x: isinstance(x, int) and x >= 0,
    "drift_max_clock": _nonneg,
    "drift_max_drift": _nonneg,
}


def validate_async_precond(doc: dict, name: str):
    errs = []
    for field, ok in ASYNC_PRECOND_TOP.items():
        if field not in doc:
            errs.append(f"{name}: missing top-level field {field!r}")
        elif not ok(doc[field]):
            errs.append(f"{name}: bad top-level {field}={doc[field]!r}")
    for i, row in enumerate(doc.get("results") or []):
        where = f"{name}: results[{i}]"
        if not isinstance(row, dict):
            errs.append(f"{where} is not an object")
            continue
        for field, ok in ASYNC_PRECOND_ROW.items():
            if field not in row:
                errs.append(f"{where}: missing field {field!r}")
            elif not ok(row[field]):
                errs.append(f"{where}: bad value {field}={row[field]!r}")
    sched = doc.get("schedule")
    if isinstance(sched, dict):
        where = f"{name}: schedule"
        for field, ok in ASYNC_PRECOND_SCHEDULE.items():
            if field not in sched:
                errs.append(f"{where}: missing field {field!r}")
            elif not ok(sched[field]):
                errs.append(f"{where}: bad value "
                            f"{field}={sched[field]!r}")
        if errs:
            return errs
        # the headline claim: at an equal max-staleness residual target
        # (threshold = the clock schedule's realized max drift), the
        # drift trigger must refresh NO MORE often than the fixed clock
        if not sched["refreshes_drift"] <= sched["refreshes_clock"]:
            errs.append(f"{where}: drift schedule refreshed more than "
                        f"the fixed clock ({sched['refreshes_drift']} vs "
                        f"{sched['refreshes_clock']})")
        # "equal residual": the drift run's realized staleness stays
        # within 2x the clock's — the trigger fires the refresh the step
        # drift crosses the threshold, but the swap lands one dispatch-
        # to-swap delay later, so a shift-boundary spike can overshoot
        if sched["drift_max_drift"] > 2.0 * sched["drift_max_clock"]:
            errs.append(f"{where}: drift run's max staleness "
                        f"{sched['drift_max_drift']} above 2x the clock "
                        f"schedule's {sched['drift_max_clock']}")
        if sched["drift_threshold"] > 1.01 * sched["drift_max_clock"]:
            errs.append(f"{where}: threshold not anchored to the clock "
                        f"run's realized max drift")
    return errs


PIPELINE_TRAIN_TOP = {
    "benchmark": lambda x: x == "pipeline_train",
    "backend": lambda x: isinstance(x, str) and x,
    "seq_len": _pos_int,
    "global_batch": _pos_int,
    "notes": _str_list,
    "results": lambda x: isinstance(x, list) and x,
    "launches": lambda x: isinstance(x, list) and x,
}

PIPELINE_TRAIN_ROW = {
    "model": lambda x: isinstance(x, str) and x,
    "stages": lambda x: _pos_int(x) and x > 1,
    "n_micro": _pos_int,
    "seq_len": _pos_int,
    "global_batch": _pos_int,
    "steps": _pos_int,
    "ticks": _pos_int,
    "bubble_fraction": lambda x: _is_num(x) and 0.0 <= x < 1.0,
    "step_s": lambda x: isinstance(x, list) and x and all(
        _is_num(s) and s > 0 for s in x),
    "tokens_per_sec": lambda x: _is_num(x) and x > 0,
    "total_s": lambda x: _is_num(x) and x > 0,
    "losses": lambda x: isinstance(x, list) and x and all(
        _is_num(s) for s in x),
}

PIPELINE_TRAIN_LAUNCH_ROW = {
    "model": lambda x: isinstance(x, str) and x,
    "stages": lambda x: _pos_int(x) and x > 1,
    "n_micro": _pos_int,
    # the §12/§13 composition contract: the steady async pipeline step
    # compiles with ZERO matfn launches — every chain lives in the
    # refresh program dispatched into the 1F1B bubbles.  Regenerating
    # under REPRO_KERNEL_MODE=ref skips counting and is rejected here.
    "steady_matfn_launches": lambda x: x == 0 and not isinstance(x, bool),
    "refresh_matfn_launches": _pos_int,
}


def validate_pipeline_train(doc: dict, name: str):
    errs = []
    for field, ok in PIPELINE_TRAIN_TOP.items():
        if field not in doc:
            errs.append(f"{name}: missing top-level field {field!r}")
        elif not ok(doc[field]):
            errs.append(f"{name}: bad top-level {field}={doc[field]!r}")
    rows = [r for r in (doc.get("results") or []) if isinstance(r, dict)]
    for i, row in enumerate(doc.get("results") or []):
        where = f"{name}: results[{i}]"
        if not isinstance(row, dict):
            errs.append(f"{where} is not an object")
            continue
        for field, ok in PIPELINE_TRAIN_ROW.items():
            if field not in row:
                errs.append(f"{where}: missing field {field!r}")
            elif not ok(row[field]):
                errs.append(f"{where}: bad value {field}={row[field]!r}")
        # the analytic 1F1B schedule model (DESIGN.md §13)
        if all(_is_num(row.get(k)) for k in ("ticks", "stages", "n_micro",
                                             "bubble_fraction")):
            T = row["n_micro"] + 2 * (row["stages"] - 1)
            if row["ticks"] != T:
                errs.append(f"{where}: ticks != n_micro + 2*(stages-1) "
                            f"({row['ticks']} vs {T})")
            b = 2.0 * (row["stages"] - 1) / T
            if abs(row["bubble_fraction"] - b) > 1e-9:
                errs.append(f"{where}: bubble_fraction off the model "
                            f"({row['bubble_fraction']} vs {b})")
    # the trajectory must cover >= 2 models, and at fixed (model, depth)
    # the bubble fraction must strictly DECREASE in n_micro
    if len({r.get("model") for r in rows}) < 2:
        errs.append(f"{name}: needs >= 2 models in results")
    groups: dict = {}
    for r in rows:
        groups.setdefault((r.get("model"), r.get("stages")), []).append(r)
    for (m, s), rs in groups.items():
        rs = sorted(rs, key=lambda r: r.get("n_micro", 0))
        for a, b in zip(rs, rs[1:]):
            if not (_is_num(a.get("bubble_fraction"))
                    and _is_num(b.get("bubble_fraction"))):
                continue
            if not b["bubble_fraction"] < a["bubble_fraction"]:
                errs.append(f"{name}: bubble_fraction must decrease in "
                            f"n_micro for {m} S={s}")
    lrows = [r for r in (doc.get("launches") or [])
             if isinstance(r, dict)]
    for i, row in enumerate(doc.get("launches") or []):
        where = f"{name}: launches[{i}]"
        if not isinstance(row, dict):
            errs.append(f"{where} is not an object")
            continue
        for field, ok in PIPELINE_TRAIN_LAUNCH_ROW.items():
            if field not in row:
                errs.append(f"{where}: missing field {field!r}")
            elif not ok(row[field]):
                errs.append(f"{where}: bad value {field}={row[field]!r}")
    if len({r.get("model") for r in lrows}) < 2:
        errs.append(f"{name}: needs the launch contract for >= 2 models")
    return errs


LOWRANK_TOP = {
    "benchmark": lambda x: x == "lowrank",
    "backend": lambda x: isinstance(x, str) and x,
    "rank": _pos_int,
    "oversample": lambda x: isinstance(x, int) and x >= 0,
    "notes": _str_list,
    "results": lambda x: isinstance(x, list) and x,
}

LOWRANK_ROW = {
    "m": _pos_int,
    "n": _pos_int,
    "aspect": _pos_int,
    "l": _pos_int,
    "rank": _pos_int,
    "oversample": lambda x: isinstance(x, int) and x >= 0,
    "power_iters": lambda x: isinstance(x, int) and x >= 0,
    "iters": _pos_int,
    "tol": lambda x: _is_num(x) and x > 0,
    "ortho_err": _nonneg,
    "topk_err": _nonneg,
    "flops_lowrank": _pos_int,
    "flops_cubic": _pos_int,
    "flops_ratio": lambda x: _is_num(x) and x > 0,
    "hbm_lowrank": _pos_int,
    "hbm_cubic": _pos_int,
    "ms_lowrank": _nonneg,
    "ms_cubic": _nonneg,
}


def validate_lowrank(doc: dict, name: str):
    errs = []
    for field, ok in LOWRANK_TOP.items():
        if field not in doc:
            errs.append(f"{name}: missing top-level field {field!r}")
        elif not ok(doc[field]):
            errs.append(f"{name}: bad top-level {field}={doc[field]!r}")
    rows = [r for r in (doc.get("results") or []) if isinstance(r, dict)]
    for i, row in enumerate(doc.get("results") or []):
        where = f"{name}: results[{i}]"
        if not isinstance(row, dict):
            errs.append(f"{where} is not an object")
            continue
        row_errs = []
        for field, ok in LOWRANK_ROW.items():
            if field not in row:
                row_errs.append(f"{where}: missing field {field!r}")
            elif not ok(row[field]):
                row_errs.append(f"{where}: bad value "
                                f"{field}={row[field]!r}")
        errs.extend(row_errs)
        if row_errs:
            continue
        # §14 accuracy contract: rangefinder orthonormality AND the
        # dominant-subspace oracle error within the stated tol
        for f in ("ortho_err", "topk_err"):
            if row[f] > row["tol"]:
                errs.append(f"{where}: {f}={row[f]} above tol "
                            f"{row['tol']}")
        # the subspace must be strict and the cell geometry consistent
        if row["l"] != row["rank"] + row["oversample"]:
            errs.append(f"{where}: l != rank + oversample")
        if not row["l"] < min(row["m"], row["n"]):
            errs.append(f"{where}: l must be a strict subspace of "
                        f"min(m, n)")
        if row["m"] != row["aspect"] * row["n"]:
            errs.append(f"{where}: m != aspect * n")
        # §14 cost contract: wherever the planner's size/aspect
        # threshold fires (m >= 4n), the modeled FLOPs AND HBM traffic
        # of the sketched path must STRICTLY beat the cubic polar
        if row["m"] >= 4 * row["n"]:
            if not row["flops_lowrank"] < row["flops_cubic"]:
                errs.append(f"{where}: lowrank FLOPs must beat cubic at "
                            f"m >= 4n ({row['flops_lowrank']} vs "
                            f"{row['flops_cubic']})")
            if not row["hbm_lowrank"] < row["hbm_cubic"]:
                errs.append(f"{where}: lowrank HBM must beat cubic at "
                            f"m >= 4n")
    # the sweep must actually cover the claimed regime
    if rows and not any(r.get("m", 0) >= 4 * r.get("n", 1)
                        for r in rows):
        errs.append(f"{name}: sweep has no m >= 4n cell")
    return errs


ROBUSTNESS_TOP = {
    "benchmark": lambda x: x == "robustness",
    "backend": lambda x: isinstance(x, str) and x,
    "period": _pos_int,
    "notes": _str_list,
    "results": lambda x: isinstance(x, list) and x,
    "recovery": lambda x: isinstance(x, dict),
}

ROBUSTNESS_ROW = {
    "n": _pos_int,
    "layers": _pos_int,
    "period": _pos_int,
    "steady_ms_bare": _nonneg,
    "steady_ms_guarded": _nonneg,
    "overhead_pct": _is_num,
    # the committed baseline must carry the §15 launch contract:
    # regenerating under REPRO_KERNEL_MODE=ref skips launch counting and
    # is rejected here — rerun without it
    "steady_matfn_launches_bare": lambda x: isinstance(x, int)
    and not isinstance(x, bool),
    "steady_matfn_launches_guarded": lambda x: isinstance(x, int)
    and not isinstance(x, bool),
    "matfn_launches_plain": _pos_int,
    "matfn_launches_status": _pos_int,
}

ROBUSTNESS_RECOVERY = {
    "steps": _pos_int,
    "injected": _pos_int,
    "bad_steps": lambda x: isinstance(x, int) and not isinstance(x, bool)
    and x >= 0,
    "final_finite": lambda x: isinstance(x, bool),
    "discarded": lambda x: isinstance(x, int) and not isinstance(x, bool)
    and x >= 0,
    "retries": lambda x: isinstance(x, int) and not isinstance(x, bool)
    and x >= 0,
    "degraded": lambda x: isinstance(x, int) and not isinstance(x, bool)
    and x >= 0,
    "recovered_install": lambda x: isinstance(x, bool),
}


def validate_robustness(doc: dict, name: str):
    errs = []
    for field, ok in ROBUSTNESS_TOP.items():
        if field not in doc:
            errs.append(f"{name}: missing top-level field {field!r}")
        elif not ok(doc[field]):
            errs.append(f"{name}: bad top-level {field}={doc[field]!r}")
    for i, row in enumerate(doc.get("results") or []):
        where = f"{name}: results[{i}]"
        if not isinstance(row, dict):
            errs.append(f"{where} is not an object")
            continue
        row_errs = []
        for field, ok in ROBUSTNESS_ROW.items():
            if field not in row:
                row_errs.append(f"{where}: missing field {field!r}")
            elif not ok(row[field]):
                row_errs.append(f"{where}: bad value "
                                f"{field}={row[field]!r}")
        errs.extend(row_errs)
        if row_errs:
            continue
        # §15 launch contracts.  The guards are selects riding existing
        # chains: the skip-step wrapper must keep the async steady step
        # at the §12 contract's ZERO matfn launches, and the divergence
        # detector's status read must add zero launches to the matfn
        # plan (it is decoded from the certificate the loop computes).
        for f in ("steady_matfn_launches_bare",
                  "steady_matfn_launches_guarded"):
            if row[f] != 0:
                errs.append(f"{where}: {f}={row[f]} — the steady step "
                            f"must stay at zero matfn launches")
        if row["matfn_launches_status"] != row["matfn_launches_plain"]:
            errs.append(f"{where}: status telemetry changed the launch "
                        f"count ({row['matfn_launches_status']} vs "
                        f"{row['matfn_launches_plain']})")
    rec = doc.get("recovery")
    if isinstance(rec, dict):
        where = f"{name}: recovery"
        rec_errs = []
        for field, ok in ROBUSTNESS_RECOVERY.items():
            if field not in rec:
                rec_errs.append(f"{where}: missing field {field!r}")
            elif not ok(rec[field]):
                rec_errs.append(f"{where}: bad value "
                                f"{field}={rec[field]!r}")
        errs.extend(rec_errs)
        if not rec_errs:
            # exact accounting: every injected NaN burst is one skipped
            # step — no false positives, none missed — and the run ends
            # finite; the poisoned refresh stream must walk the full
            # discard -> retry -> degrade ladder and then recover
            if rec["bad_steps"] != rec["injected"]:
                errs.append(f"{where}: bad_steps={rec['bad_steps']} != "
                            f"injected={rec['injected']}")
            if not rec["final_finite"]:
                errs.append(f"{where}: run ended non-finite")
            if not rec["discarded"] >= 1:
                errs.append(f"{where}: poisoned refresh was never "
                            f"discarded")
            if not rec["degraded"] >= 1:
                errs.append(f"{where}: retry ladder never degraded")
            if not rec["recovered_install"]:
                errs.append(f"{where}: no clean install after recovery")
    return errs


SERVING_TOP = {
    "benchmark": lambda x: x == "serving",
    "backend": lambda x: isinstance(x, str) and x,
    "step_dt_ms": lambda x: _is_num(x) and x > 0,
    "notes": _str_list,
    # ≥2 archs: the engine must be proven beyond one attention flavor
    "results": lambda x: isinstance(x, list) and len(x) >= 2,
}

SERVING_ROW = {
    "arch": lambda x: isinstance(x, str) and x,
    "family": lambda x: x in ("dense", "moe"),
    "slots": _pos_int,
    "cache_len": _pos_int,
    "n_requests": _pos_int,
    "step_dt_ms": lambda x: _is_num(x) and x > 0,
    "decode_step_shapes": _pos_int,
    "prefill_launches": _pos_int,
    "qps_points": lambda x: isinstance(x, list) and len(x) >= 3,
    "sat_qps": lambda x: _is_num(x) and x > 0,
    "continuous_tokens_per_s": lambda x: _is_num(x) and x > 0,
    "static_tokens_per_s": lambda x: _is_num(x) and x > 0,
    "decode_ms_per_step_wall": _nonneg,
}

SERVING_POINT = {
    "qps": lambda x: _is_num(x) and x > 0,
    "completed": _pos_int,
    "p50_s": _nonneg,
    "p99_s": _nonneg,
    "tokens_per_s": lambda x: _is_num(x) and x > 0,
    "decode_steps": _pos_int,
    "occupancy_mean": lambda x: _is_num(x) and x >= 1,
    "occupancy_max": _pos_int,
    "occupancy_traj": lambda x: isinstance(x, list) and x
    and all(isinstance(o, int) and o >= 1 for o in x),
}


def validate_serving(doc: dict, name: str):
    errs = []
    for field, ok in SERVING_TOP.items():
        if field not in doc:
            errs.append(f"{name}: missing top-level field {field!r}")
        elif not ok(doc[field]):
            errs.append(f"{name}: bad top-level {field}={doc[field]!r}")
    for i, row in enumerate(doc.get("results") or []):
        where = f"{name}: results[{i}]"
        if not isinstance(row, dict):
            errs.append(f"{where} is not an object")
            continue
        row_errs = []
        for field, ok in SERVING_ROW.items():
            if field not in row:
                row_errs.append(f"{where}: missing field {field!r}")
            elif not ok(row[field]):
                row_errs.append(f"{where}: bad value "
                                f"{field}={row[field]!r}")
        errs.extend(row_errs)
        if row_errs:
            continue
        # §16 jit-cache contract: the slot table never changes shape, so
        # the decode step compiles at most 2 shapes across a whole run
        if row["decode_step_shapes"] > 2:
            errs.append(f"{where}: decode step compiled "
                        f"{row['decode_step_shapes']} shapes (> 2)")
        # §16 engine contract: on a mixed-length seeded trace at
        # saturating load, continuous admission strictly out-runs
        # static (admit-only-when-drained) batching
        if not row["continuous_tokens_per_s"] > row["static_tokens_per_s"]:
            errs.append(
                f"{where}: continuous batching does not beat static "
                f"({row['continuous_tokens_per_s']} vs "
                f"{row['static_tokens_per_s']} tok/s)")
        for j, pt in enumerate(row["qps_points"]):
            pw = f"{where}: qps_points[{j}]"
            pt_errs = []
            for field, ok in SERVING_POINT.items():
                if field not in pt:
                    pt_errs.append(f"{pw}: missing field {field!r}")
                elif not ok(pt[field]):
                    pt_errs.append(f"{pw}: bad value "
                                   f"{field}={pt[field]!r}")
            errs.extend(pt_errs)
            if pt_errs:
                continue
            if pt["p50_s"] > pt["p99_s"]:
                errs.append(f"{pw}: p50 {pt['p50_s']} > p99 "
                            f"{pt['p99_s']}")
            if pt["completed"] != row["n_requests"]:
                errs.append(f"{pw}: completed {pt['completed']} != "
                            f"offered {row['n_requests']}")
            if pt["occupancy_max"] > row["slots"]:
                errs.append(f"{pw}: occupancy {pt['occupancy_max']} "
                            f"exceeds the slot table ({row['slots']})")
    return errs


VALIDATORS = {
    "BENCH_batched_matfn.json": validate_batched_matfn,
    "BENCH_async_precond.json": validate_async_precond,
    "BENCH_pipeline_train.json": validate_pipeline_train,
    "BENCH_lowrank.json": validate_lowrank,
    "BENCH_robustness.json": validate_robustness,
    "BENCH_serving.json": validate_serving,
}


def main() -> int:
    paths = sorted(glob.glob(os.path.join(ROOT, "BENCH_*.json")))
    if not paths:
        print("validate_bench: no BENCH_*.json baselines found", flush=True)
        return 1
    errs = []
    for path in paths:
        name = os.path.basename(path)
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            errs.append(f"{name}: unreadable JSON: {e}")
            continue
        validator = VALIDATORS.get(name)
        if validator is None:
            # unknown baselines must at least be well-formed objects
            if not isinstance(doc, dict) or "results" not in doc:
                errs.append(f"{name}: no schema registered and not a "
                            "results document")
            else:
                print(f"validate_bench: {name} OK (generic)", flush=True)
            continue
        file_errs = validator(doc, name)
        if file_errs:
            errs.extend(file_errs)
        else:
            print(f"validate_bench: {name} OK", flush=True)
    for e in errs:
        print(f"validate_bench: ERROR {e}", flush=True)
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
