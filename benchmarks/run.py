"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (benchmarks.common.emit).

``--smoke`` (or REPRO_SMOKE=1) shrinks every benchmark to CI-smoke scale
— same modules, same CSV names, reduced sweeps/steps — so CI can assert
that every registered benchmark at least executes.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

# make `python benchmarks/run.py` work from anywhere: the repo root (the
# `benchmarks` namespace package's parent) must be importable
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="shrunk sizes: every benchmark executes quickly")
    args = ap.parse_args()
    if args.smoke:
        os.environ["REPRO_SMOKE"] = "1"

    from benchmarks import (bench_async_precond, bench_batched_matfn,
                            bench_lowrank, bench_pipeline_train,
                            bench_robustness, bench_serving,
                            bench_sharded_precond, fig1_sigma_sweep,
                            fig3_gaussian, fig4_htmp, fig5_shampoo,
                            fig6_muon_lm, figd3_sqrt, figd5_newton,
                            roofline_table)

    print("name,us_per_call,derived")
    t0 = time.time()
    failed = []
    for mod in [fig1_sigma_sweep, fig3_gaussian, fig4_htmp, figd3_sqrt,
                figd5_newton, fig5_shampoo, fig6_muon_lm, roofline_table,
                bench_batched_matfn, bench_sharded_precond,
                bench_async_precond, bench_pipeline_train,
                bench_lowrank, bench_robustness, bench_serving]:
        name = mod.__name__.split(".")[-1]
        print(f"# --- {name} ---", flush=True)
        try:
            mod.run()
        except Exception as e:  # noqa: BLE001
            print(f"{name},0.0,status=ERROR;err={type(e).__name__}:{e}",
                  flush=True)
            failed.append(name)
    print(f"# total {time.time() - t0:.1f}s")
    if failed:
        sys.exit(f"benchmark modules failed: {', '.join(failed)}")

    if args.smoke:
        # smoke also gates the COMMITTED baselines on their schema, so a
        # benchmark/baseline drift fails CI instead of rotting silently
        from benchmarks import validate_bench

        rc = validate_bench.main()
        if rc:
            sys.exit(rc)


if __name__ == "__main__":
    main()
