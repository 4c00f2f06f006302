"""Run one benchmark cell on the chip this process holds.

    python3 chipbench/run.py --workload <name> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

Resolves the cell from ``BENCHMARK.json`` and its files by name, fails
(non-zero exit, no result) unless JAX sees the TPU chips the cell asks
for, and hands the cell to its driver.  The last line of standard output
is the result; the numbers compared against the reference are printed
beside their limits as the last lines of standard error.
"""
import time

T0 = time.perf_counter()  # set-up is counted from here, before JAX loads

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.resolve(args.workload)
        import jax

        devs = harness.require_accelerator(jax, cell["workload"]["chips"])
        peaks = harness.peaks(devs[0].device_kind)
        harness.enable_compile_cache(jax)
        driver = harness.load_module(cell["driver"])
    except harness.CellError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    result, checks = driver.run(cell, seed=args.seed, seconds=args.seconds,
                                traced=bool(args.trace), t0=T0, devs=devs,
                                peaks=peaks)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
