"""Readings that set a training cell's correctness limits, on the chip.

    python3 chipbench/calibrate.py --workload <name> --seeds 12 \
        --seed-base 2147483648 --faults 3

In one process (the trainer compiles once), for each seed: the program's
first three steps against the reference (the lower readings).  For the
first ``--faults`` seeds also the controls, the reference put in the
program's place one precision below the configuration's (the model's
products with float8_e4m3fn operands; the polar chain's with bfloat16
ones), and a fault, the reference given half of each batch, each
against the sound reference (the upper readings).  A step that returns
its state unchanged reads 1 on ``update_gap`` by construction and needs
no run.
One JSON line per seed on standard output.  The benchmark's own runs do
not run this.
"""
import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--seed-base", type=int, default=2 ** 31)
    ap.add_argument("--faults", type=int, default=3)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    import jax

    harness.require_accelerator(jax, cell["workload"]["chips"])
    harness.enable_compile_cache(jax)
    drv = harness.load_module(cell["driver"])
    from chipbench import traffic_gen
    from chipbench.reference import controls

    with tempfile.TemporaryDirectory() as tmp:
        tc = drv.TrainCell(cell, tmp)
        for i in range(args.seeds):
            seed = args.seed_base + 7919 * i
            key = jax.random.PRNGKey(seed)
            batches = traffic_gen.batches(jax.random.fold_in(key, 1),
                                          tc.traffic, tc.c["vocab_size"])
            got = tc.drive(key, batches, lambda t, loss: None,
                           steps=drv.CHECKED_STEPS + 1)
            ref = tc.reference(key, batches)

            def judged(run):
                run["chain"] = tc.chain_gaps(run["samples"])
                return drv.compare(run, ref)

            row = {"seed": seed, "program": judged(got),
                   "losses": {"program": got["loss"],
                              "reference": ref["loss"]}}
            if i < args.faults:
                row["control_model"] = judged(tc.reference(
                    key, batches, q8=controls.fp8_round))
                row["control_chain"] = judged(tc.reference(
                    key, batches, chain_q=controls.bf16_round))
                row["fault_half_batch"] = judged(tc.reference(
                    key, batches, half_batch=True))
            if i == 0:
                row["leaves"] = {k: {"grad": [got["grad"][k], g],
                                     "delta": [got["delta"][k],
                                               ref["delta"][k]],
                                     "chain": got["chain"].get(k)}
                                 for k, g in ref["grad"].items()}
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
