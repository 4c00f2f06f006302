"""1F1B pipeline training throughput + bubble model (DESIGN.md §13).

Sweeps registry SMOKE models x pipeline depth x microbatch count on the
8-device host mesh (pod x data x model), training for real with the
async PRISM-Muon engine, and records:

  * tokens/sec and the per-step wall-time trajectory (step 0 = compile);
  * the analytic bubble model ticks = n_micro + 2*(n_stages-1),
    bubble_fraction = 2*(n_stages-1)/ticks — schema-enforced to DECREASE
    in n_micro at fixed depth (more microbatches amortize fill+drain);
  * the §12/§13 composition contract: the steady-state pipeline step
    under ``precond_async`` compiles with ZERO matrix-function launches
    (every chain lives in the refresh program the service dispatches
    into the bubbles), counted by tracing with the kernel wrappers.

Each cell runs in a subprocess so the forced 8-device CPU world (and,
for launch counting, interpret-mode kernels) never leaks into the
parent.  Writes the committed baseline BENCH_pipeline_train.json.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit, pick, smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "BENCH_pipeline_train.json")

MODELS = ["mixtral-8x7b", "qwen3-14b"]
SMOKE_MODELS = ["qwen3-14b"]
STAGES = [2, 4]
SMOKE_STAGES = [2]
N_MICRO = [2, 4, 8]
SMOKE_N_MICRO = [2, 4]
SEQ_LEN = 32
GLOBAL_BATCH = 16


def _child_env(n_devices: int = 8, interpret: bool = False):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT,
         env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    if interpret:
        # launch counting traces the kernel wrappers: pin interpret mode
        # with the size cutoff disabled, else the ref oracles short-
        # circuit dispatch and every count is a vacuous 0
        env["REPRO_KERNEL_MODE"] = "interpret"
        env["REPRO_INTERPRET_MAX_ELEMS"] = "0"
    else:
        env["REPRO_KERNEL_MODE"] = "ref"
    return env


def _run_child(args, interpret: bool = False):
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_pipeline_train",
         "--child", *[str(a) for a in args]],
        cwd=ROOT, env=_child_env(interpret=interpret),
        capture_output=True, text=True, timeout=900)
    for line in out.stdout.splitlines():
        if line.startswith("ROW "):
            return json.loads(line[4:])
    raise RuntimeError(f"pipeline bench child produced no ROW:\n"
                       f"{out.stdout}\n{out.stderr[-4000:]}")


# ------------------------------------------------------------- child


def _child_main(argv):
    import argparse
    import tempfile
    import time

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--stages", type=int, required=True)
    ap.add_argument("--n_micro", type=int, required=True)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--count_launches", action="store_true")
    args = ap.parse_args(argv)

    from repro.launch.pipeline import bubble_fraction, n_ticks_1f1b
    from repro.train.fault import build_pipeline_trainer

    # 8 host devices however the depth slices them: deeper pipelines
    # trade the model axis for stages
    data, model_ax = (2, 2) if args.stages == 2 else (2, 1)
    trainer, enter = build_pipeline_trainer(
        arch=args.arch, stages=args.stages, n_micro=args.n_micro,
        steps=args.steps, checkpoint_every=0,
        ckpt_dir=tempfile.mkdtemp(prefix="bench_pipe_"),
        async_precond=True, seq_len=SEQ_LEN, global_batch=GLOBAL_BATCH,
        data=data, model_ax=model_ax, num_layers=max(2, args.stages))

    row = {
        "model": args.arch, "stages": args.stages,
        "n_micro": args.n_micro, "seq_len": SEQ_LEN,
        "global_batch": GLOBAL_BATCH, "steps": args.steps,
        "ticks": n_ticks_1f1b(args.stages, args.n_micro),
        "bubble_fraction": bubble_fraction(args.stages, args.n_micro),
    }
    if args.count_launches:
        import jax
        import jax.numpy as jnp

        from repro.kernels import ops

        with enter():
            params, opt_state, _ = trainer.init_state()
            batch = trainer.batch_fn(jnp.asarray(0))
            row["steady_matfn_launches"] = ops.count_launches(
                lambda p, s, b: trainer.raw_step_fn(
                    p, s, b, jnp.asarray(0, jnp.int32), False),
                params, opt_state, batch)
            row["refresh_matfn_launches"] = ops.count_launches(
                lambda s: trainer.opt.refresh(s, jax.random.PRNGKey(0)),
                opt_state)
        print("ROW " + json.dumps(row), flush=True)
        return

    step_s = []
    t_last = [None]

    def on_metrics(t, metrics):
        now = time.perf_counter()
        if t_last[0] is not None:
            step_s.append(now - t_last[0])
        t_last[0] = now

    with enter():
        t0 = time.perf_counter()
        _, _, losses = trainer.run(on_metrics=on_metrics)
        total = time.perf_counter() - t0
    tokens = GLOBAL_BATCH * SEQ_LEN
    # trajectory: per-step wall times AFTER the compile step, plus the
    # loss curve as the training-for-real receipt
    row.update({
        "step_s": [round(s, 4) for s in trainer.step_times],
        "tokens_per_sec": tokens / (sorted(trainer.step_times)
                                    [len(trainer.step_times) // 2]),
        "total_s": round(total, 2),
        "losses": [round(float(l), 4) for l in losses],
    })
    print("ROW " + json.dumps(row), flush=True)


# ------------------------------------------------------------- parent


def run(write_json: bool = True) -> None:
    models = pick(MODELS, SMOKE_MODELS)
    stages = pick(STAGES, SMOKE_STAGES)
    micros = pick(N_MICRO, SMOKE_N_MICRO)
    steps = pick(5, 3)
    rows, launch_rows = [], []
    for arch in models:
        for S in stages:
            for M in micros:
                row = _run_child(["--arch", arch, "--stages", S,
                                  "--n_micro", M, "--steps", steps])
                rows.append(row)
                emit(f"pipeline_{arch}_S{S}_M{M}",
                     1e6 * row["step_s"][-1] if row["step_s"] else 0.0,
                     tokens_per_sec=round(row["tokens_per_sec"], 1),
                     bubble=round(row["bubble_fraction"], 3),
                     loss_last=row["losses"][-1])
        if not smoke():
            # §12/§13 composition contract, once per model (trace-only)
            lrow = _run_child(["--arch", arch, "--stages", 2,
                               "--n_micro", 4, "--count_launches"],
                              interpret=True)
            launch_rows.append(lrow)
            emit(f"pipeline_{arch}_launches", 0.0,
                 steady=lrow["steady_matfn_launches"],
                 refresh=lrow["refresh_matfn_launches"])
    if not (write_json and not smoke()):
        return
    import jax

    out = {
        "benchmark": "pipeline_train",
        "backend": jax.default_backend(),
        "seq_len": SEQ_LEN,
        "global_batch": GLOBAL_BATCH,
        "notes": [
            "1F1B over pod on the 8-device host mesh; stages=2 uses "
            "(pod=2,data=2,model=2), stages=4 uses (pod=4,data=2,"
            "model=1) — data parallelism is explicit inside the fully-"
            "manual engine (microbatch dim sharded over data)",
            "tokens_per_sec from the median post-compile step; step_s "
            "is the full per-step trajectory, losses the training "
            "receipt",
            "bubble_fraction = 2*(S-1)/(M+2*(S-1)) — the analytic 1F1B "
            "model; CPU wall clock realizes it only loosely (host "
            "timesharing), the schema enforces the model itself",
            "launches: the steady async pipeline step compiles with "
            "ZERO matfn launches — every chain lives in the refresh "
            "program dispatched into the bubbles (§12/§13)",
        ],
        "results": rows,
        "launches": launch_rows,
    }
    with open(OUT, "w") as f:
        json.dump(out, f, indent=1)
    print(f"# wrote {OUT}", flush=True)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        _child_main(sys.argv[2:])
    else:
        run()
