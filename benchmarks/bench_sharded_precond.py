"""Replicated vs mesh-sharded bucketed PRISM polar (DESIGN.md §8).

The workload models one Muon orthogonalization pass over a bucket of B
same-shape momentum matrices.  The replicated engine runs the full
[B, n, n] chain on every device (PR-1 state of the world); the sharded
engine shard_maps the batch dim over the mesh's data axis, so each
device runs the chain on B/shards slices and all-gathers the result.

Runs in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device
_count=8 (the parent test/benchmark world stays single-device), on a
(4, 2) (data, model) host mesh.  Respects REPRO_KERNEL_MODE: the parent
environment is passed through, so CI's ref mode never falls into the
Pallas interpreter; host-CPU "devices" share the same cores, so the
wall-clock ratio understates the real-mesh win — the honest transferable
number is work_per_device, which drops by the data-axis size.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

from benchmarks.common import emit, pick

CELLS = [(256, 16), (512, 8)]
SMOKE_CELLS = [(256, 16)]  # subset of CELLS: smoke rows match full rows

CHILD = textwrap.dedent("""
    import os
    os.environ.setdefault("REPRO_KERNEL_MODE", "ref")
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import time
    import jax, jax.numpy as jnp
    from repro.config import OptimizerConfig, PrismConfig
    from jax.sharding import AxisType
    from repro.optim import bucketing
    from repro.sharding_ctx import activation_sharding

    n, B = int(sys.argv[1]), int(sys.argv[2])
    key = jax.random.PRNGKey(0)
    views = [jax.random.normal(jax.random.fold_in(key, i), (n, n))
             for i in range(B)]
    cfg = OptimizerConfig(prism=PrismConfig(degree=2, iterations=3,
                                            warm_alpha_iters=1,
                                            sketch_dim=8))

    def bench(fn):
        jfn = jax.jit(fn)
        t0 = time.perf_counter()
        jax.block_until_ready(jfn(views))
        compile_s = time.perf_counter() - t0
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(jfn(views))
            ts.append(time.perf_counter() - t0)
        return compile_s, min(ts)

    rep_c, rep_t = bench(lambda vs: bucketing.polar_bucketed(vs, cfg,
                                                             key))
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with mesh, activation_sharding(
            mesh, {"opt_layers": "model", "opt_rows": "data"}):
        sh_c, sh_t = bench(lambda vs: bucketing.polar_bucketed(vs, cfg,
                                                               key))
    print("RESULT", rep_c, rep_t, sh_c, sh_t)
""")


def run():
    for n, B in pick(CELLS, SMOKE_CELLS):
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env["PYTHONPATH"] = "src"
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = subprocess.run(
            [sys.executable, "-c", CHILD, str(n), str(B)], cwd=root,
            env=env, capture_output=True, text=True, timeout=600)
        line = [l for l in out.stdout.splitlines()
                if l.startswith("RESULT")]
        if not line:
            emit(f"sharded_precond_n{n}_B{B}", 0.0, status="ERROR",
                 err=out.stderr.strip().splitlines()[-1][:120]
                 if out.stderr.strip() else "no output")
            continue
        rep_c, rep_t, sh_c, sh_t = map(float, line[0].split()[1:])
        emit(f"sharded_precond_n{n}_B{B}", 1e6 * sh_t,
             replicated_ms=round(1e3 * rep_t, 2),
             sharded_ms=round(1e3 * sh_t, 2),
             replicated_compile_s=round(rep_c, 2),
             sharded_compile_s=round(sh_c, 2),
             data_shards=4,
             work_per_device_slices=f"{B}->{-(-B // 4)}")


if __name__ == "__main__":
    run()
