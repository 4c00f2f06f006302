"""Chip smoke test: the PRISM-Muon trainer on a TPU at gpt2-paper width.

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chip  # a four-chip host (v5e 2x2)

One chip runs four phases in one process, in order:
  1. device   — fail unless JAX's first device is a TPU;
  2. kernels  — the Pallas kernels, natively, on gpt2-paper's real Muon
                buckets in fp32 and bf16, each against its kernels/ref.py
                oracle, plus the fused trio at the largest shape the
                fused tier accepts;
  3. train    — gpt2-paper (full width) with Muon+PRISM through
                ``launch.train.build_trainer``: 6 steps at seq 1024,
                global batch 8, one verified checkpoint, falling loss;
  4. serve    — params restored from phase 3's checkpoint serve 4
                requests through the continuous-batching engine.

``--four-chip`` runs the device phase and then only the sharded phase:
3 steps on a (data=2, model=2) mesh of the chips present against the
same 3 steps on one device, plus each device's memory in use.

Any failure exits non-zero.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
Every time printed is host wall time.  Outputs (checkpoints) go to a
fresh ``chip_smoke_out/`` beside this file.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.checkpoint import restore_params, verify_step  # noqa: E402
from repro.config import OptimizerConfig, PrismConfig  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import polynomials as poly  # noqa: E402
from repro.core import sketch as sk  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.mesh import make_device_mesh  # noqa: E402
from repro.launch.train import build_trainer  # noqa: E402
from repro.models import build  # noqa: E402
from repro.optim import bucketing  # noqa: E402
from repro.optim.muon import matrix_view_shapes  # noqa: E402
from repro.serving import Engine, EngineConfig, make_trace  # noqa: E402

OUT = os.path.join(ROOT, "chip_smoke_out")
ARCH = "gpt2-paper"
SEED = 0
SEQ, BATCH = 1024, 8
TRAIN_STEPS, SHARDED_STEPS = 6, 3

# kernel-vs-oracle tolerances of tests/test_kernels.py (gram, matmul_add,
# sketch chain) and tests/test_fused_iter.py (the fused trio)
GRID_TOL = {"float32": 2e-5, "bfloat16": 2e-2}
CHAIN_TOL = {"float32": 2e-4, "bfloat16": 5e-2}
FUSED_TOL = {"float32": 2e-4, "bfloat16": 5e-2}
U_BF16 = 2.0 ** -8


def phase(name):
    print(f"=== phase: {name}", flush=True)
    return time.perf_counter()


def done(name, t0):
    print(f"=== phase {name} passed ({time.perf_counter() - t0:.1f} s "
          f"host wall time)", flush=True)


# ------------------------------------------------------------------ device


def phase_device(min_count: int = 1):
    t0 = phase("device")
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {d.platform!r} "
                         f"({d.device_kind}); this smoke test runs only on "
                         f"the chip")
    if len(devs) < min_count:
        raise SystemExit(f"{len(devs)} TPU devices, need {min_count}")
    print(f"device platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    done("device", t0)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ----------------------------------------------------------------- kernels


def _close(what, got, want, tol):
    got = np.asarray(jnp.asarray(got, jnp.float32))
    want = np.asarray(jnp.asarray(want, jnp.float32))
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    print(f"  {what}: max |kernel - oracle| = {err:.3e} (tol {tol:g})",
          flush=True)
    if not np.all(np.isfinite(got)):
        raise AssertionError(f"{what}: non-finite kernel output")
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol, err_msg=what)


def _unit_spectrum(key, shape, dtype):
    """Gaussian [B, m, n] scaled so its top singular value is about 1 (the
    Marchenko-Pastur edge sqrt(m) + sqrt(n)): inside the Newton-Schulz
    convergence region, like the iterates the chains see."""
    m, n = shape[-2:]
    x = jax.random.normal(key, shape, jnp.float32) / (np.sqrt(m) +
                                                      np.sqrt(n))
    return x.astype(dtype)


def _oracle(fn, *args, **kw):
    # the oracles are the fp32 reference: XLA's default TPU precision
    # for f32 dots is lower, so pin it for them (the kernels pin their
    # own, kernels/mxu.py)
    with jax.default_matmul_precision("highest"):
        return fn(*args, **kw)


def _expect_mode(expect, *arrays):
    got = ops._mode(*arrays)
    if got != expect:
        raise AssertionError(f"kernel dispatch mode {got!r}, expected "
                             f"{expect!r}")


def _tier_name(ocfg, bshape, dtype) -> str:
    n = min(bshape)  # the Gram side: polar transposes to m >= n
    tier = bucketing.resolve_tier(ocfg, bshape)
    if tier != "grid":
        return tier
    chain_fits = ops._chain_vmem_bytes(n, 128, dtype, 256) <= \
        ops.VMEM_BUDGET
    return "grid" if chain_fits else "grid, per-step chain"


def largest_fused_n(dtype) -> int:
    """Largest square n, a multiple of the 128-lane tile, that the fused
    tier accepts."""
    fits = [n for n in range(128, 4097, 128)
            if ops.fused_fits((n, n), dtype)]
    if not fits:
        raise AssertionError(f"no square shape fits the fused tier in "
                             f"{jnp.dtype(dtype).name}")
    return fits[-1]


def phase_kernels(cfg, expect_mode: str = "native"):
    """Every Pallas kernel of the optimizer on the model's real Muon
    buckets, fp32 and bf16, against the ref.py oracles."""
    t0 = phase("kernels")
    model = build(cfg)
    shapes = matrix_view_shapes(model.param_shapes(), model.logical_axes())
    max_power = poly.max_trace_power(poly.newton_schulz_residual(2))
    key = jax.random.PRNGKey(SEED)
    for dt in ("float32", "bfloat16"):
        dtype = jnp.dtype(dt)
        ocfg = OptimizerConfig(matfn_dtype=dt, prism=PrismConfig(
            degree=2, iterations=3, warm_alpha_iters=3, sketch_dim=8))
        buckets = bucketing.plan_buckets(shapes, pad=ocfg.bucket_pad,
                                         pad_slack=ocfg.bucket_pad_slack)
        for bi, b in enumerate(buckets):
            m, n = max(b.shape), min(b.shape)
            kx, ks = jax.random.split(jax.random.fold_in(key, bi))
            X = _unit_spectrum(kx, (b.size, m, n), dtype)
            S = sk.gaussian_sketch(ks, 8, n, dtype=dtype)
            print(f"bucket {b.shape} x{b.size} {dt}: tier "
                  f"{_tier_name(ocfg, b.shape, dtype)}", flush=True)
            _expect_mode(expect_mode, X)
            R = ops.gram(X)
            _close("gram", R, _oracle(ref.gram, X), GRID_TOL[dt])
            _expect_mode(expect_mode, X, R, X)
            _close("matmul_add",
                   ops.matmul_add(X, R, C=X, beta=0.5),
                   _oracle(ref.matmul_add, X, R, C=X, beta=0.5),
                   GRID_TOL[dt])
            _expect_mode(expect_mode, R)
            _close("sketch_traces",
                   ops.sketch_traces(R, S, max_power),
                   _oracle(ref.sketch_traces, R, S, max_power),
                   CHAIN_TOL[dt])
        # the fused trio at the largest shape the fused tier accepts
        nf = largest_fused_n(dtype)
        kx, ks, ka = jax.random.split(jax.random.fold_in(key, 99), 3)
        X = _unit_spectrum(kx, (4, nf, nf), dtype)
        S = sk.gaussian_sketch(ks, 8, nf, dtype=dtype)
        print(f"fused trio [4, {nf}, {nf}] {dt}", flush=True)
        _expect_mode(expect_mode, X)
        R, t = ops.residual_chain(X, S, max_power, family="polar")
        R_ref, t_ref = _oracle(ref.residual_chain, X, S, max_power,
                               family="polar")
        _close("residual_chain R", R, R_ref, FUSED_TOL[dt])
        _close("residual_chain traces", t[..., 1:], t_ref, FUSED_TOL[dt])
        coeffs = ops._gd_coeffs(2)
        alpha = jax.random.uniform(ka, (4,), jnp.float32, 0.4, 1.45)
        _expect_mode(expect_mode, X, R)
        _close("apply_g", ops.apply_g(X, R, alpha, degree=2),
               _oracle(ref.apply_g, X, R, alpha, coeffs=coeffs),
               FUSED_TOL[dt])
        alphas = (1.45, 1.2, 0.9)
        _close("warm_tail", ops.warm_tail(X, alphas, degree=2),
               _oracle(ref.warm_tail, X, alphas, coeffs=coeffs,
                       family="polar"),
               FUSED_TOL[dt])
    done("kernels", t0)


# ------------------------------------------------------------------- train


def _train(cfg, ckpt_dir, steps, *, mesh=None, ckpt_every=0):
    """Run ``steps`` steps through the launcher's construction.  Returns
    (trainer, params, opt_state, losses, per-step host wall seconds);
    step 0's time includes initialisation and compilation."""
    trainer, ctx = build_trainer(
        cfg, ckpt_dir=ckpt_dir, steps=steps, seq=SEQ, batch=BATCH,
        mesh=mesh, ckpt_every=ckpt_every, log_every=steps)
    stamps = [time.perf_counter()]
    with ctx:
        params, opt_state, losses = trainer.run(
            seed=SEED, on_metrics=lambda t, m: stamps.append(
                time.perf_counter()))
    walls = list(np.diff(stamps))
    for t, (loss, w) in enumerate(zip(losses, walls)):
        print(f"  step {t}: loss {loss:.6f}  host wall time {w:.3f} s",
              flush=True)
    return trainer, params, opt_state, losses, walls


def phase_train(cfg, out_dir, steps: int = TRAIN_STEPS):
    """Full-width training; one checkpoint at the last step, verified."""
    t0 = phase("train")
    ckpt_dir = os.path.join(out_dir, "ckpt_train")
    _, params, opt_state, losses, _ = _train(cfg, ckpt_dir, steps,
                                             ckpt_every=steps)
    del params, opt_state
    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    if not verify_step(ckpt_dir, steps):
        raise AssertionError(f"checkpoint step {steps} in {ckpt_dir} "
                             f"fails verification")
    print(f"checkpoint step {steps} verified", flush=True)
    done("train", t0)
    return ckpt_dir


# ------------------------------------------------------------------- serve


def phase_serve(cfg, ckpt_dir, n_requests: int = 4):
    """Restore the trained params and serve requests on the wall clock."""
    t0 = phase("serve")
    model = build(cfg)
    step, params = restore_params(ckpt_dir, model.param_shapes())
    print(f"restored params from step {step}", flush=True)
    eng = Engine(model, params, EngineConfig(slots=n_requests, cache_len=64,
                                             greedy=True, seed=SEED))
    trace = make_trace(SEED, n_requests=n_requests, qps=1000.0,
                       vocab_size=cfg.vocab_size)
    res = eng.run(trace)
    if len(res.completions) != n_requests:
        raise AssertionError(f"{len(res.completions)} of {n_requests} "
                             f"requests completed")
    want = {r.rid: r.max_new for r in trace}
    for c in res.completions:
        toks = np.asarray(c.tokens)
        if len(toks) != want[c.rid] or toks.min() < 0 or \
                toks.max() >= cfg.vocab_size:
            raise AssertionError(f"request {c.rid}: tokens {c.tokens}")
        print(f"  request {c.rid}: prompt {c.prompt_len}, "
              f"{len(toks)} tokens, latency {c.latency:.3f} s host wall "
              f"time", flush=True)
    print(f"served {res.generated_tokens} tokens in {res.elapsed:.3f} s "
          f"host wall time (compilation included)", flush=True)
    done("serve", t0)


# --------------------------------------------------------------- four chip


def phase_four_chip(cfg, out_dir, steps: int = SHARDED_STEPS):
    """Sharded training on a (data=2, model=2) mesh vs one device.

    Tolerance: the model computes in bf16; tensor parallelism changes the
    order in which each bf16 matmul's partial sums over the model axis
    are reduced, perturbing activations by about one bf16 rounding
    (u = 2^-8) relative, and the mean loss by at most about u of itself.
    The optimizer is exact per slice under the sharded dispatch
    (DESIGN.md §8), so the bound holds step by step: |dL_k| <= u * L_k.
    """
    t0 = phase("four-chip")
    mesh = make_device_mesh(jax.devices()[:4])
    if dict(mesh.shape) != {"data": 2, "model": 2}:
        raise AssertionError(f"mesh {dict(mesh.shape)}")
    print(f"mesh {dict(mesh.shape)}", flush=True)
    _, params, opt_state, sharded, _ = _train(
        cfg, os.path.join(out_dir, "ckpt_sharded"), steps, mesh=mesh)
    used = []
    for d in mesh.devices.flat:
        stats = d.memory_stats()
        if stats is None:
            if d.platform == "tpu":
                raise AssertionError(f"{d}: no memory_stats")
            print(f"  {d}: memory_stats not reported", flush=True)
            continue
        used.append(stats["bytes_in_use"])
        print(f"  {d}: bytes_in_use {stats['bytes_in_use']} "
              f"peak_bytes_in_use {stats.get('peak_bytes_in_use')}",
              flush=True)
    if used and min(used) <= 0:
        raise AssertionError(f"device memory in use {used}")
    del params, opt_state
    _, params, opt_state, single, _ = _train(
        cfg, os.path.join(out_dir, "ckpt_single"), steps)
    del params, opt_state
    for k, (a, b) in enumerate(zip(sharded, single)):
        tol = U_BF16 * abs(b)
        print(f"  step {k}: sharded {a:.6f} vs one device {b:.6f}  "
              f"|diff| {abs(a - b):.3e} (tol {tol:.3e})", flush=True)
        if not (np.isfinite(a) and abs(a - b) <= tol):
            raise AssertionError(f"step {k}: sharded loss {a} vs {b}")
    done("four-chip", t0)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded phase on four chips")
    args = ap.parse_args()
    device = phase_device(4 if args.four_chip else 1)
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    shutil.rmtree(OUT, ignore_errors=True)
    os.makedirs(OUT)
    cfg = get_config(ARCH)
    if args.four_chip:
        phase_four_chip(cfg, OUT)
    else:
        phase_kernels(cfg)
        ckpt_dir = phase_train(cfg, OUT)
        phase_serve(cfg, ckpt_dir)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
