"""Mesh-sharded preconditioner engine + staleness-scheduled refresh
(DESIGN.md §8).

Multi-device coverage runs in a subprocess on an 8-CPU-device host mesh
(same pattern as test_sharded_train.py — the main test world stays
single-device); the staleness-cache semantics are single-device and run
in-process.  No hypothesis usage — these are example-based tests, so the
suite collects without it (tests/conftest.py gates the property tests).
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

from repro.config import OptimizerConfig, PrismConfig
from repro.optim import make_optimizer

SHARDED_SCRIPT = textwrap.dedent("""
    import os
    # pin CPU BEFORE jax imports: with libtpu in the image an unset
    # JAX_PLATFORMS makes jax probe the TPU metadata server for minutes
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.config import OptimizerConfig, PrismConfig
    from jax.sharding import AxisType
    from repro.launch import sharding as sh
    from repro.optim import bucketing, make_optimizer
    from repro.sharding_ctx import activation_sharding

    key = jax.random.PRNGKey(0)
    # one bucket with B = 3 + 1 + 1 + 1 = 6 — does NOT divide the 4-way
    # data axis (uneven split: pads to 8 with identity slices), one
    # square bucket, and one pad-to-bucket merge exercising the sharded
    # n_real trace-correction path
    shapes = [(3, 64, 32), (64, 32), (64, 32), (64, 32), (48, 48),
              (48, 44)]
    views = [jax.random.normal(jax.random.fold_in(key, i), s)
             for i, s in enumerate(shapes)]
    cfg = OptimizerConfig(prism=PrismConfig(degree=2, iterations=6,
                                            warm_alpha_iters=1,
                                            sketch_dim=8),
                          bucket_pad=True)
    # replicated reference: no sharding context installed
    ref = bucketing.polar_bucketed(views, cfg, key)
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with mesh, activation_sharding(
            mesh, {"opt_layers": "model", "opt_rows": "data"}):
        mm, ax = bucketing.mesh_batch_axes(cfg)
        assert mm is mesh and ax == ("data",), (mm, ax)
        out = jax.jit(
            lambda vs: bucketing.polar_bucketed(vs, cfg, key))(views)
    for r, o in zip(ref, out):
        np.testing.assert_allclose(np.asarray(r), np.asarray(o),
                                   rtol=2e-5, atol=2e-5)

    # optimizer-level parity: a full Muon update under the mesh equals
    # the single-device update (same inputs, same key)
    params = {"w": views[0], "v": views[4], "b": jnp.ones((64,))}
    axes_tree = {"w": ("layers", "embed", "mlp"), "v": ("embed", "mlp"),
                 "b": ("embed",)}
    grads = jax.tree.map(
        lambda p: jax.random.normal(jax.random.fold_in(key, 77), p.shape),
        params)
    ocfg = OptimizerConfig(name="muon", learning_rate=0.05,
                           prism=PrismConfig(degree=2, iterations=5,
                                             warm_alpha_iters=1,
                                             sketch_dim=8))
    opt = make_optimizer(ocfg, axes_tree)
    p_ref, _ = jax.jit(opt.update)(grads, opt.init(params), params, 0, key)
    with mesh, activation_sharding(
            mesh, {"opt_layers": "model", "opt_rows": "data"}):
        p_sh, _ = jax.jit(opt.update)(grads, opt.init(params), params, 0,
                                      key)
    for k in params:
        np.testing.assert_allclose(np.asarray(p_ref[k]),
                                   np.asarray(p_sh[k]),
                                   rtol=2e-5, atol=2e-5)

    # shampoo inverse-root path through the sharded transform_bucketed
    socfg = OptimizerConfig(name="shampoo", learning_rate=1e-3,
                            max_precond_dim=256,
                            prism=PrismConfig(degree=2, iterations=8,
                                              sketch_dim=8))
    sopt = make_optimizer(socfg, axes_tree)
    sp_ref, _ = jax.jit(sopt.update)(grads, sopt.init(params), params, 0,
                                     key)
    with mesh, activation_sharding(
            mesh, {"opt_layers": "model", "opt_rows": "data"}):
        sp_sh, _ = jax.jit(sopt.update)(grads, sopt.init(params), params,
                                        0, key)
    for k in params:
        np.testing.assert_allclose(np.asarray(sp_ref[k]),
                                   np.asarray(sp_sh[k]),
                                   rtol=2e-5, atol=2e-5)

    # mixed-precision engine on the sharded path (DESIGN.md §9): batch-dim
    # sharding is per-slice math in ANY dtype, so the bf16 policy keeps
    # sharded == replicated — same uneven-B bucket zoo, identity-slice
    # padding now in bf16.  Tolerance is a few bf16 ulps (2^-8), NOT
    # fp32-tight: jit-vs-eager fusion boundaries can move the fp32->bf16
    # rounding point, and the contractive chains keep such one-ulp
    # perturbations from growing.
    cfg16 = OptimizerConfig(prism=PrismConfig(degree=2, iterations=6,
                                              warm_alpha_iters=1,
                                              sketch_dim=8),
                            matfn_dtype="bfloat16", bucket_pad=True)
    ref16 = bucketing.polar_bucketed(views, cfg16, key)
    assert all(o.dtype == jnp.bfloat16 for o in ref16)
    with mesh, activation_sharding(
            mesh, {"opt_layers": "model", "opt_rows": "data"}):
        out16 = jax.jit(
            lambda vs: bucketing.polar_bucketed(vs, cfg16, key))(views)
    for r, o in zip(ref16, out16):
        np.testing.assert_allclose(np.asarray(r, np.float32),
                                   np.asarray(o, np.float32),
                                   rtol=0.05, atol=1.5 * 2.0 ** -8)

    # full Muon + Shampoo steps under the bf16 policy, sharded vs
    # replicated (bf16 staleness-cache state included via precond_every),
    # compared norm-level on the applied UPDATE — per-element checks are
    # brittle where grafting/aspect scaling amplifies one-ulp bf16 chain
    # divergence on isolated entries.  Muon runs the original mixed tree
    # (polar is well-conditioned: bf16-ulp-level parity).  Shampoo's
    # leaves get a controlled full-rank gradient spectrum instead: the
    # inverse root of a step-0 EMA factor G G^T of a WIDE G is rank-
    # deficient (eps-ridge cond ~1e6), where the principled u*kappa bf16
    # tolerance is vacuous — sharding of that case is already covered
    # tightly by the fp32 parity above; precision is the only new
    # variable here, tested where kappa keeps u*sqrt(kappa) meaningful.
    from repro.core import random_matrices as rm
    sq_sig = jnp.exp(jnp.linspace(jnp.log(0.3), 0.0, 48))
    sq_params = {"a": views[4], "c": jnp.ones((64,))}
    sq_axes = {"a": ("embed", "mlp"), "c": ("embed",)}
    sq_grads = {"a": rm.with_spectrum(jax.random.fold_in(key, 5), 48, 48,
                                      sq_sig),
                "c": jnp.ones((64,))}
    cases = (("muon", 0.05, params, axes_tree, grads, 2e-2),
             ("shampoo", 1e-3, sq_params, sq_axes, sq_grads, 5e-2))
    for name, lr, prms, axs, grds, tol in cases:
        ocfg16 = OptimizerConfig(name=name, learning_rate=lr,
                                 max_precond_dim=256,
                                 matfn_dtype="bfloat16", precond_every=2,
                                 prism=PrismConfig(degree=2, iterations=5,
                                                   warm_alpha_iters=1,
                                                   sketch_dim=8))
        o16 = make_optimizer(ocfg16, axs)
        q_ref, s_ref = jax.jit(o16.update)(grds, o16.init(prms), prms,
                                           0, key)
        with mesh, activation_sharding(
                mesh, {"opt_layers": "model", "opt_rows": "data"}):
            q_sh, s_sh = jax.jit(o16.update)(grds, o16.init(prms),
                                             prms, 0, key)
        for k in prms:
            d_ref = np.asarray(q_ref[k], np.float32) - np.asarray(prms[k])
            d_sh = np.asarray(q_sh[k], np.float32) - np.asarray(prms[k])
            rel = np.linalg.norm(d_ref - d_sh) / max(
                np.linalg.norm(d_ref), 1e-12)
            assert rel < tol, (name, k, rel)
    print("SHARDED_PRECOND_OK")
""")


def test_sharded_parity_8dev():
    """Sharded == replicated bucketed PRISM on an 8-device host mesh,
    including a bucket whose B does not divide the device count."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT],
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))),
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert "SHARDED_PRECOND_OK" in out.stdout, \
        out.stdout[-2000:] + out.stderr[-3000:]


INT8_PSUM_SCRIPT = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.optim import compression

    mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
    key = jax.random.PRNGKey(3)
    # per-shard distinct magnitudes: exercises the shared-max-scale
    # renormalization (ratio < 1 on 7 of 8 shards), odd length 1000
    # exercises the block pad
    x = jax.random.normal(key, (8, 1000)) * \\
        (10.0 ** jnp.arange(8)[:, None] / 1e3)

    def quantized(xs):
        return compression.int8_psum(xs, "data")

    def exact(xs):
        return jax.lax.psum(xs, "data")

    run_q, run_f = [jax.shard_map(
        f, mesh=mesh, in_specs=(P("data", None),),
        out_specs=P("data", None), check_vma=False)(x)
        for f in (quantized, exact)]
    # every shard returns the same reduced vector; quantization error is
    # bounded by half an int8 step of the LARGEST shard's block scale,
    # times the 8 contributions
    ref = np.asarray(run_f[0])
    step = np.abs(np.asarray(x)).max() / 127.0
    assert np.abs(np.asarray(run_q[0]) - ref).max() <= 8 * step, \\
        (np.abs(np.asarray(run_q[0]) - ref).max(), step)

    # collective census: the fixed int8_psum moves exactly ONE full-size
    # int32 psum (the payload) and ONE fp32 pmax (the [-,1] scale
    # column) — the dead second all-reduce stays dead
    def collectives(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("psum", "pmax", "pmin", "ppermute",
                                      "all_reduce", "psum2"):
                out.append((eqn.primitive.name,
                            eqn.invars[0].aval.dtype.name))
            for v in eqn.params.values():
                for j in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(j, "jaxpr"):      # ClosedJaxpr
                        collectives(j.jaxpr, out)
                    elif hasattr(j, "eqns"):     # raw Jaxpr
                        collectives(j, out)
        return out

    jaxpr = jax.make_jaxpr(jax.shard_map(
        quantized, mesh=mesh, in_specs=(P("data", None),),
        out_specs=P("data", None), check_vma=False))(x)
    seen = collectives(jaxpr.jaxpr, [])
    psums = [d for (n, d) in seen if n.startswith("psum")]
    pmaxs = [d for (n, d) in seen if n == "pmax"]
    assert psums == ["int32"], seen
    assert pmaxs == ["float32"], seen
    print("INT8_PSUM_OK")
""")


def test_int8_psum_parity_and_collective_census_8dev():
    """int8_psum on an 8-device mesh: matches the fp32 psum within
    quantization error, and its jaxpr contains exactly one int32 psum
    plus one fp32 pmax (regression for the dead duplicate all-reduce)."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", INT8_PSUM_SCRIPT],
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))),
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert "INT8_PSUM_OK" in out.stdout, \
        out.stdout[-2000:] + out.stderr[-3000:]


# ------------------------------------------------------------- staleness


def _tree(key):
    params = {"w1": jax.random.normal(key, (64, 32)),
              "w3": jax.random.normal(jax.random.fold_in(key, 2),
                                      (3, 48, 32)),
              "b": jax.random.normal(jax.random.fold_in(key, 4), (64,))}
    axes = {"w1": ("embed", "mlp"), "w3": ("layers", "embed", "mlp"),
            "b": ("embed",)}
    return params, axes


def test_staleness_reuses_cache_and_refreshes_on_K():
    """precond_every=K serves the cached orthogonalized update for K-1
    steps (cache bit-identical, update direction unchanged) and refreshes
    exactly on step K."""
    key = jax.random.PRNGKey(0)
    params, axes = _tree(key)
    ocfg = OptimizerConfig(name="muon", learning_rate=0.1,
                           weight_decay=0.0, precond_every=3,
                           prism=PrismConfig(degree=2, iterations=3,
                                             warm_alpha_iters=1,
                                             sketch_dim=8))
    opt = make_optimizer(ocfg, axes)
    state = opt.init(params)
    assert "ortho" in state["leaves"]["w1"]  # cache carried in state
    upd = jax.jit(opt.update)
    p = params
    deltas, orthos = [], []
    for t in range(4):
        g = jax.tree.map(
            lambda x: jax.random.normal(jax.random.fold_in(key, 50 + t),
                                        x.shape), p)
        p2, state = upd(g, state, p, t, jax.random.fold_in(key, t))
        deltas.append(np.asarray(p["w1"]) - np.asarray(p2["w1"]))
        orthos.append(np.asarray(state["leaves"]["w1"]["ortho"]))
        p = p2
    # steps 1, 2 (count % 3 != 0): cache bit-identical to the step-0 fill
    assert np.array_equal(orthos[0], orthos[1])
    assert np.array_equal(orthos[1], orthos[2])
    # update direction unchanged while stale (lr * scale * O_cached)
    np.testing.assert_allclose(deltas[0], deltas[1], atol=1e-6)
    np.testing.assert_allclose(deltas[1], deltas[2], atol=1e-6)
    # step 3 (count % 3 == 0): refresh — new momentum orthogonalized
    assert not np.array_equal(orthos[2], orthos[3])
    assert np.abs(deltas[3] - deltas[2]).max() > 1e-4


def test_static_refresh_matches_dynamic_schedule():
    """update(..., refresh=<bool>) picks the same branch the in-state
    count schedule would — params and caches agree step for step."""
    key = jax.random.PRNGKey(1)
    params, axes = _tree(key)
    ocfg = OptimizerConfig(name="muon", learning_rate=0.1,
                           weight_decay=0.0, precond_every=2,
                           prism=PrismConfig(degree=2, iterations=3,
                                             warm_alpha_iters=1,
                                             sketch_dim=8))
    opt = make_optimizer(ocfg, axes)
    upd = jax.jit(opt.update, static_argnums=(5,))
    grads = [jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(key, 30 + t),
                                    x.shape), params) for t in range(3)]
    outs = {}
    for mode in ("dynamic", "static"):
        p, s = params, opt.init(params)
        for t in range(3):
            refresh = None if mode == "dynamic" else (t % 2 == 0)
            p, s = upd(grads[t], s, p, t, jax.random.fold_in(key, t),
                       refresh)
        outs[mode] = (p, s)
    for k in params:
        np.testing.assert_allclose(np.asarray(outs["dynamic"][0][k]),
                                   np.asarray(outs["static"][0][k]),
                                   rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(outs["dynamic"][1]["leaves"]["w1"]["ortho"]),
        np.asarray(outs["static"][1]["leaves"]["w1"]["ortho"]),
        rtol=1e-6, atol=1e-6)


def test_shampoo_static_refresh_matches_dynamic():
    key = jax.random.PRNGKey(2)
    params, axes = _tree(key)
    ocfg = OptimizerConfig(name="shampoo", learning_rate=1e-3,
                           precond_every=2, max_precond_dim=256,
                           prism=PrismConfig(degree=2, iterations=8,
                                             sketch_dim=8))
    opt = make_optimizer(ocfg, axes)
    upd = jax.jit(opt.update, static_argnums=(5,))
    grads = [jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(key, 40 + t),
                                    x.shape), params) for t in range(3)]
    outs = {}
    for mode in ("dynamic", "static"):
        p, s = params, opt.init(params)
        for t in range(3):
            refresh = None if mode == "dynamic" else (t % 2 == 0)
            p, s = upd(grads[t], s, p, t, jax.random.fold_in(key, t),
                       refresh)
        outs[mode] = p
    for k in params:
        np.testing.assert_allclose(np.asarray(outs["dynamic"][k]),
                                   np.asarray(outs["static"][k]),
                                   rtol=1e-6, atol=1e-6)
