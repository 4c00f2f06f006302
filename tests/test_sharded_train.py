"""Multi-device integration: the sharded train step EXECUTES on an 8-way
CPU mesh (both sharding strategies), and checkpoints restore elastically
onto a different mesh shape.  Subprocess keeps the main test world
single-device."""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
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
    from repro.configs import get_smoke_config
    from repro.data import DataConfig, make_batch_fn
    from repro.launch import sharding as sh
    from repro.models import build
    from repro.optim import make_optimizer
    from repro.sharding_ctx import activation_sharding
    from repro.train.state import make_train_step, master_params, \\
        opt_state_shardings
    from repro import checkpoint as ckpt

    def run_steps(mesh_shape, strategy, ckpt_dir, resume, grads_dtype):
        from jax.sharding import AxisType
        mesh = jax.make_mesh(mesh_shape, ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = get_smoke_config("qwen3-14b").replace(
            d_model=64, num_heads=4, num_kv_heads=2, head_dim=16)
        model = build(cfg)
        ocfg = OptimizerConfig(name="muon", learning_rate=0.02,
                               grads_dtype=grads_dtype,
                               muon_local_reshard=(strategy == "zero"),
                               prism=PrismConfig(degree=2, iterations=2,
                                                 warm_alpha_iters=1,
                                                 sketch_dim=4))
        opt = make_optimizer(ocfg, model.logical_axes())
        rules = sh.param_rules(cfg, mesh, strategy)
        pshapes = model.param_shapes()
        pshard = sh.tree_shardings(mesh, model.logical_axes(), rules,
                                   pshapes)
        master_shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), pshapes)
        sshard = opt_state_shardings(mesh, opt, master_shapes, pshard)
        dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                          global_batch=8, markov_rank=8)
        batch_fn = make_batch_fn(cfg, dcfg)
        with mesh, activation_sharding(
                mesh, sh.activation_rules(cfg, mesh, strategy)):
            step = jax.jit(make_train_step(model, opt, ocfg),
                           in_shardings=(pshard, sshard, None, None),
                           out_shardings=(pshard, sshard, None))
            params = master_params(model.init(jax.random.PRNGKey(0)))
            params = jax.device_put(params, pshard)
            state = opt.init(params)
            start = 0
            if resume:
                s0, restored = ckpt.restore(
                    ckpt_dir, {"params": params, "opt": state},
                    shardings={"params": pshard, "opt": sshard})
                params, state, start = (restored["params"],
                                        restored["opt"], s0)
            losses = []
            for t in range(start, start + 3):
                params, state, metrics = step(params, state,
                                              batch_fn(jnp.asarray(t)),
                                              jnp.asarray(t, jnp.int32))
                losses.append(float(metrics["loss"]))
            if ckpt_dir and not resume:
                ckpt.save(ckpt_dir, start + 3,
                          {"params": params, "opt": state})
            return losses

    l1 = run_steps((2, 4), "tp", "/tmp/elastic_ck", False, "float32")
    assert all(np.isfinite(l1)), l1
    l2 = run_steps((4, 2), "zero", "/tmp/elastic_ck", True, "bfloat16")
    assert all(np.isfinite(l2)), l2
    assert l2[-1] < l1[0], (l1, l2)  # resumed training keeps improving
    print("SHARDED_TRAIN_OK", l1, l2)
""")


def test_sharded_train_and_elastic_resume():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", SCRIPT], cwd="/root/repo",
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert "SHARDED_TRAIN_OK" in out.stdout, out.stdout[-2000:] \
        + out.stderr[-3000:]
