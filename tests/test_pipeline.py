"""Pipeline-parallel schedules: GPipe forward + the 1F1B training engine.

The heavy checks run in subprocesses with forced host devices (the test
process itself must keep the default single-device world):

  * GPipe forward == sequential reference;
  * 1F1B toy grads == explicit per-microbatch VJP accumulation, bitwise,
    for BOTH handover implementations (ppermute and the scatter+psum
    fallback), plus the scan-length/tick contract read off the jaxpr;
  * 1F1B on the real smoke transformer: a 2-stage run reproduces the
    single-stage run bitwise at fp32 and within bf16 tolerance at bf16.
"""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs import get_smoke_config
from repro.launch.pipeline import bubble_fraction, n_ticks_1f1b
from repro.models.transformer import Model
from repro.train.state import make_pipeline_stage_fn

PIN = textwrap.dedent("""
    import os
    # pin CPU BEFORE jax imports: with libtpu in the image an unset
    # JAX_PLATFORMS makes jax probe the TPU metadata server for minutes
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
    os.environ.setdefault("REPRO_KERNEL_MODE", "ref")
    import sys
    sys.path.insert(0, "src")
""")


def _run(script: str, devices: int, timeout: int = 420):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = "src"
    return subprocess.run(
        [sys.executable, "-c", PIN.format(n=devices) + script],
        cwd="/root/repo", env=env, capture_output=True, text=True,
        timeout=timeout)


def test_schedule_contract():
    """Tick count and bubble model of the 1F1B schedule (DESIGN.md §13)."""
    for S in (1, 2, 4, 8):
        for M in (1, 2, 4, 8, 16):
            T = n_ticks_1f1b(S, M)
            assert T == M + 2 * (S - 1)
            b = bubble_fraction(S, M)
            assert 0.0 <= b < 1.0
            # more microbatches amortize the fixed fill+drain
            assert bubble_fraction(S, 2 * M) <= b
    assert bubble_fraction(1, 4) == 0.0  # no pipeline, no bubble


@pytest.mark.parametrize("arch, refused", [("gpt2-paper", False),
                                           ("moonlight-16b-a3b", True)])
def test_stage_fn_refuses_leading_dense_layers(arch, refused):
    """The stages split only the scanned ``layers``: a model with leading
    dense layers would lose them silently, so it is refused."""
    model = Model(get_smoke_config(arch))
    if refused:
        with pytest.raises(NotImplementedError, match="leading dense"):
            make_pipeline_stage_fn(model)
    else:
        assert callable(make_pipeline_stage_fn(model))


SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.launch.pipeline import pipeline_apply
    from jax.sharding import AxisType

    mesh = jax.make_mesh((4,), ("pod",), axis_types=(AxisType.Auto,))
    S, L_PER, D = 4, 2, 16

    def stage_fn(params, x):  # params [L_PER, D, D]
        def body(c, w):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, params)
        return y

    key = jax.random.PRNGKey(0)
    Ws = jax.random.normal(key, (S, L_PER, D, D)) / np.sqrt(D)
    x = jax.random.normal(jax.random.fold_in(key, 1), (8, D))

    # sequential reference: all 8 layers in order
    ref = x
    for s in range(S):
        ref = stage_fn(Ws[s], ref)

    with mesh:
        got = pipeline_apply(mesh, stage_fn, Ws, x, n_micro=4, axis="pod")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    print("PIPELINE_OK")
""")


def test_gpipe_matches_sequential():
    out = _run(SCRIPT, devices=4)
    assert "PIPELINE_OK" in out.stdout, out.stdout + out.stderr


ONE_F_ONE_B_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.launch.pipeline import n_ticks_1f1b, one_f_one_b

    mesh = jax.make_mesh((4,), ("pod",), axis_types=(AxisType.Auto,))
    S, L_PER, D, MB = 4, 2, 8, 4

    def stage_fn(shared, lay, inp, x, is_first, is_last):
        # shared head weight seeds the loss on the last stage (the other
        # stages contribute exact zeros to its psum'd grad, keeping the
        # comparison bitwise); first stage consumes inp instead of the
        # incoming activation
        x = jnp.where(is_first, inp, x)
        def body(c, w):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, lay)
        loss = jnp.where(is_last, jnp.mean((y @ shared["head"]) ** 2), 0.0)
        return y, jnp.stack([loss.astype(jnp.float32)])

    key = jax.random.PRNGKey(0)
    shared = {"head": 0.3 * jax.random.normal(key, (D, D))}
    Ws = jax.random.normal(jax.random.fold_in(key, 1),
                           (S, L_PER, D, D)) / np.sqrt(D)
    M = 4
    inp = jax.random.normal(jax.random.fold_in(key, 2), (M, MB, D))

    # reference: explicit per-microbatch VJP accumulation of the SAME
    # staged computation (all stages unrolled in one function)
    def full(shared, Ws, x):
        for s in range(S):
            y, l = stage_fn(shared, Ws[s], x, x, s == 0, s == S - 1)
            x = y
        return l[0]

    ref_loss = jnp.float32(0)
    ref_gs = jax.tree.map(jnp.zeros_like, shared)
    ref_gw = jnp.zeros_like(Ws)
    for m in range(M):
        (l, (gs, gw)) = jax.value_and_grad(full, argnums=(0, 1))(
            shared, Ws, inp[m])
        ref_loss += l / M
        ref_gs = jax.tree.map(lambda a, b: a + b / M, ref_gs, gs)
        ref_gw += gw / M

    act = jax.ShapeDtypeStruct((MB, D), jnp.float32)
    for use_ppermute in (True, False):
        run = one_f_one_b(stage_fn, "pod", S, M, act,
                          use_ppermute=use_ppermute)
        mapped = jax.shard_map(
            run, mesh=mesh,
            in_specs=(P(), P("pod"), P(), P("pod")),
            out_specs=(P(), P(), P("pod")), check_vma=False)
        with mesh:
            loss, g_sh, g_lay = jax.jit(mapped)(
                shared, Ws, inp, jnp.arange(S, dtype=jnp.int32))
        tag = "ppermute" if use_ppermute else "psum"
        assert np.asarray(loss[0]).tobytes() == \
            np.asarray(ref_loss).tobytes(), (tag, loss, ref_loss)
        for (pa, a), (_, b) in zip(
                jax.tree_util.tree_leaves_with_path(g_sh),
                jax.tree_util.tree_leaves_with_path(ref_gs)):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), \
                (tag, pa)
        assert np.asarray(g_lay).tobytes() == \
            np.asarray(ref_gw).tobytes(), tag
        print("HANDOVER_OK", tag)

    # tick contract: the engine's scan really runs
    # n_micro + 2*(n_stages-1) ticks
    for m in (4, 8):
        run = one_f_one_b(stage_fn, "pod", S, m, act)
        mapped = jax.shard_map(
            run, mesh=mesh,
            in_specs=(P(), P("pod"), P(), P("pod")),
            out_specs=(P(), P(), P("pod")), check_vma=False)
        jpr = str(jax.make_jaxpr(mapped)(
            shared, Ws, inp[:1].repeat(m, 0),
            jnp.arange(S, dtype=jnp.int32)))
        T = n_ticks_1f1b(S, m)
        assert f"length={T}" in jpr, (m, T)
        print("TICKS_OK", m, T)
    print("ONE_F_ONE_B_OK")
""")


def test_1f1b_toy_bitwise_and_ticks():
    out = _run(ONE_F_ONE_B_SCRIPT, devices=4)
    assert "ONE_F_ONE_B_OK" in out.stdout, out.stdout + out.stderr[-4000:]
    assert "HANDOVER_OK ppermute" in out.stdout
    assert "HANDOVER_OK psum" in out.stdout


MODEL_PARITY_SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_smoke_config
    from repro.data import DataConfig, make_batch_fn
    from jax.sharding import AxisType
    from repro.models import build
    from repro.train.state import master_params, pipeline_loss_and_grads

    def grads_at(stages, dtype):
        cfg = get_smoke_config("qwen3-14b").replace(
            d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
            dtype=dtype)
        model = build(cfg)
        params = master_params(model.init(jax.random.PRNGKey(0)))
        batch = make_batch_fn(cfg, DataConfig(
            vocab_size=cfg.vocab_size, seq_len=32, global_batch=8,
            seed=0, markov_rank=8))(jnp.asarray(0))
        mesh = jax.make_mesh((stages,), ("pod",),
                             axis_types=(AxisType.Auto,))
        lag = pipeline_loss_and_grads(model, mesh, n_micro=4)
        with mesh:
            loss, grads, _ = jax.jit(lag)(params, batch)
        return (np.asarray(loss),
                [(p, np.asarray(a)) for p, a in
                 jax.tree_util.tree_leaves_with_path(grads)])

    # fp32: the S=2 pipeline must reproduce the S=1 run of the SAME
    # engine bitwise — identical per-microbatch compute, identical
    # accumulation order, only the stage split differs
    l1, g1 = grads_at(1, "float32")
    l2, g2 = grads_at(2, "float32")
    assert l1.tobytes() == l2.tobytes(), (l1, l2)
    for (p, a), (_, b) in zip(g1, g2):
        assert a.tobytes() == b.tobytes(), p
    print("FP32_BITWISE_OK", float(l1))

    # bf16: reduced-precision handover makes bitwise too strict; the two
    # runs must still agree to bf16 resolution
    l1, g1 = grads_at(1, "bfloat16")
    l2, g2 = grads_at(2, "bfloat16")
    assert abs(float(l1) - float(l2)) <= 0.05 * abs(float(l1)), (l1, l2)
    for (p, a), (_, b) in zip(g1, g2):
        a, b = a.astype(np.float32), b.astype(np.float32)
        tol = 0.05 * max(np.abs(a).max(), 1e-3)
        assert np.abs(a - b).max() <= tol, (p, np.abs(a - b).max(), tol)
    print("BF16_TOL_OK", float(l1))
    print("MODEL_PARITY_OK")
""")


def test_1f1b_model_grad_parity():
    out = _run(MODEL_PARITY_SCRIPT, devices=2, timeout=560)
    assert "MODEL_PARITY_OK" in out.stdout, out.stdout + out.stderr[-4000:]
    assert "FP32_BITWISE_OK" in out.stdout
    assert "BF16_TOL_OK" in out.stdout
