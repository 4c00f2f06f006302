"""Train/prefill attention: the fused Pallas kernel against the jnp chunk
loop, and the rule by which ``attend`` picks between them.

The kernel runs in the TPU interpreter here (``force_tpu_interpret_mode``)
at a small shape; the dispatch cases trace ``attend`` abstractly at a
sequence the kernel's block divides, with the backend check pointed at a
TPU where a case needs one.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AbstractMesh, AxisType

from repro.configs import get_config
from repro.models import attention as attn
from repro.sharding_ctx import activation_sharding

pytestmark = pytest.mark.tier1


def _fwd_bwd(f, q, k, v, dout):
    out, vjp = jax.vjp(f, q, k, v)
    return (out,) + vjp(dout)


@pytest.mark.parametrize("heads,kv_heads", [(16, 16), (8, 2)],
                         ids=["mha", "gqa"])
def test_kernel_matches_chunked_loop(heads, kv_heads):
    B, S, Hd = 1, 256, 64
    kq, kk, kv, kd = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(kq, (B, S, heads, Hd), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, kv_heads, Hd), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, kv_heads, Hd), jnp.bfloat16)
    dout = jax.random.normal(kd, (B, S, heads, Hd), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def chunked(q, k, v):
        return attn._flash_attend(q, k, v, pos, pos, 0, attn.KV_BLOCK)

    want = jax.jit(lambda *a: _fwd_bwd(chunked, *a))(q, k, v, dout)
    with pltpu.force_tpu_interpret_mode():
        got = jax.jit(lambda *a: _fwd_bwd(attn._kernel_attend, *a))(
            q, k, v, dout)
    for name, g, w in zip(["out", "dq", "dk", "dv"], got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        # two bf16 steps at the largest magnitude: the paths differ only
        # in summation order and where they round to bf16
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=2 ** -6 * np.abs(w).max(),
                                   err_msg=name)
        assert np.abs(g - w).mean() < 2 ** -7 * np.abs(w).mean(), name


def test_kernel_keeps_nothing_between_programs():
    """Each program that stages the kernel builds its own: a second jitted
    program (the benchmark's standalone probe after the train step) must
    not reach arrays of the first one's trace."""
    B, S, H, Hd = 1, 256, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, Hd), jnp.bfloat16)
    with pltpu.force_tpu_interpret_mode():
        first = jax.jit(attn._kernel_attend)(q, q, q)
        second = jax.jit(lambda q: _fwd_bwd(attn._kernel_attend, q, q, q,
                                            q)[0])(q)
    np.testing.assert_array_equal(np.asarray(first, np.float32),
                                  np.asarray(second, np.float32))


def _paths(window, S):
    """{path: calls} that one abstract trace of ``attend`` counts."""
    cfg = get_config("gpt2-paper").replace(d_model=128, num_heads=2,
                                           num_kv_heads=2)
    params = jax.eval_shape(
        lambda: attn.init_attention(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((1, S, cfg.d_model), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (1, S))
    before = attn.path_counts.copy()
    out, _ = jax.eval_shape(
        lambda p, x: attn.attend(p, x, pos, cfg, window=window), params, x)
    assert out.shape == x.shape
    return dict(attn.path_counts - before)


S_KERNEL = 2 * attn.KERNEL_BLOCK


@pytest.mark.parametrize("case,want", [
    ("no_window", "kernel"),
    ("window_ge_seq", "kernel"),
    ("window_lt_seq", "chunked"),
    ("cpu_backend", "chunked"),
    ("two_device_mesh", "chunked"),
])
def test_dispatch_rule(monkeypatch, case, want):
    if case != "cpu_backend":
        monkeypatch.setattr(attn.jax, "default_backend", lambda: "tpu")
    window = {"window_ge_seq": S_KERNEL,
              "window_lt_seq": S_KERNEL // 2}.get(case, 0)
    if case == "two_device_mesh":
        mesh = AbstractMesh((2,), ("model",), axis_types=(AxisType.Auto,))
        with activation_sharding(mesh, {"heads": "model"}):
            paths = _paths(window, S_KERNEL)
    else:
        paths = _paths(window, S_KERNEL)
    assert paths == {want: 1}


def test_kernel_needs_a_whole_number_of_blocks(monkeypatch):
    monkeypatch.setattr(attn.jax, "default_backend", lambda: "tpu")
    assert _paths(0, attn.KERNEL_BLOCK + 128) == {"chunked": 1}
