"""The optimizer's Pallas kernels compile for a TPU v5e.

Compiles (never runs) each kernel for a described ``v5e:2x2`` topology
at gpt2-paper's real Muon bucket shapes — the §7 grid kernels (gram,
the Gram side's power product matmul_add_upper, matmul_add,
sketch_chain) — and the §10 fused trio at a shape its VMEM
model accepts, in fp32 and bf16, and checks that the Mosaic kernel is in
the compiled program; and the model's fused causal-attention kernel,
forward and backward, at gpt2-paper's attention shape.  What the chip's
compiler refuses (unaligned slices, too much VMEM) fails here at no chip
time; results and times need the chip (``chip_smoke.py``).

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and under pytest-xdist every worker
imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import polynomials as poly
from repro.kernels import fused_iter, gram, matmul_add, ops, sketch_traces
from repro.models import attention, build
from repro.optim import bucketing
from repro.optim.muon import matrix_view_shapes

pytestmark = pytest.mark.tier1

DTYPES = ["float32", "bfloat16"]
MAX_POWER = poly.max_trace_power(poly.newton_schulz_residual(2))
FUSED_SHAPE = (4, 512, 512)


@pytest.fixture(scope="module")
def one_chip():
    """A described v5e chip, with JAX's persistent compilation cache off:
    such compiles are written to the cache but cannot be read back
    without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    old_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # no compiler logs under /tmp
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()
    if old_log is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = old_log


@pytest.fixture(scope="module")
def gpt2_buckets():
    """[B, m, n] of every gpt2-paper Muon bucket as the polar chain sees
    it (transposed to m >= n)."""
    model = build(get_config("gpt2-paper"))
    shapes = matrix_view_shapes(model.param_shapes(), model.logical_axes())
    return [(b.size, max(b.shape), min(b.shape))
            for b in bucketing.plan_buckets(shapes)]


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _assert_kernel(fn, *specs):
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text, text[:2000]


def test_gpt2_buckets_cover_both_tiers(gpt2_buckets):
    """The shapes below are gpt2-paper's: the qkv-bias bucket on the fused
    tier, the attention and MLP weights on the grid tier."""
    assert sorted(gpt2_buckets) == [(20, 4096, 1024), (30, 64, 16),
                                    (40, 1024, 1024)]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kernel", ["gram", "matmul_add_upper", "matmul_add",
                                    "sketch_chain"])
def test_grid_kernel_compiles(one_chip, gpt2_buckets, kernel, dtype):
    for B, m, n in gpt2_buckets:
        X = _spec((B, m, n), dtype, one_chip)
        R = _spec((B, n, n), dtype, one_chip)
        if kernel == "gram":
            _assert_kernel(functools.partial(gram.gram_upper,
                                             interpret=False), X)
        elif kernel == "matmul_add_upper":
            al = _spec((B,), "float32", one_chip)
            _assert_kernel(lambda r, a: gram.matmul_add_upper(
                r, r, a, r, beta=0.5, interpret=False), R, al)
        elif kernel == "matmul_add":
            _assert_kernel(lambda a, r, c: matmul_add.matmul_add(
                a, r, c, beta=0.5, interpret=False), X, R, X)
        else:
            St = _spec((n, 128), dtype, one_chip)
            _assert_kernel(lambda r, s: sketch_traces.sketch_chain(
                r, s, MAX_POWER, interpret=False), R, St)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kernel", ["residual_chain", "apply_g",
                                    "warm_tail"])
def test_fused_kernel_compiles(one_chip, kernel, dtype):
    B, m, n = FUSED_SHAPE
    assert ops.fused_fits((m, n), jnp.dtype(dtype))
    X = _spec(FUSED_SHAPE, dtype, one_chip)
    coeffs = ops._gd_coeffs(2)
    if kernel == "residual_chain":
        St = _spec((n, 128), dtype, one_chip)
        _assert_kernel(lambda x, s: fused_iter.residual_chain(
            x, s, MAX_POWER, family="polar", interpret=False), X, St)
    elif kernel == "apply_g":
        R = _spec((B, n, n), dtype, one_chip)
        a = _spec((B,), "float32", one_chip)
        _assert_kernel(lambda x, r, al: fused_iter.apply_g(
            x, r, al, coeffs=coeffs, interpret=False), X, R, a)
    else:
        alphas = _spec((3,), "float32", one_chip)
        _assert_kernel(lambda x, al: fused_iter.warm_tail(
            x, al, 3, family="polar", coeffs=coeffs, interpret=False),
            X, alphas)


def test_fused_shape_is_largest_fp32_fit():
    """FUSED_SHAPE sits at the fp32 edge of the fused tier's VMEM model:
    the next lane multiple no longer fits."""
    _, m, n = FUSED_SHAPE
    assert ops.fused_fits((m, n), np.float32)
    assert not ops.fused_fits((m + 128, n + 128), np.float32)


def test_attention_kernel_compiles(one_chip):
    """The fused attention's forward and backward at gpt2-paper's
    [B, S, H, Hd] = [8, 1024, 16, 64] in bf16, blocks as committed."""
    B, H, S, Hd = 8, 16, 1024, 64
    assert S % attention.KERNEL_BLOCK == 0 and \
        Hd in attention.KERNEL_HEAD_DIMS
    x = _spec((B, S, H, Hd), "bfloat16", one_chip)

    def fwd_bwd(q, k, v, dout):
        out, vjp = jax.vjp(attention._kernel_attend, q, k, v)
        return (out,) + vjp(dout)

    compiled = jax.jit(fwd_bwd).lower(x, x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"attention kernel fwd+bwd temporaries: {temp} bytes")
    # q, k, v, dout and the kernel's operands in heads-first layout, the
    # output and its three cotangents: a few [B, S, H, Hd] bf16 arrays,
    # never an [S, S] score matrix (512 MiB in float32 at this shape)
    assert temp < 16 * B * S * H * Hd * 2
