"""The precisions the references run in, shared by every model family:
the configuration's own (``identity``) and the controls', one step
below what the configuration states.  Each is applied to both operands
of a matrix product."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def identity(x):
    return x


def fp8_round(x: jax.Array) -> jax.Array:
    """Per-tensor scaled round trip through float8_e4m3fn: the control's
    precision, one step below the bfloat16 the model computes in."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def bf16_round(x: jax.Array) -> jax.Array:
    """Round trip through bfloat16: the control's precision, one step
    below the float32 the chain is configured to run in."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)
