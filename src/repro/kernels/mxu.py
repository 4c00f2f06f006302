"""Contraction precision of the kernels' MXU dots (DESIGN.md §9)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def precision(*operands):
    """``HIGHEST`` when any operand is fp32, else the default.

    Mosaic contracts fp32 operands at bf16 precision unless told
    otherwise, which breaks the fp32 compute contract: on a TPU v5e the
    fp32 gram kernel then misses its fp32 oracle by 1e-3.  bf16 operands
    are exact on the MXU and need nothing.
    """
    if any(o.dtype == jnp.float32 for o in operands):
        return jax.lax.Precision.HIGHEST
    return None
