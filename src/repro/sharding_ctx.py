"""Logical activation-sharding context (MaxText-style constraints).

Model code annotates activations with *logical* axis names:

    x = shard_activation(x, ("batch", "seq", "embed"))

Outside any context this is a no-op (CPU tests, single-device runs).  The
launcher/dry-run installs (mesh, rules) via ``activation_sharding(...)``;
annotations then become ``with_sharding_constraint``s.  Without them GSPMD
happily picks replicated layouts for scan carries (verified on the dry-run:
attention ran fully replicated across the model axis because the
online-softmax carry had no sharding preference).
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional, Tuple

import jax

_CTX: list = []


@contextlib.contextmanager
def activation_sharding(mesh, rules: Dict[str, Any]):
    _CTX.append((mesh, rules))
    try:
        yield
    finally:
        _CTX.pop()


@contextlib.contextmanager
def suspend_activation_sharding():
    """Temporarily disable activation constraints (trace-time scoped).

    Used while tracing code that runs *inside* a shard_map body manual
    over some mesh axis (the 1F1B pipeline stages): there,
    ``with_sharding_constraint`` against the full mesh is illegal, and
    GSPMD infers layouts for the remaining auto axes on its own."""
    saved = list(_CTX)
    _CTX.clear()
    try:
        yield
    finally:
        _CTX.clear()
        _CTX.extend(saved)


def current() -> Optional[Tuple[Any, Dict[str, Any]]]:
    return _CTX[-1] if _CTX else None


def shard_activation(x: jax.Array, axes: Tuple) -> jax.Array:
    ctx = current()
    if ctx is None:
        return x
    mesh, rules = ctx
    from jax.sharding import NamedSharding, PartitionSpec as P

    entries = []
    used: set = set()  # dedup: batch (leftmost) wins over later axes
    for dim, a in zip(x.shape, axes):
        e = rules.get(a, None)
        if e is not None:
            axs = [ax for ax in (e if isinstance(e, (tuple, list))
                                 else (e,)) if ax not in used]
            n = 1
            for ax in axs:
                n *= mesh.shape[ax]
            # constraints tolerate uneven (padded) sharding, unlike jit
            # in_shardings; only refuse when shards would outnumber rows
            if not axs or dim < n:
                e = None
            else:
                used.update(axs)
                e = tuple(axs) if len(axs) > 1 else axs[0]
        entries.append(e)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*entries)))
