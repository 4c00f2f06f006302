"""Mesh construction: the devices present, plus the fixed production and
debug shapes.

Every builder is a FUNCTION (never module-level state) so that importing
this module does not touch jax device state — required because the
dry-run process must set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def device_mesh_shape(n_devices: int) -> tuple:
    """(data, model) for ``n_devices`` chips: the model axis is the largest
    divisor d of n with d * d <= n (1 chip -> (1, 1), a v5e 2x2 host ->
    (2, 2), 8 devices -> (4, 2))."""
    model = max(d for d in range(1, n_devices + 1)
                if n_devices % d == 0 and d * d <= n_devices)
    return (n_devices // model, model)


def make_device_mesh(devices=None):
    """(data, model) mesh over the devices that are present (default
    ``jax.devices()``) — the mesh ``launch/train.py --mesh devices`` runs
    the sharded engine on."""
    devices = list(jax.devices() if devices is None else devices)
    return jax.make_mesh(device_mesh_shape(len(devices)), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: (data=16, model=16) per pod; the multi-pod
    variant adds a leading pure-DP "pod" axis (2 pods = 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(data: int = 2, model: int = 2, *,
                    multi_pod: bool = False, pods: int = 2):
    """Small mesh for CPU multi-device tests (device count forced by the
    caller via XLA_FLAGS before jax init)."""
    shape = (pods, data, model) if multi_pod else (data, model)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
