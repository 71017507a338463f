"""Production mesh construction.

Axes: ('pod', 'data', 'model'). 'pod' carries only DP whose gradient
all-reduce is the sole cross-pod collective; 'data' is FSDP; 'model' is TP.
A FUNCTION (not a module constant) so importing never touches jax device
state — the dry-run must set XLA_FLAGS before any jax initialization.

Every mesh is built by :func:`make_mesh` with Auto axis types: the
models place tensors with ``with_sharding_constraint`` and let XLA
propagate shardings, which Explicit axes (``jax.make_mesh``'s default)
refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — the "
            f"dry-run entrypoint must set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            f"any jax import")
    return make_mesh(shape, axes, devices=devices[:n])


def make_host_mesh(model: int = 1):
    """Whatever this host has — for examples/tests (usually (1, 1))."""
    n = len(jax.devices())
    data = max(1, n // model)
    return make_mesh((data, model), ("data", "model"),
                     devices=jax.devices()[:data * model])
