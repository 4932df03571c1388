"""Production mesh construction (DESIGN.md §4).

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") --
            the pod axis carries cross-pod data parallelism (compressed
            gradient exchange, train/compression.py).

A function, not a module constant: importing this module never touches
jax device state.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1, data: int | None = None):
    """Small mesh over whatever devices exist (tests / examples).

    The factorization is validated up front: `model` larger than the
    device count used to silently derive a 0-sized data axis
    (`data = n // model`), surfacing later as an opaque mesh-shape error.
    """
    n = len(jax.devices())
    if model < 1:
        raise ValueError(f"make_host_mesh: model={model}; axis sizes must "
                         f"be >= 1")
    if model > n:
        raise ValueError(
            f"make_host_mesh: model={model} exceeds the {n} available "
            f"device(s) -- the derived data axis n // model would be "
            f"zero-sized.  Shrink model or launch with more devices "
            f"(e.g. XLA_FLAGS=--xla_force_host_platform_device_count=N).")
    if data is None:
        data = n // model
    if data < 1:
        raise ValueError(f"make_host_mesh: data={data}; axis sizes must "
                         f"be >= 1")
    if data * model > n:
        raise ValueError(
            f"make_host_mesh: a ({data}, {model}) mesh needs "
            f"{data * model} devices but only {n} exist")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
