"""NamedSharding helpers shared by train/serve/dry-run paths.

Sharding conventions (see DESIGN.md §4):
  mesh axes: ("data", "model") single-pod / ("pod", "data", "model") multi-pod
  - batch-like dims        -> ("pod", "data") when multi_pod else ("data",)
  - tensor-parallel dims   -> "model"
  - replicated             -> None
"""
from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def batch_axes(mesh: Mesh) -> tuple:
    """The mesh axes that jointly shard the batch dimension."""
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


def spec_batch(mesh: Mesh, *rest: Any) -> P:
    """PartitionSpec with the leading dim sharded over the data(+pod) axes."""
    return P(batch_axes(mesh), *rest)


def ns(mesh: Mesh, spec: Optional[P]) -> NamedSharding:
    return NamedSharding(mesh, spec if spec is not None else P())


def shard_leaf(mesh: Mesh, spec: P, x):
    return jax.device_put(x, ns(mesh, spec))


def mesh_size(mesh: Mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n


def axis_size(mesh: Mesh, name: str) -> int:
    if name not in mesh.axis_names:
        return 1
    return mesh.devices.shape[mesh.axis_names.index(name)]


def tp_size(mesh: Mesh) -> int:
    return axis_size(mesh, "model")


def dp_size(mesh: Mesh) -> int:
    return axis_size(mesh, "data") * axis_size(mesh, "pod")


def check_divisible(dim: int, parts: int, what: str) -> None:
    if dim % parts != 0:
        raise ValueError(f"{what}={dim} not divisible by mesh factor {parts}")


def specs_like(tree, spec_fn) -> Any:
    """Map a function leaf->PartitionSpec over a pytree of arrays."""
    return jax.tree.map(spec_fn, tree)
