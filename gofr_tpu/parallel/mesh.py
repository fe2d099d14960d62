"""Device mesh construction for ICI-aware multi-chip execution.

The TPU-native replacement for the reference's horizontal scale-out
(stateless replicas behind brokers, SURVEY §2.9): scale comes from a
``jax.sharding.Mesh`` whose axes map onto ICI rings, with XLA inserting
the collectives. Axis conventions across the framework:

- ``dp``: data parallel (batch dim; gradient psum)
- ``pp``: pipeline parallel (layer stages; ppermute activations)
- ``tp``: tensor parallel (hidden/head dims; all-gather/reduce-scatter)
- ``sp``: sequence parallel for long context (ring attention); when a
  mesh has no dedicated ``sp`` axis, sequence sharding rides ``tp``
  (Megatron-style) via sharding constraints.
- ``ep``: expert parallel (MoE expert dim)

``create_mesh({"dp": 2, "tp": 4})`` uses all visible devices; sizes
must multiply to the device count (a trailing -1 axis is inferred).
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def mesh_axes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def create_mesh(axes: dict[str, int], devices=None) -> Mesh:
    """Build a Mesh from {axis: size}; one size may be -1 (inferred)."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    sizes = dict(axes)
    unknown = [k for k, v in sizes.items() if v == -1]
    if len(unknown) > 1:
        raise ValueError("at most one axis size may be -1")
    known = math.prod(v for v in sizes.values() if v != -1)
    if unknown:
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        sizes[unknown[0]] = n // known
    need = math.prod(sizes.values())
    if need > n:
        raise ValueError(f"mesh {sizes} needs {need} devices, have {n}")
    # a fully-specified smaller mesh uses the first `need` devices
    grid = np.array(devices[:need]).reshape(*sizes.values())
    return Mesh(grid, tuple(sizes.keys()))


def named(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, P(*spec))


def local_slice_size(mesh: Mesh, axis: str, dim: int) -> int:
    size = mesh_axes(mesh).get(axis, 1)
    if dim % size:
        raise ValueError(f"dim {dim} not divisible by {axis}={size}")
    return dim // size
