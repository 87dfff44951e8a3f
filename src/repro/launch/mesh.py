"""Production mesh factories.

Functions, not module-level constants: importing this module never touches
jax device state (device count is locked at first jax init, and smoke tests
must see 1 CPU device while the dry-run sees 512 placeholders).
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Tiny mesh over the real local devices (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return jax.make_mesh(
        (data, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )


def replica_device_groups(
    n_replicas: int, devices: Sequence | None = None
) -> list[tuple]:
    """Partition the visible devices into ``n_replicas`` data-parallel
    groups (one serving replica per group).

    Devices split contiguously and as evenly as possible; with more
    replicas than devices the assignment wraps, so oversubscribed hosts
    (every CPU test topology) still get one distinct group per replica
    rather than an error.
    """
    if n_replicas < 1:
        raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs)
    if n_replicas >= n:
        return [(devs[i % n],) for i in range(n_replicas)]
    per, extra = divmod(n, n_replicas)
    groups, start = [], 0
    for i in range(n_replicas):
        size = per + (1 if i < extra else 0)
        groups.append(tuple(devs[start : start + size]))
        start += size
    return groups


def make_replica_mesh(devices: Sequence, *, data: int = 1) -> Mesh:
    """("data", "model") mesh over ONE replica's device group: tensor
    parallelism inside the replica, data parallelism across replicas
    handled above the mesh by the service's :class:`ReplicaRouter`."""
    devs = np.asarray(list(devices), dtype=object)
    if data < 1 or len(devs) % data:
        raise ValueError(
            f"data={data} does not divide {len(devs)} replica devices"
        )
    return Mesh(devs.reshape(data, len(devs) // data), ("data", "model"))
