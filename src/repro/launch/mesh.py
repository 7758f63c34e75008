"""Production mesh definitions.

Single pod : (16, 16)    -> ("data", "model")      256 chips
Multi-pod  : (2, 16, 16) -> ("pod", "data", "model") 512 chips

A FUNCTION, not a module constant: importing this module must never
touch jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax
import; tests and benches see 1 device).

Every mesh in the repo comes from :func:`make_mesh`, whose axes are
typed ``Auto``: the sharded code places arrays with explicit
NamedShardings and ``with_sharding_constraint`` and lets GSPMD
propagate the rest, so no caller has to enter a mesh context
(``jax.make_mesh`` alone would type them ``Explicit``).
"""
from __future__ import annotations

from typing import List, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with every axis typed ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(model: int = 1):
    """A tiny mesh on whatever devices exist (CPU tests)."""
    n = jax.device_count()
    if model < 1 or n % model:
        raise ValueError(
            f"model axis size {model} does not divide the {n} available "
            "device(s); pick a tp degree that divides jax.device_count() "
            "(CPU hosts can fake more via "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return make_mesh((n // model, model), ("data", "model"))


def make_submeshes(mesh: Mesh, tp_degree: int) -> List[Mesh]:
    """Carve ``mesh`` into engine-replica submeshes of ``tp_degree``
    devices each: consecutive device groups, every submesh shaped
    ``(1, tp_degree)`` over ``("data", "model")`` so a serving replica
    tensor-parallelizes over its own devices and shares nothing with
    its neighbours. Fleet placement (serving/pools.FleetRuntime) pins
    one engine per submesh."""
    devices = mesh.devices.reshape(-1)
    if tp_degree < 1 or devices.size % tp_degree:
        raise ValueError(
            f"tp_degree {tp_degree} does not divide the mesh's "
            f"{devices.size} device(s)")
    return [Mesh(devices[i:i + tp_degree].reshape(1, tp_degree),
                 ("data", "model"),
                 axis_types=(AxisType.Auto,) * 2)
            for i in range(0, devices.size, tp_degree)]
