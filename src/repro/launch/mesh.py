"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* the first
jax device query, while smoke tests and benches must keep seeing 1 device.

Axes are *roles*, not sizes: everything downstream reads sizes from the mesh
object, so scaling to a 64-pod ``(64, 16, 16)`` mesh is config-only.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(*, multi_pod: bool = False):
    """Tiny mesh with the same axis roles — for fast sharding-rule tests on
    CPU (requires >= 8 host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_shard_mesh(n_shards: int):
    """``("data", "model") = (1, n_shards)`` over the first ``n_shards`` of
    ``jax.devices()``: one index shard per device, queries replicated —
    the sharded-DEG layout on one host (e.g. the four chips of a v5e
    2x2)."""
    devices = jax.devices()
    if len(devices) < n_shards:
        raise ValueError(f"{n_shards} shards need {n_shards} devices, "
                         f"have {len(devices)}")
    return jax.make_mesh((1, n_shards), ("data", "model"),
                         devices=devices[:n_shards])


def batch_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (pod axis included when present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def model_axis(mesh) -> str:
    return "model"


def mesh_devices(mesh) -> int:
    import numpy as np

    return int(np.prod(mesh.devices.shape))
