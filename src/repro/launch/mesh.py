"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16 x 16 = 256 chips ("data","model").
Multi-pod: 2 x 16 x 16 = 512 chips ("pod","data","model") — the pod axis
composes with data parallelism, so batch and gradient all-reduce shard
across pods with no new code paths.
"""
from __future__ import annotations

import jax

from repro.core.sharding import make_mesh
from repro.models.common import MeshAxes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 512 if multi_pod else 256
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have "
            f"{len(devices)}; the dry-run sets "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return make_mesh(shape, axes, devices)


def make_axes(mesh) -> MeshAxes:
    """Logical axis bundle for a production mesh."""
    if mesh is None:
        return MeshAxes()
    if "pod" in mesh.axis_names:
        return MeshAxes(mesh=mesh, dp=("pod", "data"), fsdp="data",
                        tp="model")
    return MeshAxes(mesh=mesh, dp=("data",), fsdp="data", tp="model")
