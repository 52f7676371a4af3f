"""Device-mesh helpers: bucket id ≡ device shard.

The reference's parallelism is Spark hash-partitioning ("bucketing"); here the
same layout is a 1-D ``jax.sharding.Mesh`` where bucket ``b`` lives on device
``b % n_devices`` — so a bucketed join needs no collective at all, and
re-bucketing is one ``all_to_all`` over ICI (SURVEY.md §2.9, §5.8).
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DEFAULT_AXIS = "buckets"


def make_mesh(n_devices: Optional[int] = None, axis: str = DEFAULT_AXIS) -> Mesh:
    """1-D mesh over the first ``n_devices`` local devices (all by default).

    Raises ``ValueError`` when the request oversubscribes the runtime — a
    silently truncated mesh would shard programs across fewer devices than
    the caller planned capacity for."""
    devices = jax.devices()
    if n_devices is not None:
        if n_devices < 1:
            raise ValueError(f"n_devices must be >= 1, got {n_devices}")
        if n_devices > len(devices):
            raise ValueError(
                f"requested a {n_devices}-device mesh but only {len(devices)} "
                f"devices are available ({devices[0].platform}); on CPU, raise the "
                "count with XLA_FLAGS=--xla_force_host_platform_device_count=N"
            )
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis,))


def mesh_fingerprint(mesh: Mesh) -> str:
    """Stable identity of a mesh for program-cache keys: platform, device
    grid shape, and axis names. Two meshes with the same fingerprint compile
    to interchangeable executables (same partitioning), so single-device and
    sharded paths can share one skeleton cache keyed on
    ``(program skeleton, shape bucket, mesh fingerprint)``."""
    first = next(iter(mesh.devices.flat), None)
    platform = getattr(first, "platform", "none")
    shape = "x".join(str(s) for s in mesh.devices.shape)
    return f"{platform}:{shape}:{','.join(mesh.axis_names)}"


def device_of_bucket(bucket: int, n_devices: int) -> int:
    return bucket % n_devices

def sharded(mesh: Mesh, axis: Optional[str] = None) -> NamedSharding:
    axis = axis or mesh.axis_names[0]
    return NamedSharding(mesh, PartitionSpec(axis))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def make_mesh_2d(n_slices: Optional[int] = None, per_slice: Optional[int] = None) -> Mesh:
    """2-D (dcn, ici) mesh for multi-slice / multi-host topologies: the ici
    axis spans devices within a slice (fast interconnect), the dcn axis spans
    slices (data-center network). On a multi-host runtime the slice count
    defaults to ``jax.process_count()`` so the dcn axis aligns with host
    boundaries and XLA keeps phase-1 all_to_all traffic on ICI
    (SURVEY.md §5.8)."""
    devices = jax.devices()
    if n_slices is None:
        n_slices = max(1, jax.process_count())
    if per_slice is None:
        if len(devices) % n_slices:
            raise ValueError(
                f"{len(devices)} devices do not divide evenly into {n_slices} slices; "
                "pass per_slice explicitly"
            )
        per_slice = len(devices) // n_slices
    if n_slices * per_slice > len(devices):
        raise ValueError(
            f"requested {n_slices}x{per_slice} mesh but only {len(devices)} devices are available"
        )
    grid = np.array(devices[: n_slices * per_slice]).reshape(n_slices, per_slice)
    return Mesh(grid, ("dcn", "ici"))


def sharded_2d(mesh: Mesh) -> NamedSharding:
    """Row sharding of a 1-D array across every device of a 2-D mesh."""
    return NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))
