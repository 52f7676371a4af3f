"""Mesh-sharded execution: mesh helpers, shard_map programs, HLO assertions.

Eagerly exported: the mesh/layout helpers and the compiled-HLO collective
assertions (pure jax + regex, no heavy imports). ``ShardedExecutor`` and the
collectives module load lazily — they import ``exec/device.py``'s program
machinery, which callers of a bare ``make_mesh`` should not pay for.
"""

from hyperspace_tpu.check.hlo_lint import (
    assert_collectives,
    assert_shuffle_free,
    collective_counts,
    hlo_text_of,
)
from hyperspace_tpu.parallel.mesh import (
    DEFAULT_AXIS,
    device_of_bucket,
    make_mesh,
    make_mesh_2d,
    mesh_fingerprint,
    replicated,
    sharded,
    sharded_2d,
)

__all__ = [
    "DEFAULT_AXIS",
    "ShardedExecutor",
    "assert_collectives",
    "assert_shuffle_free",
    "collective_counts",
    "device_of_bucket",
    "hlo_text_of",
    "make_mesh",
    "make_mesh_2d",
    "mesh_fingerprint",
    "replicated",
    "sharded",
    "sharded_2d",
]


def __getattr__(name):
    if name == "ShardedExecutor":
        from hyperspace_tpu.parallel.executor import ShardedExecutor

        return ShardedExecutor
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
