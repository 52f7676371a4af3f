"""ShardedExecutor: the conf-gated entry point of the mesh-sharded path.

One instance per (session, mesh shape): owns the 1-D ``("buckets",)`` mesh
the sharded programs run over, and is threaded (as the ``parallel=`` argument)
through ``exec/device.py``'s filter / grouped-aggregate entry points, which
switch from GSPMD jit to the explicit ``shard_map`` programs in
``parallel/collectives.py`` when it is present.

Gating (``ShardedExecutor.maybe``): ``hyperspace.parallel.enabled`` is the
default-off master switch — when off, ``maybe`` returns None and every caller
falls through to the byte-identical single-device path. The mesh is the
session's (``Session.mesh``: ``hyperspace.parallel.mesh.devices`` devices, 0 =
all local devices, on the session's bucket axis), the same the index build's
exchange runs over; chunks below ``hyperspace.parallel.minRows`` rows stay
on the single-device path even when the switch is on (per-shard padding and
the collective merge would dominate).

On CPU CI the mesh is emulated: conftest.py forces
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the sharded =
single-device oracle tests (tests/test_mesh_exec.py) are tier-1.
"""

from __future__ import annotations

import time
from typing import Optional

from hyperspace_tpu.parallel.mesh import mesh_fingerprint


class ShardedExecutor:
    """Holds the execution mesh and the sharded-path metrics instruments."""

    def __init__(self, session, mesh=None):
        conf = session.conf
        if mesh is None:
            mesh = session.mesh  # the one mesh of the session: the build's too
        self.session = session
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.fingerprint = mesh_fingerprint(mesh)
        # the sharded path stages with THIS mesh's device count: align the
        # native decode fast path's buffer padding so staging stays zero-copy
        from hyperspace_tpu.exec import io as _io

        _io.set_staging_pad(int(mesh.devices.size))
        self.min_rows = conf.parallel_min_rows
        from hyperspace_tpu.obs.metrics import REGISTRY

        REGISTRY.gauge(
            "hs_mesh_devices",
            "Devices in the sharded-execution mesh (0 when the parallel path is off)",
        ).set(mesh.devices.size)

    # -- gating ---------------------------------------------------------------

    @classmethod
    def maybe(cls, session) -> Optional["ShardedExecutor"]:
        """The session's executor, or None when ``hyperspace.parallel.enabled``
        is off. Memoized on the session per session mesh, so repeated queries
        reuse one executor (and its jit/device caches) until the mesh-shaping
        conf or ``set_mesh`` gives the session another mesh."""
        if not session.conf.parallel_enabled:
            return None
        mesh = session.mesh
        cached = getattr(session, "_parallel_executor", None)
        if cached is not None and cached[0] is mesh:
            return cached[1]
        px = cls(session, mesh)
        session._parallel_executor = (mesh, px)
        return px

    def rows_ok(self, n_rows: int) -> bool:
        return n_rows >= self.min_rows

    # -- metrics --------------------------------------------------------------

    def note_op(self, op: str) -> None:
        from hyperspace_tpu.obs.metrics import REGISTRY

        REGISTRY.counter(
            "hs_mesh_sharded_ops_total",
            "Operations executed through the mesh-sharded path",
            op=op,
        ).inc()

    def timed_call(self, op: str, family: str, fn, *args):
        """Run one sharded program of ``family`` synchronously, through the
        dispatch layer's launch/wait pair, attributing its wall time (including
        the collective merge) to ``hs_mesh_collective_seconds_total``."""
        from hyperspace_tpu.exec import device as D
        from hyperspace_tpu.obs.metrics import REGISTRY

        self.note_op(op)
        t0 = time.perf_counter()
        with D.launch(family):
            out = fn(*args)
        D.wait(out, family)
        REGISTRY.counter(
            "hs_mesh_collective_seconds_total",
            "Cumulative wall time of sharded programs incl. collective merges (seconds)",
        ).inc(time.perf_counter() - t0)
        return out

    # -- public execution API -------------------------------------------------

    def filter_mask(self, batch, condition, scan_key=None):
        """Sharded twin of ``device.device_filter_mask``."""
        from hyperspace_tpu.exec import device as D

        return D.device_filter_mask(
            self.session, batch, condition, scan_key=scan_key, parallel=self
        )

    def grouped_aggregate(
        self, batch, condition, group_keys, aggs, scan_key=None, *, max_groups, cap_floor
    ):
        """Sharded twin of ``device.device_grouped_aggregate``."""
        from hyperspace_tpu.exec import device as D

        return D.device_grouped_aggregate(
            self.session, batch, condition, group_keys, aggs, scan_key,
            max_groups=max_groups, cap_floor=cap_floor, parallel=self,
        )

    def grouped_stream(self, group_keys, aggs, *, max_groups, cap_floor, hint_key=None):
        """A ``GroupedAggStream`` whose chunk programs run sharded."""
        from hyperspace_tpu.exec import device as D

        return D.GroupedAggStream(
            self.session, group_keys, aggs,
            max_groups=max_groups, cap_floor=cap_floor, hint_key=hint_key,
            parallel=self,
        )
