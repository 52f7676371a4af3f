"""Session: configuration + source providers + the optimizer kill-switch.

Plays the role of SparkSession in the reference: carries conf, hosts the
provider manager and the (caching) index collection manager, and owns the
"Hyperspace enabled" flag that installs the optimizer rule
(ref: ``spark.enableHyperspace()``, HS/package.scala:29-69).

Also owns the device mesh used by the TPU execution layer: bucket id ≡ device
shard (SURVEY.md §5.8).
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Any, Dict, List, Optional

from hyperspace_tpu.config import HyperspaceConf
from hyperspace_tpu.sources.manager import FileBasedSourceProviderManager


class Session:
    def __init__(self, conf: Optional[Dict[str, Any]] = None):
        # multi-process runtimes come up before any device is touched —
        # ONLY when the env explicitly configures one (both HS_NUM_PROCESSES
        # and HS_PROCESS_ID), so Session() stays side-effect-free otherwise
        # (SURVEY §5.8)
        from hyperspace_tpu.parallel.distributed import configured_from_env, initialize_from_env
        from hyperspace_tpu.utils.x64 import ensure_x64

        if configured_from_env():
            initialize_from_env()
        # the device layer needs int64 keys / float64 sketch bounds; enabling
        # x64 here (not at import) keeps `import hyperspace_tpu` free of
        # global JAX side effects — documented in docs/configuration.md
        ensure_x64()
        self.conf = HyperspaceConf(conf)
        # apply the configured decode-pool width (the pool is process-global;
        # the most recently constructed session's conf wins, env overrides)
        from hyperspace_tpu.exec import io as _io

        _io.set_decode_threads(self.conf.io_decode_threads)
        _io.set_native_options(
            enabled=self.conf.io_native_enabled,
            rowgroup=self.conf.io_native_rowgroup,
            max_dict_entries=self.conf.io_native_max_dict_entries,
        )
        # the device column cache is the process's too: its budget follows
        # the most recently constructed session
        from hyperspace_tpu.exec import device as _device

        _device.set_device_cache_bytes(self.conf.device_cache_bytes)
        # check-layer runtime switches are process-global for the same
        # reason (compile sites without a session in scope consult them).
        # HLO verification: most recent session's conf wins, like decode
        # threads. Lock watching is enable-only: locks wrap at construction,
        # so a later Session with the flag off can't unwrap them anyway.
        from hyperspace_tpu.check import hlo_lint as _hlo_lint
        from hyperspace_tpu.check import locks as _locks

        _hlo_lint.set_default_enabled(self.conf.check_hlo_enabled)
        if self.conf.check_locks_enabled:
            _locks.watcher.enable()
        # reliability registries (fault injection, retry policy, quarantine
        # breakers) are process-global like the decode pool; all default-off
        from hyperspace_tpu import reliability as _reliability

        _reliability.configure(self)
        self.provider_manager = FileBasedSourceProviderManager(self)
        # context-local override beats the session-wide default, so a scoped
        # toggle (with_hyperspace_disabled, a serving worker pinning the flag
        # captured at submit) never leaks into queries racing on other threads
        self._hyperspace_override: contextvars.ContextVar = contextvars.ContextVar(
            "hyperspace_enabled_override", default=None
        )
        self.hyperspace_enabled = False
        self._index_manager = None
        self._lifecycle_bus = None
        self._mesh = None
        self._mesh_key = None
        self._temp_views: Dict[str, Any] = {}
        # most recent QueryProfile from a traced collect() (obs tracing on)
        self._last_profile = None
        # lazily-built fingerprint-keyed ProfileHistory for ad-hoc queries
        # (QueryServer instances own their own, registry-labeled per server)
        self._profile_history = None
        # scale-out fabric runtime (commit watcher + coherence sidecar) —
        # None at defaults; wired last so its bus subscription and watcher
        # see a fully-constructed session
        from hyperspace_tpu import fabric as _fabric

        self._fabric = _fabric.configure(self)

    # --- reading data ------------------------------------------------------
    def read(self, paths, file_format: str, **options) -> "DataFrame":  # noqa: F821
        from hyperspace_tpu.plan.dataframe import DataFrame
        from hyperspace_tpu.plan.logical import Scan

        if isinstance(paths, str):
            paths = [paths]
        relation = self.provider_manager.create_relation((list(paths), file_format, options))
        return DataFrame(Scan(relation), self)

    def read_parquet(self, *paths, **options) -> "DataFrame":  # noqa: F821
        return self.read(list(paths), "parquet", **options)

    def read_csv(self, *paths, **options) -> "DataFrame":  # noqa: F821
        return self.read(list(paths), "csv", **options)

    def read_json(self, *paths, **options) -> "DataFrame":  # noqa: F821
        return self.read(list(paths), "json", **options)

    def read_orc(self, *paths, **options) -> "DataFrame":  # noqa: F821
        return self.read(list(paths), "orc", **options)

    def read_avro(self, *paths, **options) -> "DataFrame":  # noqa: F821
        return self.read(list(paths), "avro", **options)

    def read_text(self, *paths, **options) -> "DataFrame":  # noqa: F821
        return self.read(list(paths), "text", **options)

    def read_delta(self, path, version: Optional[int] = None) -> "DataFrame":  # noqa: F821
        from hyperspace_tpu.plan.dataframe import DataFrame
        from hyperspace_tpu.plan.logical import Scan
        from hyperspace_tpu.sources.delta import DeltaLakeRelation

        return DataFrame(Scan(DeltaLakeRelation(path, version=version)), self)

    def read_iceberg(self, path, snapshot_id: Optional[int] = None) -> "DataFrame":  # noqa: F821
        from hyperspace_tpu.plan.dataframe import DataFrame
        from hyperspace_tpu.plan.logical import Scan
        from hyperspace_tpu.sources.iceberg import IcebergRelation

        return DataFrame(Scan(IcebergRelation(path, snapshot_id=snapshot_id)), self)

    # --- SQL (the reference's users drive Hyperspace through Spark SQL) ----
    def sql(self, query: str) -> "DataFrame":  # noqa: F821
        from hyperspace_tpu.plan.sql import run_sql

        return run_sql(query, self)

    def register_view(self, name: str, df: "DataFrame") -> None:  # noqa: F821
        self._temp_views[name] = df

    def drop_view(self, name: str) -> None:
        self._temp_views.pop(name, None)

    # --- hyperspace toggle (ref: HS/package.scala:36-43) -------------------
    @property
    def hyperspace_enabled(self) -> bool:
        override = self._hyperspace_override.get()
        return self._hyperspace_default if override is None else override

    @hyperspace_enabled.setter
    def hyperspace_enabled(self, value: bool) -> None:
        self._hyperspace_default = bool(value)

    def enable_hyperspace(self) -> "Session":
        self.hyperspace_enabled = True
        return self

    def disable_hyperspace(self) -> "Session":
        self.hyperspace_enabled = False
        return self

    def is_hyperspace_enabled(self) -> bool:
        return self.hyperspace_enabled

    # reference-API aliases (ref: HS/package.scala:36-43 spark.enableHyperspace());
    # delegating defs so subclass overrides stay authoritative
    def enableHyperspace(self) -> "Session":
        return self.enable_hyperspace()

    def disableHyperspace(self) -> "Session":
        return self.disable_hyperspace()

    def isHyperspaceEnabled(self) -> bool:
        return self.is_hyperspace_enabled()

    @contextlib.contextmanager
    def hyperspace_scope(self, enabled: bool):
        """Pin the hyperspace flag for this thread/context only. Other threads
        (and requests queued behind this one) keep the session default —
        unlike mutating the flag, which raced under concurrent queries."""
        token = self._hyperspace_override.set(bool(enabled))
        try:
            yield self
        finally:
            self._hyperspace_override.reset(token)

    def with_hyperspace_disabled(self):
        return self.hyperspace_scope(False)

    # --- index manager ------------------------------------------------------
    @property
    def index_manager(self):
        if self._index_manager is None:
            from hyperspace_tpu.manager import CachingIndexCollectionManager

            self._index_manager = CachingIndexCollectionManager(self)
        return self._index_manager

    # --- lifecycle commit bus ----------------------------------------------
    @property
    def lifecycle_bus(self):
        """The session's commit/invalidation bus (lazy, one per session).
        Every index mutation publishes here; snapshot pins read its commit
        sequence. See hyperspace_tpu/lifecycle/invalidation.py."""
        if self._lifecycle_bus is None:
            from hyperspace_tpu.lifecycle.invalidation import InvalidationBus

            self._lifecycle_bus = InvalidationBus(self)
        return self._lifecycle_bus

    # --- scale-out fabric ---------------------------------------------------
    @property
    def fabric(self):
        """This session's :class:`~hyperspace_tpu.fabric.FabricRuntime`
        (commit watcher + coherence sidecar), or None while
        ``hyperspace.fabric.enabled`` is off. See docs/scale-out.md."""
        return self._fabric

    # --- query profiles (obs) ----------------------------------------------
    def last_query_profile(self):
        """The ``QueryProfile`` of the most recent traced ``collect()`` on
        this session, or None. Requires ``hyperspace.obs.tracing.enabled``;
        see docs/observability.md."""
        return self._last_profile

    @property
    def profile_history(self):
        """The session's fingerprint-keyed :class:`ProfileHistory` (traced
        ad-hoc ``collect()`` calls fold into it), or None when
        ``hyperspace.obs.history.enabled`` is false."""
        if self._profile_history is None and self.conf.obs_history_enabled:
            from hyperspace_tpu.obs.history import ProfileHistory

            self._profile_history = ProfileHistory(
                max_fingerprints=self.conf.obs_history_max_fingerprints
            )
        return self._profile_history

    def estimate_cost(self, query):
        """Learned latency estimate for a SQL string or DataFrame from this
        session's profile history (see ``ProfileHistory.estimate_cost``);
        None when the history is disabled or the fingerprint is unseen."""
        history = self.profile_history
        if history is None:
            return None
        from hyperspace_tpu.serving.fingerprint import plan_fingerprint

        df = self.sql(query) if isinstance(query, str) else query
        fp = plan_fingerprint(getattr(df, "plan", df))
        return history.estimate_cost(fp.structure)

    def data_version_brand(self, query):
        """The data-version brand a served result of ``query`` (SQL string or
        DataFrame) would be cached under: a digest of the session's ACTIVE
        index roster + rewrite conf + every scan leaf's source snapshot
        signature. None when any source cannot be signed. Two calls returning
        the same brand are guaranteed to observe the same data version — the
        invariant the serving result cache is keyed on (docs/serving.md)."""
        from hyperspace_tpu.serving.result_cache import version_brand

        df = self.sql(query) if isinstance(query, str) else query
        return version_brand(self, getattr(df, "plan", df), bool(self.hyperspace_enabled))

    # --- profiling ----------------------------------------------------------
    # The reference delegates runtime profiling to the Spark UI (SURVEY.md
    # §5.1); here the XLA profiler is the equivalent surface: traces cover the
    # build/query device programs and host stages, viewable in TensorBoard or
    # Perfetto.
    def start_profile(self, log_dir: str) -> None:
        import jax

        jax.profiler.start_trace(log_dir)

    def stop_profile(self) -> None:
        import jax

        jax.profiler.stop_trace()

    @contextlib.contextmanager
    def profile(self, log_dir: str):
        self.start_profile(log_dir)
        try:
            yield
        finally:
            self.stop_profile()

    # --- device mesh --------------------------------------------------------
    @property
    def mesh(self):
        """The session's 1-D device mesh, the one the index build and the
        sharded query programs (``parallel/executor.py``) both run over; the
        axis name comes from conf ``hyperspace.tpu.mesh.axis``. It spans all
        local devices, or with ``hyperspace.parallel.enabled`` the first
        ``hyperspace.parallel.mesh.devices`` of them (0 = all). Built lazily,
        and again when those keys change; a mesh given to ``set_mesh`` stays."""
        conf = self.conf
        n = conf.parallel_mesh_devices if conf.parallel_enabled else 0
        key = (n, conf.mesh_axis)
        if self._mesh is None or self._mesh_key not in (None, key):
            from hyperspace_tpu.parallel.mesh import make_mesh

            self._mesh = make_mesh(n if n > 0 else None, axis=key[1])
            self._mesh_key = key
            self._note_mesh(self._mesh)
        return self._mesh

    def set_mesh(self, mesh) -> "Session":
        self._mesh = mesh
        self._mesh_key = None  # pinned: conf no longer shapes it
        self._note_mesh(mesh)
        return self

    @staticmethod
    def _note_mesh(mesh) -> None:
        # tell the decode fast path the device-count multiple staged arrays
        # pad to, so its buffers come out device-put-ready (exec/io.py); a
        # stale value only costs the zero-copy handoff, never correctness
        from hyperspace_tpu.exec import io as _io

        _io.set_staging_pad(int(mesh.devices.size))


_current: Optional[Session] = None


def get_session() -> Session:
    global _current
    if _current is None:
        _current = Session()
    return _current


def set_session(session: Optional[Session]) -> None:
    global _current
    _current = session
