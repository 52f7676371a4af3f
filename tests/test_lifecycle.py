"""Live-data lifecycle tests (hyperspace_tpu/lifecycle/): snapshot pinning,
the commit/invalidation bus, the background refresh manager (including crash
safety under injected log-manager faults), hybrid-scan threshold re-gating at
rule time, device-side lineage delete filtering, and a fast deterministic
refresh-while-serving soak. The long endurance variant lives in
test_lifecycle_soak.py behind the ``soak`` marker."""

import os
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.actions.base import NoChangesException
from hyperspace_tpu.lifecycle import (
    CommitEvent,
    InvalidationBus,
    RefreshManager,
    SnapshotHandle,
    current_snapshot,
    snapshot_scope,
)
from hyperspace_tpu.manager import CachingIndexCollectionManager
from hyperspace_tpu.models.log_manager import IndexLogManagerFactory
from hyperspace_tpu.obs.metrics import REGISTRY
from hyperspace_tpu.plan import logical as L

from tests.test_e2e_rules import assert_batches_equal

pytestmark = pytest.mark.lifecycle


# --- data helpers ------------------------------------------------------------


def write_part(root, idx, n=250, seed=0):
    rng = np.random.default_rng(seed + idx)
    t = pa.table(
        {
            "c1": rng.integers(0, 100, n).astype(np.int64),
            "c2": rng.integers(0, 1000, n).astype(np.int64),
        }
    )
    # write-then-rename: a concurrent directory listing must never observe a
    # half-written file (the soak's torn-result check relies on this)
    final = os.path.join(root, f"part-{idx:05d}.parquet")
    tmp = final + ".tmp"
    pq.write_table(t, tmp)
    os.replace(tmp, final)
    return final


def write_marked_part(root, marker, n=120):
    """One file whose rows all carry ``m == marker`` — the soak's unit of
    all-or-nothing visibility."""
    t = pa.table(
        {
            "c1": (np.arange(n, dtype=np.int64) * 13) % 100,
            "m": np.full(n, marker, dtype=np.int64),
        }
    )
    final = os.path.join(root, f"part-{marker:05d}.parquet")
    # written under a name the source listing skips (DataPathFilter: "_" and
    # "." prefixes), as Spark writes under _temporary: a lister must never
    # meet a file that is renamed away before it is stat'ed
    tmp = os.path.join(root, f"_part-{marker:05d}.parquet.tmp")
    pq.write_table(t, tmp)
    os.replace(tmp, final)
    return final


@pytest.fixture()
def mutable_data(tmp_path):
    root = tmp_path / "mutable"
    root.mkdir()
    for i in range(3):
        write_part(str(root), i)
    return str(root)


@pytest.fixture()
def hs(session):
    return hst.Hyperspace(session)


def counter_value(name, **labels):
    return REGISTRY.counter(name, **labels).value


# --- snapshot pinning --------------------------------------------------------


class TestSnapshotPin:
    def test_capture_roster_and_lookup(self, session, hs, mutable_data):
        df = session.read_parquet(mutable_data)
        entry = hs.create_index(df, hst.CoveringIndexConfig("pinA", ["c1"], ["c2"]))
        before = counter_value("hs_snapshot_pins_total")
        h = SnapshotHandle.capture(session)
        assert counter_value("hs_snapshot_pins_total") == before + 1
        assert ("pinA", entry.id) in h.roster
        assert h.get_index("pinA").id == entry.id
        assert h.index_version("pinA") == entry.id
        assert h.get_index("nope") is None and h.index_version("nope") is None

    def test_scope_is_contextual_and_none_is_noop(self, session):
        assert current_snapshot() is None
        with snapshot_scope(None) as got:
            assert got is None and current_snapshot() is None
        h = SnapshotHandle([], commit_seq=7)
        with snapshot_scope(h):
            assert current_snapshot() is h
            with snapshot_scope(None):
                # None never *unpins* — call sites that branch on "pinning
                # disabled" must not strip an outer request's pin
                assert current_snapshot() is h
        assert current_snapshot() is None

    def test_pin_freezes_roster_across_commit(self, session, hs, mutable_data):
        df = session.read_parquet(mutable_data)
        hs.create_index(df, hst.CoveringIndexConfig("pinB", ["c1"], ["c2"]))
        h = SnapshotHandle.capture(session)
        old_id = h.index_version("pinB")

        write_part(mutable_data, 3, seed=11)
        hs.refresh_index("pinB", "incremental")
        live = session.index_manager.get_index("pinB")
        assert live.id > old_id

        # pinned resolution still answers with the pre-commit version …
        with snapshot_scope(h):
            assert session.index_manager.get_index("pinB").id == old_id
            assert [e.id for e in session.index_manager.get_indexes() if e.name == "pinB"] == [old_id]
            # … and a nested capture is idempotent (no forward time-travel)
            assert SnapshotHandle.capture(session).roster == h.roster
        # unpinned resolution sees the commit
        assert session.index_manager.get_index("pinB").id == live.id

    def test_commit_seq_read_before_roster(self, session, hs, mutable_data):
        df = session.read_parquet(mutable_data)
        hs.create_index(df, hst.CoveringIndexConfig("pinC", ["c1"], ["c2"]))
        seq = session.lifecycle_bus.commit_seq
        h = SnapshotHandle.capture(session)
        assert h.commit_seq == seq  # create's commit already counted


# --- commit/invalidation bus -------------------------------------------------


class TestInvalidationBus:
    def test_commit_seq_counts_real_commits_only(self, session, hs, mutable_data):
        bus = session.lifecycle_bus
        df = session.read_parquet(mutable_data)
        seq0 = bus.commit_seq
        c0 = counter_value("hs_lifecycle_commits_total")
        hs.create_index(df, hst.CoveringIndexConfig("busA", ["c1"], ["c2"]))
        assert bus.commit_seq == seq0 + 1
        assert counter_value("hs_lifecycle_commits_total") == c0 + 1
        # an idempotent no-change refresh must NOT publish a commit
        with pytest.raises(NoChangesException):
            hs.refresh_index("busA", "incremental")
        assert bus.commit_seq == seq0 + 1

    def test_mutations_publish_typed_events(self, session, hs, mutable_data):
        bus = session.lifecycle_bus
        events = []
        bus.subscribe(events.append)
        try:
            df = session.read_parquet(mutable_data)
            old = hs.create_index(df, hst.CoveringIndexConfig("busB", ["c1"], ["c2"]))
            write_part(mutable_data, 3, seed=5)
            new = hs.refresh_index("busB", "incremental")
        finally:
            bus.unsubscribe(events.append)
        kinds = [e.kind for e in events]
        assert kinds == ["create", "refresh-incremental"]
        assert events[0].index_name == "busB" and events[0].log_id == old.id
        refresh_ev = events[1]
        assert refresh_ev.log_id == new.id
        # the refresh supersedes the previous entry's index data files
        assert set(old.content.files) <= set(refresh_ev.affected_files)

    def test_broken_subscriber_does_not_block_commit(self, session, hs, mutable_data):
        bus = session.lifecycle_bus

        def boom(event):
            raise RuntimeError("subscriber bug")

        bus.subscribe(boom)
        try:
            df = session.read_parquet(mutable_data)
            hs.create_index(df, hst.CoveringIndexConfig("busC", ["c1"], ["c2"]))
        finally:
            bus.unsubscribe(boom)
        assert session.index_manager.get_index("busC") is not None

    def test_publish_clears_roster_ttl_cache(self, session, hs, mutable_data):
        df = session.read_parquet(mutable_data)
        hs.create_index(df, hst.CoveringIndexConfig("busD", ["c1"], ["c2"]))
        mgr = session.index_manager
        mgr.get_indexes()  # warm the TTL cache
        assert mgr._cache.get() is not None
        r0 = counter_value("hs_lifecycle_invalidations_total", cache="roster")
        counts = session.lifecycle_bus.publish(CommitEvent("busD", 99, "test"))
        assert counts["roster"] == 1
        assert mgr._cache.get() is None
        assert counter_value("hs_lifecycle_invalidations_total", cache="roster") == r0 + 1

    def test_publish_purges_byte_caches_for_affected_files(self, session, hs, mutable_data):
        from hyperspace_tpu.exec import device as D
        from hyperspace_tpu.exec import io as IO
        from hyperspace_tpu.serving.bucket_cache import BucketCache

        victim = os.path.join(mutable_data, "part-00000.parquet")
        other = os.path.join(mutable_data, "part-00001.parquet")

        bc = BucketCache(1 << 22)
        bc.read([victim], ["c1"])
        bc.read([other], ["c1"])
        session.bucket_cache = bc

        io_victim_key = (victim, 1, 2, ("c1",))
        io_other_key = (other, 1, 2, ("c1",))
        IO._io_cache.put(io_victim_key, {"c1": np.zeros(1, dtype=np.int64)}, 8)
        IO._io_cache.put(io_other_key, {"c1": np.zeros(1, dtype=np.int64)}, 8)

        dev_victim_key = (((victim, 1, 2),), "c1", "mesh-fp")
        dev_other_key = (((other, 1, 2),), "c1", "mesh-fp")
        D._device_cache_put(dev_victim_key, ("arr", None, 1), 8)
        D._device_cache_put(dev_other_key, ("arr", None, 1), 8)

        try:
            counts = session.lifecycle_bus.publish(
                CommitEvent("whatever", 1, "test", affected_files=[victim])
            )
            # io may exceed 1: the bucket read itself populated the real io
            # cache for the victim file, and the purge sweeps that entry too
            assert counts["bucket"] == 1 and counts["io"] >= 1 and counts["device"] == 1
            # untouched files stay cached
            assert IO._io_cache.get(io_other_key) is not None
            assert IO._io_cache.get(io_victim_key) is None
            assert D._device_cache_get(dev_other_key) is not None
            assert D._device_cache_get(dev_victim_key) is None
        finally:
            del session.bucket_cache
            bc.shutdown()
            for k in (io_victim_key, io_other_key):
                IO._io_cache.discard(k)
            for k in (dev_victim_key, dev_other_key):
                D._device_cache.discard(k)

    def test_purge_primitives_direct(self):
        from hyperspace_tpu.exec.io import _key_mentions_path
        from hyperspace_tpu.utils.lru import BytesLRU

        lru = BytesLRU(1 << 16)
        lru.put("k", "v", 4)
        assert lru.discard("k") is True
        assert lru.discard("k") is False  # second discard is a no-op
        assert lru.get("k") is None

        # recursive key scan covers file, concat and row-group key shapes
        assert _key_mentions_path(("a.pq", 1, 2, None), {"a.pq"})
        assert _key_mentions_path((("a.pq", 1, 2), ("b.pq", 3, 4)), {"b.pq"})
        assert _key_mentions_path(((("a.pq", 1, 2),), ("rg", 0)), {"a.pq"})
        assert not _key_mentions_path(("a.pq", 1, 2), {"c.pq"})


# --- refresh manager ---------------------------------------------------------


class TestRefreshManager:
    def test_no_drift_polls_fresh(self, session, hs, mutable_data):
        df = session.read_parquet(mutable_data)
        hs.create_index(df, hst.CoveringIndexConfig("rmA", ["c1"], ["c2"]))
        rm = RefreshManager(session)
        entry = session.index_manager.get_index("rmA")
        d = rm.drift(entry)
        assert d is not None and not d.has_drift
        assert rm.decide(d) is None
        assert rm.poll_once() == [{"index": "rmA", "mode": None, "outcome": "fresh"}]

    def test_auto_mode_picks_quick_then_incremental(self, session, hs, mutable_data):
        df = session.read_parquet(mutable_data)
        hs.create_index(df, hst.CoveringIndexConfig("rmB", ["c1"], ["c2"]))
        write_part(mutable_data, 3, seed=21)  # 1 of 4 files appended (~25% of bytes)
        rm = RefreshManager(session)
        entry = session.index_manager.get_index("rmB")
        d = rm.drift(entry)
        assert d.appended_files == 1 and d.deleted_files == 0
        assert 0.0 < d.appended_ratio < 0.5

        # below the appended threshold: hybrid scan absorbs it, quick refresh
        session.conf.set(hst.keys.HYBRID_SCAN_MAX_APPENDED_RATIO, 0.9)
        assert rm.decide(d) == "quick"
        # past the threshold: the candidate gate would reject — incremental
        session.conf.set(hst.keys.HYBRID_SCAN_MAX_APPENDED_RATIO, 0.01)
        assert rm.decide(d) == "incremental"

    def test_pinned_mode_overrides_auto(self, session, hs, mutable_data):
        df = session.read_parquet(mutable_data)
        hs.create_index(df, hst.CoveringIndexConfig("rmC", ["c1"], ["c2"]))
        write_part(mutable_data, 3, seed=22)
        rm = RefreshManager(session)
        d = rm.drift(session.index_manager.get_index("rmC"))
        session.conf.set(hst.keys.LIFECYCLE_REFRESH_MODE, "full")
        assert rm.decide(d) == "full"
        session.conf.set(hst.keys.LIFECYCLE_REFRESH_MODE, "bogus")
        assert rm.decide(d) is None

    def test_poll_commits_then_converges(self, session, hs, mutable_data):
        df = session.read_parquet(mutable_data)
        hs.create_index(df, hst.CoveringIndexConfig("rmD", ["c1"], ["c2"]))
        old_id = session.index_manager.get_index("rmD").id
        write_part(mutable_data, 3, seed=23)
        session.conf.set(hst.keys.HYBRID_SCAN_MAX_APPENDED_RATIO, 0.01)
        rm = RefreshManager(session)
        c0 = counter_value("hs_lifecycle_refresh_total", mode="incremental", outcome="committed")
        assert rm.poll_once() == [
            {"index": "rmD", "mode": "incremental", "outcome": "committed"}
        ]
        assert session.index_manager.get_index("rmD").id > old_id
        assert (
            counter_value("hs_lifecycle_refresh_total", mode="incremental", outcome="committed")
            == c0 + 1
        )
        # drift fully folded in: the next poll sees a fresh index
        assert rm.poll_once() == [{"index": "rmD", "mode": None, "outcome": "fresh"}]

    def test_single_writer_busy_and_no_changes(self, session, hs, mutable_data):
        df = session.read_parquet(mutable_data)
        hs.create_index(df, hst.CoveringIndexConfig("rmE", ["c1"], ["c2"]))
        rm = RefreshManager(session)
        # a racing writer holds the per-index lock: skip, don't double-build
        lock = rm._lock_for("rmE")
        assert lock.acquire(blocking=False)
        try:
            assert rm.refresh_index("rmE", "incremental") == "busy"
        finally:
            lock.release()
        # no drift: the action raises NoChangesException — converged
        assert rm.refresh_index("rmE", "incremental") == "no-changes"

    def test_background_thread_commits_drift(self, session, hs, mutable_data):
        df = session.read_parquet(mutable_data)
        hs.create_index(df, hst.CoveringIndexConfig("rmF", ["c1"], ["c2"]))
        old_id = session.index_manager.get_index("rmF").id
        write_part(mutable_data, 3, seed=24)
        session.conf.set(hst.keys.HYBRID_SCAN_MAX_APPENDED_RATIO, 0.01)
        rm = RefreshManager(session, interval_seconds=0.05)
        rm.start()
        try:
            rm.start()  # idempotent second start
            deadline = time.monotonic() + 15.0
            while time.monotonic() < deadline:
                if session.index_manager.get_index("rmF").id > old_id:
                    break
                time.sleep(0.05)
            assert session.index_manager.get_index("rmF").id > old_id
        finally:
            rm.stop()
        assert rm._thread is None


class FlakyLogManagerFactory(IndexLogManagerFactory):
    """Wraps real log managers; while armed, the next ``write_log`` fails —
    a crash injected mid-action, before any stable-pointer move."""

    def __init__(self):
        self.armed = False
        self.failures = 0

    def create(self, index_path):
        real = super().create(index_path)
        factory = self

        class Flaky:
            def __getattr__(self, attr):
                return getattr(real, attr)

            def write_log(self, log_id, entry):
                if factory.armed:
                    factory.armed = False
                    factory.failures += 1
                    raise OSError("injected log write failure")
                return real.write_log(log_id, entry)

        return Flaky()


class TestRefreshCrashSafety:
    def test_failed_refresh_keeps_prior_active_then_retry_converges(
        self, session, mutable_data
    ):
        flaky = FlakyLogManagerFactory()
        session._index_manager = CachingIndexCollectionManager(
            session, log_manager_factory=flaky
        )
        hs = hst.Hyperspace(session)
        df = session.read_parquet(mutable_data)
        created = hs.create_index(df, hst.CoveringIndexConfig("crashA", ["c1"], ["c2"]))
        write_part(mutable_data, 3, seed=31)

        rm = RefreshManager(session)
        bus = session.lifecycle_bus
        seq0 = bus.commit_seq
        e0 = counter_value("hs_lifecycle_refresh_total", mode="incremental", outcome="error")

        # crash mid-refresh: outcome=error, no commit published, and the
        # prior ACTIVE entry still serves both metadata and queries
        flaky.armed = True
        assert rm.refresh_index("crashA", "incremental") == "error"
        assert flaky.failures == 1
        assert bus.commit_seq == seq0
        assert (
            counter_value("hs_lifecycle_refresh_total", mode="incremental", outcome="error")
            == e0 + 1
        )
        entry = session.index_manager.get_index("crashA")
        assert entry.id == created.id and entry.state == "ACTIVE"

        q = session.read_parquet(mutable_data).filter(hst.col("c1") == 7).select("c2")
        session.enable_hyperspace()
        on = q.collect()
        session.disable_hyperspace()
        assert_batches_equal(on, q.collect())

        # retry re-runs the same diff and commits exactly once
        assert rm.refresh_index("crashA", "incremental") == "committed"
        assert bus.commit_seq == seq0 + 1
        new_id = session.index_manager.get_index("crashA").id
        assert new_id > created.id

        # a second retry after the commit is idempotent: no drift, no commit
        assert rm.refresh_index("crashA", "incremental") == "no-changes"
        assert bus.commit_seq == seq0 + 1
        assert session.index_manager.get_index("crashA").id == new_id


# --- hybrid-scan threshold re-gating at rule time (satellite) ----------------


class TestHybridThresholdRegating:
    def _index_scans(self, q):
        return [
            p
            for p in L.collect(q.optimized_plan(), lambda x: True)
            if isinstance(p, L.IndexScan)
        ]

    def test_tightened_appended_threshold_rejects_on_next_rewrite(
        self, session, hs, mutable_data
    ):
        session.conf.set(hst.keys.HYBRID_SCAN_ENABLED, True)
        session.conf.set(hst.keys.HYBRID_SCAN_MAX_APPENDED_RATIO, 0.9)
        session.conf.set(hst.keys.HYBRID_SCAN_MAX_DELETED_RATIO, 0.9)
        df = session.read_parquet(mutable_data)
        hs.create_index(df, hst.CoveringIndexConfig("gateA", ["c1"], ["c2"]))
        write_part(mutable_data, 3, seed=41)

        session.enable_hyperspace()
        df2 = session.read_parquet(mutable_data)
        q = df2.filter(hst.col("c1") == 7).select("c2")
        assert self._index_scans(q), "loose threshold: hybrid scan applies the index"

        # tighten the conf: the very next rewrite must re-gate and reject,
        # without waiting for the roster TTL cache to expire
        session.conf.set(hst.keys.HYBRID_SCAN_MAX_APPENDED_RATIO, 0.0001)
        q2 = session.read_parquet(mutable_data).filter(hst.col("c1") == 7).select("c2")
        assert not self._index_scans(q2)
        session.disable_hyperspace()
        assert_batches_equal(q2.collect(), q2.collect())

    def test_tightened_deleted_threshold_rejects_on_next_rewrite(
        self, session, hs, mutable_data
    ):
        session.conf.set(hst.keys.HYBRID_SCAN_ENABLED, True)
        session.conf.set(hst.keys.LINEAGE_ENABLED, True)
        session.conf.set(hst.keys.HYBRID_SCAN_MAX_APPENDED_RATIO, 0.9)
        session.conf.set(hst.keys.HYBRID_SCAN_MAX_DELETED_RATIO, 0.9)
        df = session.read_parquet(mutable_data)
        hs.create_index(df, hst.CoveringIndexConfig("gateB", ["c1"], ["c2"]))
        os.remove(os.path.join(mutable_data, "part-00002.parquet"))

        session.enable_hyperspace()
        q = session.read_parquet(mutable_data).filter(hst.col("c1") == 7).select("c2")
        assert self._index_scans(q), "loose threshold: delete-tolerant hybrid scan"

        session.conf.set(hst.keys.HYBRID_SCAN_MAX_DELETED_RATIO, 0.0001)
        q2 = session.read_parquet(mutable_data).filter(hst.col("c1") == 7).select("c2")
        assert not self._index_scans(q2)
        session.disable_hyperspace()
        assert_batches_equal(q2.collect(), q2.collect())


# --- device-side lineage delete filtering ------------------------------------


class TestDeviceLineage:
    def test_matcher_accepts_not_in_int_literals(self):
        from hyperspace_tpu.exec.executor import Executor
        from hyperspace_tpu.plan.expr import Col, In, Lit, Not

        cond = Not(In(Col("_data_file_id"), [Lit(3), Lit(1), Lit(2)]))
        assert Executor._lineage_not_in(cond) == ("_data_file_id", [3, 1, 2])
        # non-integer literals, non-Col children and other shapes don't match
        assert Executor._lineage_not_in(Not(In(Col("x"), [Lit("a")]))) is None
        assert Executor._lineage_not_in(Not(In(Lit(1), [Lit(2)]))) is None
        assert Executor._lineage_not_in(In(Col("x"), [Lit(1)])) is None

    def test_mask_matches_host_not_in_oracle(self, session):
        from hyperspace_tpu.exec.lineage import lineage_delete_mask

        rng = np.random.default_rng(7)
        for n, ids in [
            (1000, [3, 17, 999999]),     # some present, some absent
            (257, []),                   # empty delete set: all kept
            (64, list(range(64))),       # everything deleted
            (5, [0]),                    # tiny batch
        ]:
            col = rng.integers(0, 500, n).astype(np.int64)
            if ids and n == 64:
                col = np.arange(64, dtype=np.int64)  # force full deletion
            batch = {"_data_file_id": col}
            got = lineage_delete_mask(session, batch, "_data_file_id", ids)
            want = ~np.isin(col, np.asarray(ids, dtype=np.int64))
            np.testing.assert_array_equal(got, want), (n, ids)
            assert got.dtype == np.bool_

    def test_duplicate_and_unsorted_ids(self, session):
        from hyperspace_tpu.exec.lineage import lineage_delete_mask

        col = np.array([5, 1, 9, 5, 2], dtype=np.int64)
        got = lineage_delete_mask(session, {"f": col}, "f", [9, 5, 5, 9])
        np.testing.assert_array_equal(got, np.array([False, True, False, False, True]))

    def test_unsupported_inputs_raise(self, session):
        from hyperspace_tpu.exec.device import DeviceUnsupported
        from hyperspace_tpu.exec.lineage import lineage_delete_mask

        with pytest.raises(DeviceUnsupported):
            lineage_delete_mask(session, {"f": np.zeros(4)}, "f", [1])  # float column
        with pytest.raises(DeviceUnsupported):
            lineage_delete_mask(session, {"f": np.zeros(4, dtype=np.int64)}, "g", [1])

    def test_hlo_contract_zero_collectives(self, session):
        from hyperspace_tpu.check import hlo_lint
        from hyperspace_tpu.exec.lineage import lineage_delete_mask

        session.conf.set("hyperspace.check.hlo.enabled", True)
        col = np.arange(9000, dtype=np.int64)
        got = lineage_delete_mask(session, {"f": col}, "f", [5, 6, 7])
        assert got.sum() == 9000 - 3
        bad = [f for f in hlo_lint.runtime_violations() if "lineage-antijoin" in f.path]
        assert bad == [], "\n".join(f.render() for f in bad)

    def test_e2e_delete_filter_device_equals_host(self, session, hs, mutable_data):
        session.conf.set(hst.keys.HYBRID_SCAN_ENABLED, True)
        session.conf.set(hst.keys.LINEAGE_ENABLED, True)
        session.conf.set(hst.keys.HYBRID_SCAN_MAX_APPENDED_RATIO, 0.9)
        session.conf.set(hst.keys.HYBRID_SCAN_MAX_DELETED_RATIO, 0.9)
        df = session.read_parquet(mutable_data)
        hs.create_index(df, hst.CoveringIndexConfig("linA", ["c1"], ["c2"]))
        os.remove(os.path.join(mutable_data, "part-00001.parquet"))

        session.enable_hyperspace()
        q = session.read_parquet(mutable_data).filter(hst.col("c1") < 50).select("c2")

        # device path for any batch size
        session.conf.set(hst.keys.LIFECYCLE_DEVICE_LINEAGE_MIN_ROWS, 1)
        on_device = q.collect()
        # host oracle: device lineage disabled entirely
        session.conf.set(hst.keys.LIFECYCLE_DEVICE_LINEAGE_ENABLED, False)
        on_host = q.collect()
        assert_batches_equal(on_device, on_host)

        # hyperspace off ground truth
        session.disable_hyperspace()
        assert_batches_equal(on_device, q.collect())
        session.enable_hyperspace()

        # min-rows gate: below the floor the host path serves and the
        # fallback is counted
        session.conf.set(hst.keys.LIFECYCLE_DEVICE_LINEAGE_ENABLED, True)
        session.conf.set(hst.keys.LIFECYCLE_DEVICE_LINEAGE_MIN_ROWS, 10**9)
        f0 = counter_value("hs_device_fallback_total", op="lineage", reason="min-rows")
        small = q.collect()
        assert_batches_equal(small, on_host)
        assert counter_value("hs_device_fallback_total", op="lineage", reason="min-rows") > f0


# --- refresh-while-serving soak (fast deterministic tier-1 variant) ----------


def run_refresh_serving_soak(session, tmp_path, rounds, workers, initial_files=3, n=120):
    """Shared soak driver (the long variant in test_lifecycle_soak.py reuses
    it with bigger numbers). Returns the list of violations — empty on a
    clean run — plus summary counters for the caller to assert on."""
    from hyperspace_tpu.serving import QueryServer

    root = tmp_path / "soak"
    root.mkdir()
    for i in range(initial_files):
        write_marked_part(str(root), i, n=n)

    session.conf.set(hst.keys.HYBRID_SCAN_ENABLED, True)
    session.conf.set(hst.keys.HYBRID_SCAN_MAX_APPENDED_RATIO, 0.95)
    session.conf.set(hst.keys.HYBRID_SCAN_MAX_DELETED_RATIO, 0.95)
    hs_api = hst.Hyperspace(session)
    df = session.read_parquet(str(root))
    hs_api.create_index(df, hst.CoveringIndexConfig("soakIdx", ["c1"], ["m"]))
    session.enable_hyperspace()

    bus = session.lifecycle_bus
    rm = RefreshManager(session)
    seq_at_create = bus.commit_seq

    state_lock = threading.Lock()
    committed = list(range(initial_files))  # markers refresh-committed so far
    violations = []
    stop = threading.Event()
    queries_done = [0]

    def query_loop():
        while not stop.is_set():
            with state_lock:
                need = list(committed)
            try:
                q = session.read_parquet(str(root)).filter(hst.col("c1") >= 0).select("m")
                res = server.submit(q).result(timeout=60)
            except Exception as exc:  # admission overflow etc. — not a staleness bug
                violations.append(("query-error", repr(exc)))
                continue
            vals, cnts = np.unique(res["m"], return_counts=True)
            seen = dict(zip(vals.tolist(), cnts.tolist()))
            for mk, c in seen.items():
                if c != n:
                    violations.append(("torn", mk, c))
            for mk in need:
                if seen.get(mk) != n:
                    violations.append(("stale", mk, seen.get(mk)))
            queries_done[0] += 1

    with QueryServer(session, workers=workers) as server:
        threads = [threading.Thread(target=query_loop) for _ in range(2)]
        for t in threads:
            t.start()
        try:
            for r in range(rounds):
                marker = initial_files + r
                write_marked_part(str(root), marker, n=n)
                outcome = rm.refresh_index("soakIdx", "incremental")
                if outcome != "committed":
                    violations.append(("refresh", marker, outcome))
                    continue
                with state_lock:
                    committed.append(marker)
                time.sleep(0.02)  # let a few queries land between commits
        finally:
            stop.set()
            for t in threads:
                t.join(30)

    return {
        "violations": violations,
        "queries": queries_done[0],
        "commits": bus.commit_seq - seq_at_create,
        "final_markers": list(committed),
    }


class TestRefreshWhileServing:
    def test_soak_fast_no_stale_no_torn(self, session, tmp_path):
        assert session.conf.lifecycle_snapshot_enabled  # pinning on by default
        pins0 = counter_value("hs_snapshot_pins_total")
        roster0 = counter_value("hs_lifecycle_invalidations_total", cache="roster")

        out = run_refresh_serving_soak(session, tmp_path, rounds=4, workers=2)

        assert out["violations"] == [], out["violations"][:10]
        assert out["commits"] == 4  # one commit per refresh round
        assert out["queries"] > 0
        # every admitted request pinned a snapshot, every commit purged the
        # roster cache (brand rotation visible immediately)
        assert counter_value("hs_snapshot_pins_total") > pins0
        assert counter_value("hs_lifecycle_invalidations_total", cache="roster") >= roster0 + 4

        # post-soak ground truth: the final answer matches hyperspace-off
        q = session.read_parquet(str(tmp_path / "soak")).filter(hst.col("c1") >= 0).select("m")
        on = q.collect()
        session.disable_hyperspace()
        assert_batches_equal(on, q.collect())
        assert sorted(np.unique(on["m"]).tolist()) == out["final_markers"]
