"""Reliability subsystem: typed error taxonomy, seeded fault injection,
deadline-aware retries, index quarantine with fallback-to-source, and the
chaos soak (serving + refresh + injected faults).

Pinned properties:
- all machinery is off by default: at default conf every seam is one
  attribute read and results/plans are identical to a clean build;
- injected and classified failures are always *typed* (`ReliabilityError`),
  never raw third-party exceptions or silent wrong answers;
- the retry policy never sleeps past the serving deadline;
- repeated corrupt reads of an index's files quarantine the index and
  queries transparently re-plan against source; a clean half-open probe
  un-quarantines;
- a torn trailing operation-log entry degrades to the prior version
  instead of making the index vanish;
- the chaos soak holds the serving invariants (no torn/stale answers,
  only typed errors, no hung workers) under a seeded fault mix.
"""

import os
import random
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from conftest import index_scans  # noqa: E402
from hyperspace_tpu.obs.metrics import REGISTRY
from hyperspace_tpu.reliability import errors as rerr
from hyperspace_tpu.reliability.degrade import QUARANTINE
from hyperspace_tpu.reliability.faults import FAULTS, FaultRule, fault_scope, parse_spec
from hyperspace_tpu.reliability.retry import (
    RetryPolicy,
    current_deadline,
    deadline_scope,
    with_retry,
)

pytestmark = pytest.mark.faults


def counter_value(name, **labels):
    return REGISTRY.counter(name, **labels).value


@pytest.fixture(autouse=True)
def _reset_reliability_globals():
    """The registries are process-global (most-recent-session-wins); make
    sure no test leaks armed faults/retries/quarantine into the next."""
    yield
    from hyperspace_tpu.reliability import faults as fmod
    from hyperspace_tpu.reliability import retry as rmod

    fmod.FAULTS.clear()
    fmod._CONF_INSTALLED = False
    rmod._POLICY = None
    QUARANTINE.enabled = False
    QUARANTINE._breakers = {}


def _write_files(d, num_files=4, rows_per=300, seed=7):
    os.makedirs(d, exist_ok=True)
    rng = np.random.default_rng(seed)
    for i in range(num_files):
        t = pa.table(
            {
                "c1": rng.integers(0, 100, rows_per).astype(np.int64),
                "c2": np.round(rng.uniform(0, 100, rows_per), 3),
            }
        )
        pq.write_table(t, os.path.join(d, f"part-{i:05d}.parquet"))
    return d


def _mk_session(tmp_path, **conf):
    base = {
        hst.keys.SYSTEM_PATH: str(tmp_path / "indexes"),
        hst.keys.NUM_BUCKETS: 4,
    }
    base.update(conf)
    sess = hst.Session(conf=base)
    hst.set_session(sess)
    return sess


def _sorted_rows(batch):
    cols = sorted(batch.keys())
    return sorted(zip(*[np.asarray(batch[c]).tolist() for c in cols]))


# --- taxonomy ----------------------------------------------------------------


class TestTaxonomy:
    def test_transient_is_oserror(self):
        # existing `except OSError` fallbacks must keep catching classified
        # transients — that is what makes the taxonomy a safe retrofit
        assert issubclass(rerr.TransientIOError, OSError)
        assert issubclass(rerr.InjectedTransientIOError, OSError)
        assert not issubclass(rerr.CorruptDataError, OSError)

    def test_classify_routing(self):
        corrupt = rerr.classify(pa.lib.ArrowInvalid("bad magic"), path="/x.parquet")
        assert isinstance(corrupt, rerr.CorruptDataError)
        assert corrupt.path == "/x.parquet"
        assert isinstance(corrupt.__cause__, pa.lib.ArrowInvalid)

        transient = rerr.classify(OSError("EIO"))
        assert isinstance(transient, rerr.TransientIOError)

        # already-typed errors pass through identically
        e = rerr.CorruptDataError("x", path="/p")
        assert rerr.classify(e) is e
        # production classifiers never mint injected errors
        assert not isinstance(transient, rerr.FaultInjected)

    def test_count_io_error_families(self):
        before = counter_value(
            "hs_io_errors_total", op="t.op", kind="corrupt", outcome="handled"
        )
        rerr.count_io_error("t.op", rerr.CorruptDataError("x"), swallowed=True)
        assert counter_value(
            "hs_io_errors_total", op="t.op", kind="corrupt", outcome="handled"
        ) == before + 1
        before = counter_value(
            "hs_io_errors_total", op="t.op", kind="transient", outcome="raised"
        )
        rerr.count_io_error("t.op", OSError("x"))
        assert counter_value(
            "hs_io_errors_total", op="t.op", kind="transient", outcome="raised"
        ) == before + 1


# --- fault harness -----------------------------------------------------------


class TestFaultHarness:
    def test_default_off_is_one_attr(self):
        assert FAULTS.active is False
        FAULTS.check("io.decode", "/any")  # no-op, no raise

    def test_parse_spec_full_syntax(self):
        rules = parse_spec(
            "io.decode:transient:p=0.25;"
            "log.read:truncate:glob=*_hyperspace_log*:nth=3:max=1;"
            "device.transfer:latency:delay=0.5"
        )
        assert [(r.site, r.kind) for r in rules] == [
            ("io.decode", "transient"),
            ("log.read", "truncate"),
            ("device.transfer", "latency"),
        ]
        assert rules[0].probability == 0.25
        assert rules[1].path_glob == "*_hyperspace_log*"
        assert rules[1].nth == 3 and rules[1].max_fires == 1
        assert rules[2].delay_s == 0.5

    def test_parse_spec_rejects_garbage(self):
        with pytest.raises(ValueError):
            parse_spec("io.decode")  # no kind
        with pytest.raises(ValueError):
            parse_spec("io.decode:frobnicate")  # unknown kind
        with pytest.raises(ValueError):
            parse_spec("io.decode:transient:bogus=1")  # unknown option

    def test_nth_glob_and_max_targeting(self):
        with fault_scope(
            FaultRule("io.decode", "transient", path_glob="*hit*", nth=2, max_fires=1)
        ):
            FAULTS.check("io.decode", "/miss/a")  # glob mismatch: not even counted
            FAULTS.check("io.decode", "/hit/1")  # op 1: no fire
            with pytest.raises(rerr.TransientIOError) as ei:
                FAULTS.check("io.decode", "/hit/2")  # op 2 = nth
            assert isinstance(ei.value, rerr.FaultInjected)
            FAULTS.check("io.decode", "/hit/3")  # max_fires exhausted
        assert FAULTS.active is False  # scope restored

    def test_seeded_probability_is_deterministic(self):
        def pattern(seed):
            fired = []
            with fault_scope(FaultRule("io.decode", "transient", probability=0.5), seed=seed):
                for i in range(32):
                    try:
                        FAULTS.check("io.decode", f"/f{i}")
                        fired.append(0)
                    except rerr.TransientIOError:
                        fired.append(1)
            return fired

        assert pattern(7) == pattern(7)
        assert any(pattern(7)) and not all(pattern(7))

    def test_mangle_bytes_kinds(self):
        data = b"PAR1" + b"x" * 96
        with fault_scope(FaultRule("log.read", "truncate")):
            out = FAULTS.mangle_bytes("log.read", "/log/5", data)
            assert len(out) < len(data)
        with fault_scope(FaultRule("log.read", "magic")):
            out = FAULTS.mangle_bytes("log.read", "/log/5", data)
            assert out[:4] == b"XXXX" and len(out) == len(data)

    def test_injection_counted(self):
        before = counter_value("hs_faults_injected_total", site="io.footer", kind="transient")
        with fault_scope(FaultRule("io.footer", "transient")):
            with pytest.raises(rerr.TransientIOError):
                FAULTS.check("io.footer", "/x")
        assert counter_value(
            "hs_faults_injected_total", site="io.footer", kind="transient"
        ) == before + 1


# --- retry policy ------------------------------------------------------------


def _fake_env():
    """Deterministic clock/sleep pair: sleeping advances the clock."""
    now = [100.0]
    slept = []

    def clock():
        return now[0]

    def sleep(s):
        slept.append(s)
        now[0] += s

    return clock, sleep, slept


class TestRetryPolicy:
    def test_succeeds_after_transients(self):
        clock, sleep, slept = _fake_env()
        p = RetryPolicy(4, 0.005, 0.1, clock=clock, sleep=sleep, rng=random.Random(3))
        calls = [0]

        def flaky():
            calls[0] += 1
            if calls[0] < 3:
                raise rerr.TransientIOError("blip")
            return 42

        before = counter_value("hs_io_retries_total", op="t.flaky", reason="oserror")
        assert p.call(flaky, op="t.flaky") == 42
        assert calls[0] == 3 and len(slept) == 2
        assert all(0.005 <= s <= 0.1 for s in slept)
        assert counter_value("hs_io_retries_total", op="t.flaky", reason="oserror") == before + 2

    def test_attempts_giveup_counts_and_raises(self):
        clock, sleep, _ = _fake_env()
        p = RetryPolicy(3, 0.005, 0.1, clock=clock, sleep=sleep, rng=random.Random(3))
        before = counter_value("hs_io_giveups_total", op="t.dead", reason="attempts")
        with pytest.raises(rerr.TransientIOError):
            p.call(lambda: (_ for _ in ()).throw(rerr.TransientIOError("x")), op="t.dead")
        assert counter_value("hs_io_giveups_total", op="t.dead", reason="attempts") == before + 1

    def test_never_sleeps_past_deadline(self):
        clock, sleep, slept = _fake_env()
        p = RetryPolicy(10, 0.050, 5.0, clock=clock, sleep=sleep, rng=random.Random(1))
        before = counter_value("hs_io_giveups_total", op="t.dl", reason="deadline")
        with deadline_scope(clock() + 0.010):  # under the minimum backoff
            with pytest.raises(rerr.TransientIOError):
                p.call(lambda: (_ for _ in ()).throw(rerr.TransientIOError("x")), op="t.dl")
        assert slept == []  # gave up instead of sleeping past the deadline
        assert counter_value("hs_io_giveups_total", op="t.dl", reason="deadline") == before + 1

    def test_corrupt_and_enoent_never_retry(self):
        clock, sleep, slept = _fake_env()
        p = RetryPolicy(5, 0.005, 0.1, clock=clock, sleep=sleep)
        calls = [0]

        def corrupt():
            calls[0] += 1
            raise rerr.CorruptDataError("torn", path="/p")

        with pytest.raises(rerr.CorruptDataError):
            p.call(corrupt, op="t.c")
        assert calls[0] == 1

        calls[0] = 0

        def missing():
            calls[0] += 1
            raise FileNotFoundError("/gone")

        with pytest.raises(FileNotFoundError):
            p.call(missing, op="t.m")
        assert calls[0] == 1 and slept == []

    def test_deadline_scope_nests_and_restores(self):
        assert current_deadline() is None
        with deadline_scope(10.0):
            assert current_deadline() == 10.0
            with deadline_scope(5.0):
                assert current_deadline() == 5.0
            assert current_deadline() == 10.0
        assert current_deadline() is None

    def test_with_retry_passthrough_when_disabled(self):
        from hyperspace_tpu.reliability import retry as rmod

        assert rmod.active_policy() is None
        calls = [0]

        def once():
            calls[0] += 1
            return "v"

        assert with_retry(once, op="t.off") == "v"
        assert calls[0] == 1


# --- default-off byte identity ----------------------------------------------


class TestDefaultOff:
    def test_defaults_leave_registries_dormant_and_results_identical(self, tmp_path):
        from hyperspace_tpu.reliability import retry as rmod

        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(tmp_path)
        hs = hst.Hyperspace(sess)
        df = sess.read_parquet(data)
        hs.create_index(df, hst.CoveringIndexConfig("defIdx", ["c1"], ["c2"]))
        sess.enable_hyperspace()

        assert FAULTS.active is False
        assert rmod.active_policy() is None
        assert QUARANTINE.enabled is False

        injected0 = REGISTRY.counter("hs_faults_injected_total", site="x", kind="x").value
        q = sess.read_parquet(data).filter(hst.col("c1") < 50).select("c1", "c2")
        assert index_scans(q)  # quarantine filter at defaults filtered nothing
        on = q.collect()
        sess.disable_hyperspace()
        off = q.collect()
        assert _sorted_rows(on) == _sorted_rows(off)
        # dormant harness fired nothing anywhere in the query path
        assert REGISTRY.counter("hs_faults_injected_total", site="x", kind="x").value == injected0


# --- operation log: torn trailing entry (satellite regression) ---------------


class TestTornLog:
    def test_torn_trailing_entry_degrades_to_prior_version(self, tmp_path):
        from hyperspace_tpu.models.log_manager import IndexLogManager

        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(tmp_path)
        hs = hst.Hyperspace(sess)
        df = sess.read_parquet(data)
        hs.create_index(df, hst.CoveringIndexConfig("tornIdx", ["c1"], ["c2"]))

        lm = IndexLogManager(os.path.join(str(tmp_path / "indexes"), "tornIdx"))
        latest = lm.get_latest_id()
        assert latest is not None
        good = lm.get_latest_log()
        assert good is not None

        # a torn write: the next entry exists but holds half a JSON document
        torn_id = latest + 1
        full = lm.get_log(latest)
        raw = full.to_json().encode("utf-8")
        with open(lm._path(torn_id), "wb") as f:
            f.write(raw[: len(raw) // 2])

        before = counter_value("hs_log_corrupt_total", index="tornIdx")
        # the id allocator still sees the torn id — two writers must never
        # both derive torn_id + 0 as "next"
        assert lm.get_latest_id() == torn_id
        # ... but readers walk past it to the newest parseable entry
        got = lm.get_latest_log()
        assert got is not None and got.id == good.id
        assert counter_value("hs_log_corrupt_total", index="tornIdx") == before + 1

        # a genuinely missing latest id keeps the old absent semantics
        os.unlink(lm._path(torn_id))
        assert lm.get_latest_log().id == good.id

    def test_log_read_faults_are_retried_when_enabled(self, tmp_path):
        from hyperspace_tpu.models.log_manager import IndexLogManager

        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(
            tmp_path,
            **{
                hst.keys.RELIABILITY_RETRY_ENABLED: True,
                hst.keys.RELIABILITY_RETRY_BASE_MS: 0.1,
                hst.keys.RELIABILITY_RETRY_CAP_MS: 0.5,
            },
        )
        hs = hst.Hyperspace(sess)
        hs.create_index(
            sess.read_parquet(data), hst.CoveringIndexConfig("retryIdx", ["c1"], ["c2"])
        )
        lm = IndexLogManager(os.path.join(str(tmp_path / "indexes"), "retryIdx"))
        before = counter_value("hs_io_retries_total", op="log.read", reason="injected")
        with fault_scope(FaultRule("log.read", "transient", nth=1)):
            entry = lm.get_latest_log()  # first read fails, retry succeeds
        assert entry is not None
        assert counter_value("hs_io_retries_total", op="log.read", reason="injected") == before + 1


# --- typed errors through the scan pipeline (satellite regression) -----------


class TestPipelineTypedErrors:
    def test_decode_fault_surfaces_typed_cancels_queue_leaks_no_spans(self, tmp_path, monkeypatch):
        from concurrent.futures import ThreadPoolExecutor

        from hyperspace_tpu.exec import pipeline
        from hyperspace_tpu.obs import spans

        data = _write_files(str(tmp_path / "data"), num_files=8, rows_per=2000)
        sess = _mk_session(
            tmp_path,
            **{
                hst.keys.EXEC_STREAM_CHUNK_BYTES: 1,  # one file per chunk
                hst.keys.EXEC_IO_DECODE_THREADS: 1,  # serialize the decode pool
                hst.keys.OBS_TRACING_ENABLED: True,
            },
        )
        # the prefetch pool is 4 wide and the lookahead 2 deep: every submitted
        # chunk starts at once and nothing is ever queued, so whether close()
        # finds a future to cancel was a race between this thread and the
        # pool's thread start-up. One worker makes the queue real: chunk 0
        # fails, chunk 1 holds the worker for its 0.3 s stall, chunk 2 waits.
        one_wide = ThreadPoolExecutor(max_workers=1, thread_name_prefix="hs-pipeline-test")
        monkeypatch.setattr(pipeline, "_PIPELINE_POOL", one_wide)
        df = sess.read_parquet(data)
        q = df.filter(hst.col("c1") >= 0).select("c1", "c2")

        cancelled0 = counter_value("hs_pipeline_cancelled_total")
        raised0 = counter_value(
            "hs_io_errors_total", op="io.decode", kind="corrupt", outcome="raised"
        )
        with fault_scope(
            # chunk 0's decode is corrupt; chunk 1 stalls the 1-wide pool so
            # later queued prefetches are deterministically still cancellable
            FaultRule("io.decode", "corrupt", path_glob="*part-00000*"),
            FaultRule("io.decode", "latency", path_glob="*part-00001*", delay_s=0.3),
        ):
            with spans.trace("typed-error-stream") as root:
                it = q.to_local_iterator()
                with pytest.raises(rerr.CorruptDataError) as ei:
                    next(it)
                it.close()
                assert isinstance(ei.value, rerr.FaultInjected)
                open_spans = [s for s in root.walk() if s is not root and s.t1 is None]
                assert open_spans == []
            assert spans.current_span() is None
        one_wide.shutdown(wait=True)
        assert counter_value("hs_pipeline_cancelled_total") > cancelled0
        assert counter_value(
            "hs_io_errors_total", op="io.decode", kind="corrupt", outcome="raised"
        ) > raised0

    def test_source_corruption_fails_query_typed_not_quarantined(self, tmp_path):
        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(
            tmp_path, **{hst.keys.RELIABILITY_QUARANTINE_ENABLED: True}
        )
        victim = os.path.join(data, "part-00001.parquet")
        with open(victim, "wb") as f:
            f.write(b"XXXX this is not parquet")
        q = sess.read_parquet(data).filter(hst.col("c1") >= 0).select("c1")
        with pytest.raises(rerr.CorruptDataError) as ei:
            q.collect()
        # a real corruption, not an injected one, and no index to blame:
        # there is no fallback below the ground truth
        assert not isinstance(ei.value, rerr.FaultInjected)
        assert QUARANTINE.index_of_path(victim) is None


# --- quarantine circuit breaker ---------------------------------------------


class TestQuarantine:
    def _corrupt_index_files(self, index_dir):
        saved = {}
        for dirpath, _dirs, files in os.walk(index_dir):
            for fn in files:
                if fn.endswith(".parquet"):
                    p = os.path.join(dirpath, fn)
                    with open(p, "rb") as f:
                        saved[p] = f.read()
                    with open(p, "wb") as f:
                        f.write(b"XXXX torn to shreds")
        assert saved, "no index data files found to corrupt"
        return saved

    def test_trip_fallback_and_half_open_probe(self, tmp_path):
        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(
            tmp_path,
            **{
                hst.keys.RELIABILITY_QUARANTINE_ENABLED: True,
                hst.keys.RELIABILITY_QUARANTINE_THRESHOLD: 2,
                hst.keys.RELIABILITY_QUARANTINE_COOLDOWN_SECONDS: 1.0,
            },
        )
        hs = hst.Hyperspace(sess)
        df = sess.read_parquet(data)
        hs.create_index(df, hst.CoveringIndexConfig("qIdx", ["c1"], ["c2"]))
        sess.enable_hyperspace()

        def fresh_q():
            return sess.read_parquet(data).filter(hst.col("c1") < 50).select("c1", "c2")

        assert index_scans(fresh_q())
        sess.disable_hyperspace()
        want = _sorted_rows(fresh_q().collect())
        sess.enable_hyperspace()

        saved = self._corrupt_index_files(os.path.join(str(tmp_path / "indexes"), "qIdx"))
        trips0 = counter_value("hs_index_quarantined_total", index="qIdx")

        # corrupt decodes strike the breaker; every failure is typed
        for _ in range(6):
            if QUARANTINE.state_of("qIdx") == "open":
                break
            with pytest.raises(rerr.CorruptDataError):
                fresh_q().collect()
        assert QUARANTINE.state_of("qIdx") == "open"
        assert counter_value("hs_index_quarantined_total", index="qIdx") == trips0 + 1

        # quarantined: the planner re-plans against source — correct, slower
        q = fresh_q()
        assert index_scans(q) == []
        assert _sorted_rows(q.collect()) == want

        # heal the files, wait out the cooldown: the next query is the
        # half-open probe; its clean read closes the breaker
        for p, raw in saved.items():
            with open(p, "wb") as f:
                f.write(raw)
        time.sleep(1.1)
        assert _sorted_rows(fresh_q().collect()) == want
        assert QUARANTINE.state_of("qIdx") == "closed"
        assert index_scans(fresh_q())  # back in the plans

    def test_corrupt_probe_re_trips(self, tmp_path):
        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(
            tmp_path,
            **{
                hst.keys.RELIABILITY_QUARANTINE_ENABLED: True,
                hst.keys.RELIABILITY_QUARANTINE_THRESHOLD: 1,
                hst.keys.RELIABILITY_QUARANTINE_COOLDOWN_SECONDS: 1.0,
            },
        )
        hs = hst.Hyperspace(sess)
        hs.create_index(
            sess.read_parquet(data), hst.CoveringIndexConfig("rtIdx", ["c1"], ["c2"])
        )
        sess.enable_hyperspace()
        self._corrupt_index_files(os.path.join(str(tmp_path / "indexes"), "rtIdx"))

        def fresh_q():
            return sess.read_parquet(data).filter(hst.col("c1") < 50).select("c1")

        with pytest.raises(rerr.CorruptDataError):
            fresh_q().collect()
        assert QUARANTINE.state_of("rtIdx") == "open"
        time.sleep(1.1)
        # files are still corrupt: the probe read re-trips immediately
        with pytest.raises(rerr.CorruptDataError):
            fresh_q().collect()
        assert QUARANTINE.state_of("rtIdx") == "open"
        # and while re-opened, queries fall back to source again
        got = fresh_q().collect()
        assert len(got["c1"]) > 0

    def test_trip_publishes_on_invalidation_bus(self, tmp_path):
        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(
            tmp_path,
            **{
                hst.keys.RELIABILITY_QUARANTINE_ENABLED: True,
                hst.keys.RELIABILITY_QUARANTINE_THRESHOLD: 1,
            },
        )
        hs = hst.Hyperspace(sess)
        hs.create_index(
            sess.read_parquet(data), hst.CoveringIndexConfig("busIdx", ["c1"], ["c2"])
        )
        events = []
        sess.lifecycle_bus.subscribe(events.append)
        idx_file = None
        idx_root = os.path.join(str(tmp_path / "indexes"), "busIdx")
        for dirpath, _d, files in os.walk(idx_root):
            for fn in files:
                if fn.endswith(".parquet"):
                    idx_file = os.path.join(dirpath, fn)
        assert idx_file is not None
        assert QUARANTINE.note_corrupt(idx_file) == "busIdx"
        kinds = [(e.index_name, e.kind) for e in events]
        assert ("busIdx", "quarantine") in kinds
        ev = [e for e in events if e.kind == "quarantine"][0]
        assert idx_file in list(ev.affected_files)

    def test_why_not_reason(self):
        from hyperspace_tpu.analysis import reasons as R

        r = R.index_quarantined("qIdx")
        assert r.code == "INDEX_QUARANTINED"
        assert "quarantine" in r.verbose.lower()


# --- chaos soak --------------------------------------------------------------


def write_marked_part(root, marker, n=120):
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


def run_chaos_soak(tmp_path, *, rounds, workers=2, initial_files=3, n=120, seed=11):
    """Serving + background refresh + seeded fault mix. Returns violations
    (empty on a clean run) and summary counters. Invariants checked per
    result: no torn file visibility, no missing committed marker, and every
    failure is a typed, injected reliability error."""
    from hyperspace_tpu.lifecycle import RefreshManager
    from hyperspace_tpu.obs import spans
    from hyperspace_tpu.serving import QueryServer

    root = tmp_path / "chaos"
    root.mkdir()
    for i in range(initial_files):
        write_marked_part(str(root), i, n=n)

    sess = _mk_session(
        tmp_path,
        **{
            hst.keys.HYBRID_SCAN_ENABLED: True,
            hst.keys.HYBRID_SCAN_MAX_APPENDED_RATIO: 0.95,
            hst.keys.HYBRID_SCAN_MAX_DELETED_RATIO: 0.95,
            hst.keys.RELIABILITY_RETRY_ENABLED: True,
            hst.keys.RELIABILITY_RETRY_BASE_MS: 1.0,
            hst.keys.RELIABILITY_RETRY_CAP_MS: 10.0,
        },
    )
    hs = hst.Hyperspace(sess)
    hs.create_index(
        sess.read_parquet(str(root)), hst.CoveringIndexConfig("chaosIdx", ["c1"], ["m"])
    )
    sess.enable_hyperspace()
    rm = RefreshManager(sess)

    state_lock = threading.Lock()
    committed = list(range(initial_files))
    violations = []
    stop = threading.Event()
    queries_done = [0]
    typed_errors = [0]

    def query_loop():
        while not stop.is_set():
            with state_lock:
                need = list(committed)
            try:
                q = sess.read_parquet(str(root)).filter(hst.col("c1") >= 0).select("m")
                res = server.submit(q).result(timeout=60)
            except rerr.ReliabilityError as exc:
                # an injected fault that out-lived the retry budget: typed,
                # attributable, and exactly what the harness caused
                if not isinstance(exc, rerr.FaultInjected):
                    violations.append(("untyped-origin", repr(exc)))
                typed_errors[0] += 1
                continue
            except Exception as exc:
                violations.append(("unclassified-error", repr(exc)))
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

    with QueryServer(sess, workers=workers) as server:
        with fault_scope(
            FaultRule("io.decode", "transient", probability=0.08),
            FaultRule("io.footer", "transient", probability=0.05),
            FaultRule("log.read", "transient", probability=0.05),
            FaultRule("pipeline.task", "transient", probability=0.02),
            seed=seed,
        ) as registry:
            threads = [threading.Thread(target=query_loop) for _ in range(2)]
            for t in threads:
                t.start()
            try:
                for r in range(rounds):
                    marker = initial_files + r
                    write_marked_part(str(root), marker, n=n)
                    # refresh never raises: an injected fault inside the
                    # action FSM seals as outcome="error" and the prior
                    # ACTIVE entry keeps serving; the next round retries
                    outcome = rm.refresh_index("chaosIdx", "incremental")
                    if outcome == "committed":
                        with state_lock:
                            committed.append(marker)
                    time.sleep(0.05)
            finally:
                stop.set()
                for t in threads:
                    t.join(30)
            for t in threads:
                if t.is_alive():
                    violations.append(("hung-query-thread", t.name))
            fires = sum(r.fires for r in registry.rules())
    # outside the scope and the server: nothing left attached to this thread
    if spans.current_span() is not None:
        violations.append(("span-leak", repr(spans.current_span())))

    # clean-oracle comparison: faults off, hyperspace on vs off byte-compare
    q = sess.read_parquet(str(root)).filter(hst.col("c1") >= 0).select("m")
    on = q.collect()
    sess.disable_hyperspace()
    off = q.collect()
    if _sorted_rows(on) != _sorted_rows(off):
        violations.append(("oracle-mismatch", len(on["m"]), len(off["m"])))

    return {
        "violations": violations,
        "queries": queries_done[0],
        "typed_errors": typed_errors[0],
        "fault_fires": fires,
        "committed": list(committed),
    }


class TestChaosSoak:
    def test_chaos_fast(self, tmp_path):
        out = run_chaos_soak(tmp_path, rounds=4, seed=11)
        assert out["violations"] == [], out["violations"][:20]
        assert out["queries"] >= 4  # traffic really overlapped the fault mix
        assert out["fault_fires"] > 0  # the harness actually did something
        assert len(out["committed"]) >= 3


@pytest.mark.soak
@pytest.mark.slow
class TestChaosSoakLong:
    def test_chaos_long(self, tmp_path):
        out = run_chaos_soak(tmp_path, rounds=16, workers=4, seed=23)
        assert out["violations"] == [], out["violations"][:20]
        assert out["queries"] >= 16
        assert out["fault_fires"] > 10
