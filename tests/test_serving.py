"""Unit tests for the serving runtime (hyperspace_tpu/serving/).

Each component is exercised in isolation — plan cache tiers and eviction,
admission backpressure, bucket cache + prefetch, metrics, micro-batch
decomposition — plus QueryServer integration against ``collect()`` ground
truth. Concurrency/throughput behavior lives in test_serving_stress.py.
"""

import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.serving import (
    AdmissionController,
    AdmissionRejected,
    BucketCache,
    PlanCache,
    QueryServer,
    RequestTimeout,
    ServerClosed,
    ServingMetrics,
    plan_fingerprint,
    session_token,
)


@pytest.fixture()
def simple(tmp_path):
    n = 500
    pq.write_table(
        pa.table(
            {
                "id": np.arange(n, dtype=np.int64),
                "name": np.array([f"n{i % 11}" for i in range(n)]),
                "price": (np.arange(n, dtype=np.int64) * 7) % 100,
            }
        ),
        str(tmp_path / "t.parquet"),
    )
    sess = hst.Session()
    sess.read_parquet(str(tmp_path / "t.parquet")).create_or_replace_temp_view("t")
    return sess


# --- plan cache --------------------------------------------------------------


def test_plan_cache_param_tier_hits(simple):
    cache = PlanCache(max_entries=8)
    tok = session_token(simple, False)
    p45 = simple.sql("SELECT name FROM t WHERE price > 45").plan
    f45 = plan_fingerprint(p45)
    assert cache.lookup(tok, f45) is None  # cold
    cache.insert(tok, f45, p45)

    f40 = plan_fingerprint(simple.sql("SELECT name FROM t WHERE price > 40").plan)
    hit = cache.lookup(tok, f40)
    assert hit is not None
    bound, entry = hit
    assert entry.parameterizable
    assert plan_fingerprint(bound).exact == f40.exact  # literals rebound
    s = cache.stats()
    assert s["paramHits"] == 1 and s["misses"] == 1 and s["entries"] == 1


def test_plan_cache_session_token_separates_modes(simple):
    cache = PlanCache()
    p = simple.sql("SELECT name FROM t WHERE price > 45").plan
    fp = plan_fingerprint(p)
    cache.insert(session_token(simple, False), fp, p)
    # same plan under hyperspace-on token must NOT reuse the off-mode template
    assert cache.lookup(session_token(simple, True), fp) is None


def test_plan_cache_eviction_accounting(simple):
    cache = PlanCache(max_entries=2)
    tok = session_token(simple, False)
    texts = [
        "SELECT name FROM t WHERE price > 1",
        "SELECT id FROM t WHERE price > 1",
        "SELECT price FROM t WHERE id > 1",
    ]
    for q in texts:
        p = simple.sql(q).plan
        cache.insert(tok, plan_fingerprint(p), p)
    s = cache.stats()
    assert s["entries"] == 2 and s["evictions"] == 1
    assert len(cache) == 2


def test_plan_cache_subquery_goes_exact_tier(simple):
    cache = PlanCache()
    tok = session_token(simple, False)
    q = "SELECT name FROM t WHERE price > (SELECT avg(price) FROM t WHERE id < 100)"
    p = simple.sql(q).plan
    fp = plan_fingerprint(p)
    entry = cache.insert(tok, fp, p)
    assert not entry.parameterizable
    # verbatim repeat hits the exact tier
    hit = cache.lookup(tok, plan_fingerprint(simple.sql(q).plan))
    assert hit is not None
    assert cache.stats()["exactHits"] == 1


# --- admission ---------------------------------------------------------------


def test_admission_rejects_on_overflow():
    adm = AdmissionController(depth=2, default_timeout=None)
    adm.submit("a")
    adm.submit("b")
    with pytest.raises(AdmissionRejected):
        adm.submit("c")
    s = adm.stats()
    assert s == {"depth": 2, "queued": 2, "submitted": 2, "rejected": 1, "timeouts": 0}
    assert adm.take() == "a" and adm.take_nowait() == "b" and adm.take_nowait() is None


def test_admission_deadlines():
    adm = AdmissionController(depth=1, default_timeout=5.0)
    assert adm.deadline_for(None) > time.monotonic()
    assert adm.deadline_for(0.1) < time.monotonic() + 1.0
    assert AdmissionController(depth=1, default_timeout=None).deadline_for(None) is None
    with pytest.raises(ValueError):
        AdmissionController(depth=0, default_timeout=None)


# --- bucket cache ------------------------------------------------------------


def _write_files(tmp_path, k, rows=200):
    files = []
    for i in range(k):
        f = str(tmp_path / f"b{i}.parquet")
        pq.write_table(
            pa.table({"v": np.arange(i * rows, (i + 1) * rows, dtype=np.int64)}), f
        )
        files.append(f)
    return files


def test_bucket_cache_hit_miss_and_freeze(tmp_path):
    files = _write_files(tmp_path, 2)
    bc = BucketCache(cap_bytes=1 << 20)
    a = bc.read(files, ["v"])
    b = bc.read(files, ["v"])
    assert np.array_equal(a["v"], b["v"]) and len(a["v"]) == 400
    s = bc.stats()
    assert s["hits"] == 1 and s["misses"] == 1 and s["hitRate"] == 0.5
    with pytest.raises(ValueError):
        b["v"][0] = 99  # cached arrays are frozen


def test_bucket_cache_byte_budget_evicts(tmp_path):
    files = _write_files(tmp_path, 4, rows=500)
    bc = BucketCache(cap_bytes=int(500 * 8 * 1.5))  # fits ~one file's batch
    for f in files:
        bc.read([f], ["v"])
    s = bc.stats()
    assert s["evictions"] >= 2
    assert s["bytes"] <= s["capBytes"]


def test_bucket_cache_prefetch_lands(tmp_path):
    files = _write_files(tmp_path, 1)
    bc = BucketCache(cap_bytes=1 << 20, prefetch_workers=1)
    assert bc.prefetch(files, ["v"]) is True
    deadline = time.monotonic() + 10
    while bc.stats()["prefetchCompleted"] < 1 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert bc.stats()["prefetchCompleted"] == 1
    before = bc.stats()["hits"]
    bc.read(files, ["v"])
    assert bc.stats()["hits"] == before + 1  # request path found it resident
    assert bc.prefetch(files, ["v"]) is False  # already cached: no refetch
    bc.shutdown()


# --- metrics -----------------------------------------------------------------


def test_metrics_percentiles_and_counters():
    m = ServingMetrics(latency_window=128)
    assert m.latency_percentiles() == {"p50": None, "p95": None, "p99": None}
    for v in np.linspace(0.001, 0.1, 100):
        m.observe(float(v))
    m.observe(1.0, error=True)
    m.observe_batch(4)
    p = m.latency_percentiles()
    assert p["p50"] <= p["p95"] <= p["p99"]
    snap = m.snapshot()
    assert snap["completed"] == 100 and snap["errors"] == 1
    assert snap["batches"] == 1 and snap["batchedRequests"] == 4


# --- telemetry thread safety -------------------------------------------------


def test_collecting_logger_concurrent_appends():
    from hyperspace_tpu.telemetry.events import CollectingEventLogger, HyperspaceEvent

    logger = CollectingEventLogger()
    n_threads, per_thread = 8, 250

    def work():
        for _ in range(per_thread):
            logger.log_event(HyperspaceEvent(message="x"))

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(logger.events) == n_threads * per_thread
    assert len(logger.snapshot()) == n_threads * per_thread
    # events stays a real list: in-place clear() (used by existing tests) works
    logger.events.clear()
    assert logger.snapshot() == []


# --- context-local hyperspace toggle ----------------------------------------


def test_hyperspace_scope_is_thread_local(simple):
    simple.enable_hyperspace()
    seen = {}

    def other_thread():
        seen["other"] = simple.hyperspace_enabled

    with simple.with_hyperspace_disabled():
        assert simple.hyperspace_enabled is False
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
    assert simple.hyperspace_enabled is True
    # a scope in one thread never leaks into another: the other thread saw
    # the session default, not this thread's override
    assert seen["other"] is True
    simple.disable_hyperspace()
    assert simple.hyperspace_enabled is False


def test_hyperspace_scope_nests_and_restores_on_error(simple):
    simple.enable_hyperspace()
    with simple.hyperspace_scope(False):
        with simple.hyperspace_scope(True):
            assert simple.hyperspace_enabled is True
        assert simple.hyperspace_enabled is False
    with pytest.raises(RuntimeError):
        with simple.with_hyperspace_disabled():
            raise RuntimeError("boom")
    assert simple.hyperspace_enabled is True


# --- micro-batch decomposition ----------------------------------------------


def test_shared_scan_ops_shapes(simple):
    from hyperspace_tpu.serving.batcher import shared_scan_ops

    chain = simple.sql("SELECT name FROM t WHERE price > 5").plan
    got = shared_scan_ops(chain)
    assert got is not None
    ops, leaf = got
    assert [k for k, _ in ops] == ["project", "filter"]
    # no filter -> nothing literal-varying to share
    assert shared_scan_ops(simple.sql("SELECT name FROM t").plan) is None
    # one aggregate may cap the chain (its filters sit below it)
    agg = shared_scan_ops(simple.sql("SELECT count(*) AS c FROM t WHERE price > 5").plan)
    assert agg is not None
    assert "aggregate" in [k for k, _ in agg[0]]


def test_execute_shared_scan_matches_individual(simple):
    from hyperspace_tpu.serving.batcher import execute_shared_scan, shared_scan_ops

    template = simple.sql("SELECT name, id FROM t WHERE price > 45").plan
    ops, leaf = shared_scan_ops(template)
    bound = [simple.sql(f"SELECT name, id FROM t WHERE price > {v}").plan for v in (45, 20, 80)]
    batches = execute_shared_scan(simple, ops, leaf, bound)
    for v, got in zip((45, 20, 80), batches):
        want = simple.sql(f"SELECT name, id FROM t WHERE price > {v}").collect()
        assert np.array_equal(got["name"], want["name"])
        assert np.array_equal(got["id"], want["id"])


# --- QueryServer integration -------------------------------------------------


def test_server_matches_collect_and_relabels(simple):
    with QueryServer(simple, workers=2) as srv:
        r1 = srv.query("SELECT name FROM t WHERE price > 45")
        r2 = srv.query("SELECT name FROM t WHERE price > 20")
        r3 = srv.query("SELECT name AS m FROM t WHERE price > 20")
        want45 = simple.sql("SELECT name FROM t WHERE price > 45").collect()
        want20 = simple.sql("SELECT name FROM t WHERE price > 20").collect()
        assert np.array_equal(r1["name"], want45["name"])
        assert np.array_equal(r2["name"], want20["name"])
        assert list(r3.keys()) == ["m"] and np.array_equal(r3["m"], want20["name"])
        s = srv.stats()
        assert s["planCache"]["paramHits"] >= 2  # r2 and r3 bound the r1 template
        assert s["queue"]["submitted"] == 3 and s["queue"]["rejected"] == 0
        assert s["completed"] == 3 and s["errors"] == 0


def test_server_accepts_dataframe_and_exact_repeat(simple):
    with QueryServer(simple, workers=1) as srv:
        df = simple.sql("SELECT id FROM t WHERE price < 10")
        a = srv.query(df)
        b = srv.query("SELECT id FROM t WHERE price < 10")
        want = df.collect()
        assert np.array_equal(a["id"], want["id"]) and np.array_equal(b["id"], want["id"])
        assert srv.stats()["planCache"]["hits"] >= 1


def test_server_bad_query_resolves_future_with_error(simple, tmp_path):
    import os

    doomed = str(tmp_path / "gone.parquet")
    pq.write_table(pa.table({"v": np.arange(5, dtype=np.int64)}), doomed)
    simple.read_parquet(doomed).create_or_replace_temp_view("gone")
    with QueryServer(simple, workers=1) as srv:
        # parse errors surface synchronously at submit time
        with pytest.raises(Exception):
            srv.submit("SELECT nope FROM t WHERE price > 1")
        # execution errors resolve the future, and the worker survives them
        df = simple.sql("SELECT v FROM gone WHERE v > 1")
        os.remove(doomed)
        with pytest.raises(Exception):
            srv.query(df)
        got = srv.query("SELECT id FROM t WHERE price > 90")
        want = simple.sql("SELECT id FROM t WHERE price > 90").collect()
        assert np.array_equal(got["id"], want["id"])
        assert srv.stats()["errors"] >= 1


def test_server_overflow_rejects_and_shutdown_drains(simple):
    # workers=0: nothing consumes the queue, so overflow is deterministic
    srv = QueryServer(simple, workers=0, queue_depth=3).start()
    futs = [srv.submit(f"SELECT id FROM t WHERE price > {i}") for i in range(3)]
    with pytest.raises(AdmissionRejected):
        srv.submit("SELECT id FROM t WHERE price > 99")
    assert srv.stats()["queue"]["rejected"] == 1
    srv.shutdown()
    for f in futs:  # no future is left dangling after shutdown
        with pytest.raises(ServerClosed):
            f.result(timeout=1)
    with pytest.raises(ServerClosed):
        srv.submit("SELECT id FROM t WHERE price > 1")


def test_server_rejection_emits_telemetry(tmp_path):
    pq.write_table(pa.table({"v": np.arange(10, dtype=np.int64)}), str(tmp_path / "x.parquet"))
    sess = hst.Session(
        conf={hst.keys.EVENT_LOGGER_CLASS: "hyperspace_tpu.telemetry.events.CollectingEventLogger"}
    )
    sess.read_parquet(str(tmp_path / "x.parquet")).create_or_replace_temp_view("x")
    logger = hst.telemetry.events.get_event_logger(sess)
    logger.reset()
    srv = QueryServer(sess, workers=0, queue_depth=1).start()
    try:
        srv.submit("SELECT v FROM x WHERE v > 1")
        with pytest.raises(AdmissionRejected):
            srv.submit("SELECT v FROM x WHERE v > 2")
        rejections = [e for e in logger.snapshot() if e.name == "ServingRejectionEvent"]
        assert len(rejections) == 1 and rejections[0].queue_depth == 1
        srv.stats(emit=True)
        stats_events = [e for e in logger.snapshot() if e.name == "ServingStatsEvent"]
        assert len(stats_events) == 1
        assert stats_events[0].rejected == 1
    finally:
        srv.shutdown()
        logger.reset()


def test_server_timeout_in_queue(simple):
    with QueryServer(simple, workers=1) as srv:
        fut = srv.submit("SELECT id FROM t WHERE price > 7", timeout=0.0)
        with pytest.raises(RequestTimeout):
            fut.result(timeout=10)
        assert srv.stats()["queue"]["timeouts"] >= 1


def test_server_rejects_unknown_option(simple):
    with pytest.raises(TypeError):
        QueryServer(simple, wrokers=2)


@pytest.mark.parametrize("requires, error", [
    (["join-agg-resident"], None),
    (("fused-agg", "grouped-agg-dense", "join-agg-resident"), None),
    ([], None),
    (["join-agg-resident", "no-such-program"], "no-such-program"),
])
def test_server_requires_program_families(simple, requires, error):
    """``requires`` asserts that the build registers the device program
    families a deployment was sized for; it refuses at construction."""
    if error is None:
        with QueryServer(simple, requires=requires, workers=1):
            pass
        return
    with pytest.raises(ValueError, match=error):
        QueryServer(simple, requires=requires)


def test_serving_conf_defaults(simple):
    conf = simple.conf
    assert conf.serving_queue_depth == 64
    assert conf.serving_workers == 4
    assert conf.serving_default_timeout_seconds == 30.0
    assert conf.serving_plan_cache_enabled is True
    assert conf.serving_plan_cache_max_entries == 256
    assert conf.serving_micro_batch_enabled is True
    assert conf.serving_micro_batch_max_requests == 16
    assert conf.serving_micro_batch_max_wait_ms == 2.0
    assert conf.serving_bucket_cache_bytes == 1 << 30
    assert conf.serving_prefetch_enabled is True
    assert conf.serving_prefetch_workers == 2


def test_server_reads_conf_keys(tmp_path):
    pq.write_table(pa.table({"v": np.arange(10, dtype=np.int64)}), str(tmp_path / "x.parquet"))
    sess = hst.Session(
        conf={
            hst.keys.SERVING_QUEUE_DEPTH: 7,
            hst.keys.SERVING_WORKERS: 1,
            hst.keys.SERVING_PLAN_CACHE_ENABLED: False,
            hst.keys.SERVING_BUCKET_CACHE_BYTES: 12345,
        }
    )
    srv = QueryServer(sess)
    assert srv.admission.depth == 7
    assert srv.workers_n == 1
    assert srv.plan_cache_enabled is False
    assert srv.bucket_cache.stats()["capBytes"] == 12345
    assert "planCache" not in srv.metrics.snapshot(
        admission=srv.admission, plan_cache=None, bucket_cache=srv.bucket_cache
    )
