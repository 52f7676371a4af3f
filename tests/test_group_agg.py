"""Device grouped-aggregation engine: fused filter→group-by segment reduction
with streaming partial-aggregate merge.

The device path must agree with the host pandas aggregation on every supported
shape — byte-identical for counts/int sums/min/max/keys, fp-tolerance for float
reductions (summation order differs) — and produce groups in first-appearance
order (pandas ``groupby(sort=False)`` parity). Everything else falls back,
counted in ``hs_device_fallback_total``.
"""

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.exec import trace
from hyperspace_tpu.obs.metrics import REGISTRY

pytestmark = pytest.mark.groupagg

FLOAT_RTOL = 1e-9


@pytest.fixture()
def hs(session):
    return hst.Hyperspace(session)


@pytest.fixture()
def lineitems(tmp_path):
    """TPC-H q1-shaped data: two low-cardinality string keys (with NULLs),
    int/float measures (with NULL floats), and a pruning-friendly int column."""
    d = tmp_path / "li"
    d.mkdir()
    rng = np.random.default_rng(42)
    n = 4000
    rf = rng.choice(["A", "N", "R"], n).astype(object)
    ls = rng.choice(["O", "F"], n).astype(object)
    rf[7] = None
    rf[123] = None
    qty = rng.integers(1, 51, n).astype(np.int64)
    price = np.round(rng.uniform(900.0, 105000.0, n), 2)
    disc = np.round(rng.uniform(0.0, 0.1, n), 2)
    disc[rng.choice(n, 60, replace=False)] = np.nan
    ship = rng.integers(0, 2500, n).astype(np.int64)
    for i in range(4):
        sl = slice(i * 1000, (i + 1) * 1000)
        pq.write_table(
            pa.table(
                {
                    "rf": rf[sl],
                    "ls": ls[sl],
                    "qty": qty[sl],
                    "price": price[sl],
                    "disc": disc[sl],
                    "ship": ship[sl],
                }
            ),
            d / f"p{i}.parquet",
        )
    return str(d)


def assert_grouped_equal(dev, host, float_cols=()):
    """Positional (appearance-order) equality: float columns to tolerance,
    object key columns nan/None-aware, everything else byte-identical."""
    assert sorted(dev.keys()) == sorted(host.keys())
    for k in dev:
        a, b = np.asarray(dev[k]), np.asarray(host[k])
        assert a.shape == b.shape, k
        if k in float_cols:
            np.testing.assert_allclose(a, b, rtol=FLOAT_RTOL, equal_nan=True, err_msg=k)
        elif a.dtype == object or b.dtype == object:
            # nan != nan for object arrays; any non-string (None/nan) matches
            assert all(
                (not isinstance(x, str) and not isinstance(y, str)) or x == y
                for x, y in zip(a, b)
            ), k
        else:
            np.testing.assert_array_equal(a, b, err_msg=k)


def q1_query(df):
    return (
        df.filter(hst.col("ship") <= 2400)
        .group_by("rf", "ls")
        .agg(
            sum_qty=("qty", "sum"),
            sum_price=("price", "sum"),
            avg_qty=("qty", "avg"),
            avg_price=("price", "avg"),
            avg_disc=("disc", "avg"),
            sd_price=("price", "stddev_samp"),
            n=("*", "count"),
            nd=("disc", "count"),
            lo=("price", "min"),
            hi=("qty", "max"),
        )
    )


def collect_device_and_host(session, q):
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
    dev = q.collect()
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
    host = q.collect()
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
    return dev, host


class TestDeviceVsHostOracle:
    def test_q1_shape_over_covering_index(self, session, hs, lineitems):
        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        df = session.read_parquet(lineitems)
        hs.create_index(
            df,
            hst.CoveringIndexConfig(
                "q1Idx", ["ship"], ["rf", "ls", "qty", "price", "disc"]
            ),
        )
        session.enable_hyperspace()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        q = q1_query(df)
        with trace.recording() as events:
            dev = q.collect()
        assert ("agg", "device-grouped-scan") in events
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
        host = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        # all (rf, ls) pairs present, including the NULL-rf group
        assert len(dev["rf"]) == len(host["rf"]) >= 6
        assert_grouped_equal(
            dev, host,
            float_cols=("sum_price", "avg_qty", "avg_price", "avg_disc", "sd_price", "lo"),
        )
        # byte-identical columns really are byte-identical
        for k in ("sum_qty", "n", "nd", "hi"):
            assert np.asarray(dev[k]).tobytes() == np.asarray(host[k]).tobytes(), k

    def test_null_and_signed_zero_float_keys(self, session, tmp_path):
        """NaN float keys collapse into ONE group (pandas dropna=False parity)
        and -0.0/+0.0 share a group; NULL string keys form one group."""
        d = tmp_path / "nullkeys"
        d.mkdir()
        g = np.array([1.5, np.nan, -0.0, 0.0, np.nan, 1.5, 0.0, np.nan] * 250)
        s = np.array(["x", None, "y", "x", None, "y", "x", "y"] * 250, dtype=object)
        v = np.arange(2000, dtype=np.int64)
        for i in range(2):
            sl = slice(i * 1000, (i + 1) * 1000)
            pq.write_table(pa.table({"g": g[sl], "s": s[sl], "v": v[sl]}), d / f"p{i}.parquet")
        df = session.read_parquet(str(d))
        q = df.group_by("g", "s").agg(n=("*", "count"), total=("v", "sum"))
        dev, host = collect_device_and_host(session, q)
        assert_grouped_equal(dev, host)
        # the host oracle itself: one NaN-key group per distinct (nan, s) pair
        ref = pd.DataFrame({"g": g, "s": s}).groupby(["g", "s"], dropna=False).ngroups
        assert len(host["n"]) == ref

    def test_grouped_without_filter_and_int_dtypes(self, session, tmp_path):
        """No predicate to fuse (mask is just the valid-row window) and
        narrow int / bool measures keep their host result dtypes."""
        d = tmp_path / "plain"
        d.mkdir()
        t = pa.table(
            {
                "k": np.repeat(np.arange(16, dtype=np.int64), 125),
                "i32": np.tile(np.arange(125, dtype=np.int32), 16),
                "flag": np.tile(np.array([True, False] * 62 + [True]), 16),
            }
        )
        pq.write_table(t, d / "p.parquet")
        df = session.read_parquet(str(d))
        q = df.group_by("k").agg(
            lo=("i32", "min"), hi=("i32", "max"), s=("i32", "sum"), anyf=("flag", "max")
        )
        dev, host = collect_device_and_host(session, q)
        assert_grouped_equal(dev, host)
        for k in ("lo", "hi", "s", "anyf"):
            assert np.asarray(dev[k]).dtype == np.asarray(host[k]).dtype, k


def _groups_answered() -> float:
    """``hs_agg_groups_total`` over its ``program`` series."""
    entry = REGISTRY.snapshot().get("hs_agg_groups_total", {"series": []})
    return sum(series["value"] for series in entry["series"])


class TestStreaming:
    def test_streamed_equals_materialized_and_host(self, session, lineitems):
        df = session.read_parquet(lineitems)
        q = q1_query(df)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        session.conf.set(hst.keys.EXEC_STREAM_AGG_MIN_BYTES, 1)
        session.conf.set(hst.keys.EXEC_STREAM_CHUNK_BYTES, 1)  # one file per chunk
        groups_before = _groups_answered()
        merge_before = REGISTRY.counter("hs_agg_merge_seconds_total", "").value
        with trace.recording() as events:
            streamed = q.collect()
        assert ("agg", "device-grouped-stream") in events
        assert _groups_answered() > groups_before
        # 4 chunks -> at least one device-side partial merge, with timing
        assert REGISTRY.counter("hs_agg_merge_seconds_total", "").value > merge_before
        session.conf.set(hst.keys.EXEC_STREAM_AGG_MIN_BYTES, 1 << 40)
        materialized = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
        host = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        floats = ("sum_price", "avg_qty", "avg_price", "avg_disc", "sd_price", "lo")
        assert_grouped_equal(streamed, host, float_cols=floats)
        assert_grouped_equal(materialized, host, float_cols=floats)
        for k in ("rf", "ls", "sum_qty", "n", "nd", "hi"):
            a, b = np.asarray(streamed[k]), np.asarray(materialized[k])
            if a.dtype != object:
                assert a.tobytes() == b.tobytes(), k

    def test_compile_count_flat_across_chunk_sizes(self, session, lineitems):
        """One executable per (skeleton, shape-bucket): after a warmup sweep
        over chunk sizes, repeating the same sweep adds ZERO compiles, and
        requerying a different group cardinality adds none either."""
        df = session.read_parquet(lineitems)
        q = q1_query(df)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        session.conf.set(hst.keys.EXEC_STREAM_AGG_MIN_BYTES, 1)
        compiles = REGISTRY.counter("hs_xla_compiles_total", "")
        sweep = (1, 120_000, 60_000)
        for cb in sweep:
            session.conf.set(hst.keys.EXEC_STREAM_CHUNK_BYTES, cb)
            q.collect()
        warm = compiles.value
        for _ in range(2):
            for cb in sweep:
                session.conf.set(hst.keys.EXEC_STREAM_CHUNK_BYTES, cb)
                q.collect()
        assert compiles.value == warm
        # different cardinality, same skeleton family: warm on requery
        q2 = df.group_by("ls").agg(n=("*", "count"), s=("qty", "sum"))
        q2.collect()
        warm2 = compiles.value
        q2.collect()
        assert compiles.value == warm2

    def test_chunk_over_compiled_capacity_reruns_once_right_sized(self, session, lineitems):
        """A cold stream compiles for the capacity floor. A chunk that finds
        more groups than that is re-run once at the capacity that holds them,
        the chunks after it start there, and the answer equals the host's."""
        from hyperspace_tpu.exec import device as D

        df = session.read_parquet(lineitems)
        q = df.group_by("qty").agg(n=("*", "count"), s=("price", "sum"), hi=("ship", "max"))
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        session.conf.set(hst.keys.EXEC_STREAM_AGG_MIN_BYTES, 1)
        session.conf.set(hst.keys.EXEC_STREAM_CHUNK_BYTES, 1)  # one file per chunk
        session.conf.set(hst.keys.EXEC_AGG_CAPACITY_FLOOR, 2)
        D.clear_device_cache()  # no capacity hint from an earlier run of this shape
        chunk_runs = REGISTRY.counter("hs_device_dispatches_total", "", program="grouped-agg-chunk")
        before = chunk_runs.value
        with trace.recording() as events:
            dev = q.collect()
        assert ("agg", "device-grouped-stream") in events
        assert len(dev["qty"]) == 50 > D.group_capacity(1, 2)
        assert chunk_runs.value - before == 4 + 1  # four chunks, the first of them twice
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
        host = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        assert_grouped_equal(dev, host, float_cols=("s",))

    def test_cardinality_spill_matches_host(self, session, lineitems):
        """Group cardinality above ``hyperspace.exec.agg.maxGroups`` folds the
        device partial into the host merge mid-stream — same result, plus a
        counted ``spill`` fallback."""
        df = session.read_parquet(lineitems)
        q = df.group_by("ship").agg(n=("*", "count"), s=("qty", "sum"))
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        session.conf.set(hst.keys.EXEC_STREAM_AGG_MIN_BYTES, 1)
        session.conf.set(hst.keys.EXEC_STREAM_CHUNK_BYTES, 1)
        session.conf.set(hst.keys.EXEC_AGG_MAX_GROUPS, 64)
        spills = REGISTRY.counter("hs_device_fallback_total", "", op="agg", reason="spill")
        before = spills.value
        try:
            dev = q.collect()
        finally:
            session.conf.set(hst.keys.EXEC_AGG_MAX_GROUPS, 1 << 20)
        assert spills.value > before
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
        host = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        # ~2000 distinct ship values stream through the host merge unharmed
        assert len(dev["ship"]) == len(host["ship"]) > 64
        assert_grouped_equal(dev, host)


class TestFallbacks:
    def test_unsupported_fn_falls_back_counted(self, session, hs, lineitems):
        """count_distinct is not segment-reducible: the device gate declines,
        the fallback counter ticks, and the host answer is identical to a
        device-disabled run."""
        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        df = session.read_parquet(lineitems)
        hs.create_index(
            df, hst.CoveringIndexConfig("cdIdx", ["ship"], ["rf", "qty"])
        )
        session.enable_hyperspace()
        q = (
            df.filter(hst.col("ship") < 1200)
            .group_by("rf")
            .agg(u=("qty", "count_distinct"), n=("*", "count"))
        )
        unsupported = REGISTRY.counter(
            "hs_device_fallback_total", "", op="agg", reason="unsupported"
        )
        before = unsupported.value
        dev, host = collect_device_and_host(session, q)
        # streaming declines distinct shapes before the device gate is ever
        # consulted, so only the materialized run can tick the counter; with
        # streaming off the gate must tick it exactly once per attempt
        session.conf.set(hst.keys.EXEC_STREAM_AGG_MIN_BYTES, 1 << 40)
        dev2 = q.collect()
        assert unsupported.value > before
        session.conf.set(hst.keys.EXEC_STREAM_AGG_MIN_BYTES, 1 << 30)
        assert_grouped_equal(dev, host)
        assert_grouped_equal(dev2, host)

    def test_device_program_error_in_stream_propagates(self, session, lineitems, monkeypatch):
        """Only the typed "not a device shape" signals send a streamed device
        aggregate back to the host. Anything else a device program raises (a
        TypeError, an XLA or Mosaic compile error, a runtime error) reaches the
        caller: a correct host answer must not hide a broken device path."""
        from hyperspace_tpu.exec import device as D

        df = session.read_parquet(lineitems)
        q = q1_query(df)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        session.conf.set(hst.keys.EXEC_STREAM_AGG_MIN_BYTES, 1)
        session.conf.set(hst.keys.EXEC_STREAM_CHUNK_BYTES, 1)

        def broken(self, *args, **kwargs):
            raise TypeError("shard_map() got an unexpected keyword argument")

        monkeypatch.setattr(D.GroupedAggStream, "update", broken)
        with trace.recording() as events:
            with pytest.raises(TypeError, match="unexpected keyword"):
                q.collect()
        assert ("agg", "stream-fallback") not in events

        def unsupported(self, *args, **kwargs):
            raise D.DeviceUnsupported("not a device shape")

        monkeypatch.setattr(D.GroupedAggStream, "update", unsupported)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
        host = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        assert_grouped_equal(
            q.collect(), host,
            float_cols=("sum_price", "avg_qty", "avg_price", "avg_disc", "sd_price", "lo"),
        )

    def test_min_rows_gate_counted(self, session, hs, lineitems):
        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        df = session.read_parquet(lineitems)
        hs.create_index(df, hst.CoveringIndexConfig("mrIdx", ["ship"], ["rf", "qty"]))
        session.enable_hyperspace()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 1 << 40)
        session.conf.set(hst.keys.EXEC_STREAM_AGG_MIN_BYTES, 1 << 40)
        minrows = REGISTRY.counter(
            "hs_device_fallback_total", "", op="agg", reason="min-rows"
        )
        before = minrows.value
        q = df.filter(hst.col("ship") < 1200).group_by("rf").agg(n=("*", "count"))
        q.collect()
        assert minrows.value > before
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        session.conf.set(hst.keys.EXEC_STREAM_AGG_MIN_BYTES, 1 << 30)

    def test_disabled_by_conf_never_dispatches_device(self, session, hs, lineitems):
        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        df = session.read_parquet(lineitems)
        hs.create_index(df, hst.CoveringIndexConfig("offIdx", ["ship"], ["rf", "qty"]))
        session.enable_hyperspace()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        session.conf.set(hst.keys.EXEC_AGG_DEVICE_GROUPED, False)
        try:
            q = df.filter(hst.col("ship") < 1200).group_by("rf").agg(n=("*", "count"))
            with trace.recording() as events:
                got = q.collect()
            assert ("agg", "device-grouped-scan") not in events
            assert ("agg", "device-grouped-stream") not in events
        finally:
            session.conf.set(hst.keys.EXEC_AGG_DEVICE_GROUPED, True)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
        host = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        assert_grouped_equal(got, host)


class TestPrunedScanBranding:
    """Regression: a row-group-pruned scan batch must be cached under a key
    branded with what the read kept. Two predicates can prune the same
    scan to EQUAL row counts but DIFFERENT rows; an unbranded key aliases
    them in the device column cache. The predicate itself never reaches
    the key: a whole read shares one key whatever was asked."""

    def test_pruned_key_distinct(self):
        from hyperspace_tpu.exec.executor import _pruned_scan_key

        base = (("a.parquet", 1, 2), ("b.parquet", 3, 4))
        a = _pruned_scan_key(base, (("a.parquet", (0,)),))
        b = _pruned_scan_key(base, (("a.parquet", (1,)),))
        assert a != b != base and a != base
        # the same kept groups, whichever predicate kept them: the same key
        assert a == _pruned_scan_key(base, (("a.parquet", (0,)),))
        assert a[: len(base)] == base  # purge_device_cache_files finds the files
        assert _pruned_scan_key(base, None) == base
        assert _pruned_scan_key(None, (("a.parquet", (0,)),)) is None

    def test_same_count_different_rows_no_aliasing(self, session, tmp_path):
        """Two streamed grouped aggregates over the SAME files whose pushdown
        predicates prune to identical row counts but disjoint rows: stale
        column staging would make the second result wrong."""
        d = tmp_path / "pruned"
        d.mkdir()
        # each file: ship sorted, two 500-row row groups
        for i in range(2):
            base = i * 1000
            pq.write_table(
                pa.table(
                    {
                        "ship": np.arange(base, base + 1000, dtype=np.int64),
                        "g": np.tile(np.arange(5, dtype=np.int64), 200),
                        "v": np.arange(base, base + 1000, dtype=np.int64) * 3,
                    }
                ),
                d / f"p{i}.parquet",
                row_group_size=500,
            )
        df = session.read_parquet(str(d))
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        session.conf.set(hst.keys.EXEC_STREAM_AGG_MIN_BYTES, 1)
        session.conf.set(hst.keys.EXEC_STREAM_CHUNK_BYTES, 1)

        def run(lo, hi):
            q = (
                df.filter((hst.col("ship") >= lo) & (hst.col("ship") < hi))
                .group_by("g")
                .agg(n=("*", "count"), s=("v", "sum"))
            )
            session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
            dev = q.collect()
            session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
            host = q.collect()
            session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
            assert_grouped_equal(dev, host)
            assert int(np.sum(dev["n"])) == hi - lo

        # both windows keep 500 rows of file p0 — different 500 rows
        run(0, 500)
        run(500, 1000)
        # and a window over the second file with the same shape
        run(1000, 1500)


class TestServingBatchedAggregate:
    def test_shared_scan_grouped_aggregate_matches_individual(self, session, tmp_path):
        from hyperspace_tpu.serving.batcher import execute_shared_scan, shared_scan_ops

        rng = np.random.default_rng(3)
        n = 3000
        pq.write_table(
            pa.table(
                {
                    "dept": rng.integers(0, 9, n).astype(np.int64),
                    "price": rng.standard_normal(n) * 50 + 50,
                    "qty": rng.integers(1, 20, n).astype(np.int32),
                }
            ),
            tmp_path / "t.parquet",
        )
        session.read_parquet(str(tmp_path / "t.parquet")).create_or_replace_temp_view("t")
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        sql = "SELECT dept, count(*) AS n, sum(qty) AS s FROM t WHERE price > {v} GROUP BY dept"
        template = session.sql(sql.format(v=45)).plan
        got = shared_scan_ops(template)
        assert got is not None
        ops, leaf = got
        assert "aggregate" in [k for k, _ in ops]
        bound = [session.sql(sql.format(v=v)).plan for v in (45, 20, 80)]
        batches = execute_shared_scan(session, ops, leaf, bound)
        for v, gotb in zip((45, 20, 80), batches):
            want = session.sql(sql.format(v=v)).collect()
            assert sorted(gotb.keys()) == sorted(want.keys())
            for c in want:
                np.testing.assert_array_equal(
                    np.asarray(gotb[c]), np.asarray(want[c]), err_msg=f"{v}:{c}"
                )

    def test_having_shape_stays_unbatched(self, session, tmp_path):
        from hyperspace_tpu.serving.batcher import shared_scan_ops

        pq.write_table(
            pa.table({"k": np.arange(100, dtype=np.int64) % 5, "v": np.arange(100.0)}),
            tmp_path / "h.parquet",
        )
        session.read_parquet(str(tmp_path / "h.parquet")).create_or_replace_temp_view("h")
        plan = session.sql(
            "SELECT k, count(*) AS n FROM h WHERE v > 1 GROUP BY k HAVING count(*) > 2"
        ).plan
        assert shared_scan_ops(plan) is None
