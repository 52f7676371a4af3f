"""Streaming device join engine (round-11): pipelined bucketed SMJ,
broadcast hash join, fused post-join filter, shared build sides.

The contract under test everywhere: streamed ≡ materialized ≡ host pandas
oracle, for every join type, across NULL keys, composite keys, empty
buckets, and fallback boundaries — streaming is an execution strategy,
never a semantics change.
"""

import os
import time

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.plan import logical as L
from hyperspace_tpu.plan.expr import col

pytestmark = pytest.mark.join


# --------------------------------------------------------------------------
# harness
# --------------------------------------------------------------------------


def _mk_session(tmp_path, **conf):
    base = {hst.keys.SYSTEM_PATH: str(tmp_path / "indexes")}
    base.update(conf)
    sess = hst.Session(conf=base)
    hst.set_session(sess)
    return sess


def _write(d, table):
    os.makedirs(d, exist_ok=True)
    pq.write_table(pa.table(table), os.path.join(d, "p.parquet"))
    return d


def _norm(df: pd.DataFrame):
    return sorted(
        tuple(
            "NULL" if x is None or (isinstance(x, float) and x != x) else str(x)
            for x in row
        )
        for row in df.itertuples(index=False)
    )


def _counter(name) -> float:
    from hyperspace_tpu.obs.metrics import REGISTRY

    snap = REGISTRY.snapshot().get(name)
    if not snap:
        return 0.0
    return sum(s["value"] for s in snap["series"])


def _stream_concat(sess, plan) -> pd.DataFrame:
    from hyperspace_tpu.exec.executor import Executor

    chunks = [pd.DataFrame(c) for c in Executor(sess).execute_stream(plan)]
    return pd.concat(chunks, ignore_index=True) if chunks else pd.DataFrame()


@pytest.fixture()
def broadcast_sides(tmp_path):
    """A large probe side and a small broadcastable side, NULL keys in both."""
    rng = np.random.default_rng(11)
    n, m = 2500, 110
    lk = rng.integers(0, 60, n).astype(np.float64)
    lk[rng.random(n) < 0.04] = np.nan
    ldata = {
        "k": lk,
        "c": np.array([f"g{v}" for v in rng.integers(0, 6, n)]),
        "v": np.round(rng.standard_normal(n), 4),
    }
    rk = rng.integers(0, 70, m).astype(np.float64)
    rk[rng.random(m) < 0.04] = np.nan
    rdata = {
        "k2": rk,
        "c2": np.array([f"g{v}" for v in rng.integers(0, 7, m)]),
        "w": np.round(rng.standard_normal(m), 4),
    }
    _write(str(tmp_path / "l"), ldata)
    _write(str(tmp_path / "r"), rdata)
    sess = _mk_session(tmp_path)
    return sess, sess.read_parquet(str(tmp_path / "l")), sess.read_parquet(
        str(tmp_path / "r")
    ), pd.DataFrame(ldata), pd.DataFrame(rdata)


# --------------------------------------------------------------------------
# broadcast hash join: oracle equivalence
# --------------------------------------------------------------------------


class TestBroadcastOracle:
    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_streamed_materialized_oracle(self, broadcast_sides, how):
        sess, ldf, rdf, lpd, rpd = broadcast_sides
        q = ldf.join(rdf, on=col("k") == col("k2"), how=how)
        before = _counter("hs_join_broadcast_total")
        got_mat = pd.DataFrame(q.collect())
        assert _counter("hs_join_broadcast_total") > before, "broadcast path not taken"
        exp = lpd.merge(
            rpd, left_on="k", right_on="k2", how="outer" if how == "outer" else how
        )
        cols = list(exp.columns)
        assert sorted(got_mat.columns) == sorted(cols)
        assert _norm(got_mat[cols]) == _norm(exp)
        got_str = _stream_concat(sess, q.optimized_plan())
        assert _norm(got_str[cols]) == _norm(exp)

    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_composite_keys(self, broadcast_sides, how):
        sess, ldf, rdf, lpd, rpd = broadcast_sides
        q = ldf.join(
            rdf, on=(col("k") == col("k2")) & (col("c") == col("c2")), how=how
        )
        got = pd.DataFrame(q.collect())
        exp = lpd.merge(
            rpd,
            left_on=["k", "c"],
            right_on=["k2", "c2"],
            how="outer" if how == "outer" else how,
        )
        assert _norm(got[list(exp.columns)]) == _norm(exp)

    def test_no_match_join_is_typed_empty(self, tmp_path):
        _write(str(tmp_path / "l"), {"k": np.arange(50, dtype=np.int64), "v": np.ones(50)})
        _write(str(tmp_path / "r"), {"k2": np.arange(1000, 1010, dtype=np.int64), "w": np.ones(10)})
        sess = _mk_session(tmp_path)
        q = sess.read_parquet(str(tmp_path / "l")).join(
            sess.read_parquet(str(tmp_path / "r")), on=col("k") == col("k2")
        )
        got = q.collect()
        assert sorted(got) == ["k", "k2", "v", "w"]
        assert all(len(a) == 0 for a in got.values())

    def test_build_over_budget_falls_back(self, broadcast_sides):
        sess, ldf, rdf, lpd, rpd = broadcast_sides
        sess.conf.set(hst.keys.EXEC_JOIN_BROADCAST_MAX_BYTES, 16)
        try:
            before = _counter("hs_join_broadcast_total")
            q = ldf.join(rdf, on=col("k") == col("k2"), how="left")
            got = pd.DataFrame(q.collect())
            assert _counter("hs_join_broadcast_total") == before, "budget gate ignored"
            exp = lpd.merge(rpd, left_on="k", right_on="k2", how="left")
            assert _norm(got[list(exp.columns)]) == _norm(exp)
        finally:
            sess.conf.set(
                hst.keys.EXEC_JOIN_BROADCAST_MAX_BYTES,
                hst.config.DEFAULTS[hst.keys.EXEC_JOIN_BROADCAST_MAX_BYTES],
            )

    def test_fused_filter_project_over_join(self, broadcast_sides):
        """Filter→Project above a Join streams through the fused post-join
        path and matches the unfused materialized answer."""
        sess, ldf, rdf, lpd, rpd = broadcast_sides
        q = (
            ldf.join(rdf, on=col("k") == col("k2"), how="inner")
            .filter(col("w") > 0.25)
            .select("k", "v", "w")
        )
        got_str = _stream_concat(sess, q.optimized_plan())
        exp = lpd.merge(rpd, left_on="k", right_on="k2", how="inner")
        exp = exp[exp["w"] > 0.25][["k", "v", "w"]]
        assert _norm(got_str[["k", "v", "w"]]) == _norm(exp)
        got_mat = pd.DataFrame(q.collect())
        assert _norm(got_mat[["k", "v", "w"]]) == _norm(exp)

    def test_outer_join_post_filter_applies_after_null_extension(self, broadcast_sides):
        """WHERE over an outer join filters the null-extended result — the
        fused path must not filter pairs before null extension."""
        sess, ldf, rdf, lpd, rpd = broadcast_sides
        q = ldf.join(rdf, on=col("k") == col("k2"), how="left").filter(col("v") > 0.0)
        got = _stream_concat(sess, q.optimized_plan())
        exp = lpd.merge(rpd, left_on="k", right_on="k2", how="left")
        exp = exp[exp["v"] > 0.0]
        assert _norm(got[list(exp.columns)]) == _norm(exp)


class TestQ3Chain:
    def test_three_table_chain_streams_end_to_end(self, tmp_path):
        """q3-shaped: big fact joined through two small dimensions with a
        filter and projection — streamed ≡ materialized ≡ pandas."""
        rng = np.random.default_rng(21)
        n = 3000
        fact = {
            "fk1": rng.integers(0, 40, n).astype(np.int64),
            "fk2": rng.integers(0, 25, n).astype(np.int64),
            "amount": np.round(rng.uniform(0, 100, n), 3),
        }
        d1 = {
            "dk1": np.arange(40, dtype=np.int64),
            "dname": np.array([f"d{i}" for i in range(40)]),
        }
        d2 = {
            "dk2": np.arange(25, dtype=np.int64),
            "region": np.array([f"r{i % 5}" for i in range(25)]),
        }
        fdir = str(tmp_path / "fact")
        os.makedirs(fdir, exist_ok=True)
        for i in range(3):  # multi-file probe side -> multi-chunk stream
            sl = slice(i * n // 3, (i + 1) * n // 3)
            pq.write_table(
                pa.table({k: v[sl] for k, v in fact.items()}),
                os.path.join(fdir, f"part-{i}.parquet"),
            )
        _write(str(tmp_path / "d1"), d1)
        _write(str(tmp_path / "d2"), d2)
        sess = _mk_session(
            tmp_path, **{hst.keys.EXEC_STREAM_CHUNK_BYTES: 8 * 1024}
        )
        f = sess.read_parquet(fdir)
        t1 = sess.read_parquet(str(tmp_path / "d1"))
        t2 = sess.read_parquet(str(tmp_path / "d2"))
        q = (
            f.join(t1, on=col("fk1") == col("dk1"))
            .join(t2, on=col("fk2") == col("dk2"))
            .filter(col("region") == "r2")
            .select("dname", "region", "amount")
        )
        exp = (
            pd.DataFrame(fact)
            .merge(pd.DataFrame(d1), left_on="fk1", right_on="dk1")
            .merge(pd.DataFrame(d2), left_on="fk2", right_on="dk2")
        )
        exp = exp[exp["region"] == "r2"][["dname", "region", "amount"]]
        before = _counter("hs_join_broadcast_total")
        got_str = _stream_concat(sess, q.optimized_plan())
        # both joins of the chain ride the broadcast streaming path
        assert _counter("hs_join_broadcast_total") >= before + 2
        assert _norm(got_str[["dname", "region", "amount"]]) == _norm(exp)
        got_mat = pd.DataFrame(q.collect())
        assert _norm(got_mat[["dname", "region", "amount"]]) == _norm(exp)

    def test_grouped_aggregate_over_streamed_join_matches_host(self, tmp_path):
        """TPC-H q3's shape: Filter -> broadcast Join -> grouped Aggregate.
        The probe side streams a file a chunk through the broadcast join and
        its post-join filter, every chunk folds into one GroupedAggStream, and
        the answer equals the host executor's (deviceExecution off)."""
        from hyperspace_tpu.exec import device as D
        from hyperspace_tpu.exec.executor import Executor

        probe_dir, build_dir = str(tmp_path / "probe"), str(tmp_path / "build")
        os.makedirs(probe_dir), os.makedirs(build_dir)
        rng = np.random.default_rng(3)
        for i in range(4):
            pq.write_table(pa.table({
                "k": rng.integers(0, 80, 900).astype(np.int64),
                "g": rng.integers(0, 12, 900).astype(np.int64),
                "v": np.round(rng.standard_normal(900), 4),
            }), os.path.join(probe_dir, f"p{i}.parquet"))
        pq.write_table(pa.table({
            "k2": rng.integers(0, 90, 120).astype(np.int64),
            "w": np.round(rng.standard_normal(120), 4),
        }), os.path.join(build_dir, "b.parquet"))

        def joined(sess):
            probe, build = sess.read_parquet(probe_dir), sess.read_parquet(build_dir)
            return probe.join(build, on=col("k") == col("k2"), how="inner").filter(col("v") > -0.5)

        aggs = dict(n=("*", "count"), s=("v", "sum"), a=("w", "avg"), mn=("v", "min"), mx=("w", "max"))
        host = _mk_session(tmp_path, **{hst.keys.TPU_QUERY_DEVICE_EXECUTION: False})
        want = pd.DataFrame(joined(host).group_by("g").agg(**aggs).collect())

        sess = _mk_session(
            tmp_path,
            **{hst.keys.TPU_QUERY_DEVICE_MIN_ROWS: 0, hst.keys.EXEC_STREAM_CHUNK_BYTES: 1},
        )
        gs = D.GroupedAggStream(
            sess, ["g"],
            [("n", "count", None), ("s", "sum", "v"), ("a", "avg", "w"),
             ("mn", "min", "v"), ("mx", "max", "w")],
            max_groups=sess.conf.agg_max_groups,
            cap_floor=sess.conf.agg_capacity_floor,
        )
        before = _counter("hs_join_broadcast_total")
        chunks = 0
        for chunk in Executor(sess).execute_stream(joined(sess).plan):
            gs.update({c: np.asarray(v) for c, v in chunk.items()}, None)
            chunks += 1
        assert chunks == 4 and _counter("hs_join_broadcast_total") == before + 1
        got = pd.DataFrame(gs.finalize())

        got, want = (d.sort_values("g").reset_index(drop=True) for d in (got, want))
        assert list(got["g"]) == list(want["g"]) and list(got["n"]) == list(want["n"])
        for c in ("s", "a", "mn", "mx"):
            np.testing.assert_allclose(got[c], want[c], rtol=1e-9, err_msg=c)

    def test_probe_compile_flatness_across_chunk_sizes(self, tmp_path):
        """Sweeping the probe chunk size must not mint per-chunk-shape probe
        executables: √2 shape buckets keep it to ≤3 per stream."""
        from hyperspace_tpu.exec import device as D

        rng = np.random.default_rng(31)
        n = 4000
        fdir = str(tmp_path / "fact")
        os.makedirs(fdir, exist_ok=True)
        for i in range(4):
            sl = slice(i * n // 4, (i + 1) * n // 4)
            pq.write_table(
                pa.table(
                    {
                        "k": rng.integers(0, 30, n).astype(np.int64)[sl],
                        "v": rng.standard_normal(n)[sl],
                    }
                ),
                os.path.join(fdir, f"part-{i}.parquet"),
            )
        _write(
            str(tmp_path / "dim"),
            {"k2": np.arange(30, dtype=np.int64), "w": np.ones(30)},
        )
        sess = _mk_session(tmp_path)
        dim = sess.read_parquet(str(tmp_path / "dim"))

        def run(chunk_bytes):
            sess.conf.set(hst.keys.EXEC_STREAM_CHUNK_BYTES, chunk_bytes)
            q = sess.read_parquet(fdir).join(dim, on=col("k") == col("k2"))
            return _stream_concat(sess, q.optimized_plan())

        baseline = run(16 * 1024)
        probes = lambda: {  # noqa: E731
            key for key in D._COMPILE_SEEN if key[0] == "hash-probe"
        }
        seen0 = probes()
        for cb in (4 * 1024, 24 * 1024, 64 * 1024, 256 * 1024 * 1024):
            got = run(cb)
            assert len(got) == len(baseline)
        new = probes() - seen0
        assert len(new) <= 3, f"probe executables not flat: {sorted(new)}"


# --------------------------------------------------------------------------
# HLO contracts
# --------------------------------------------------------------------------


class TestHloContracts:
    def test_join_programs_verify_with_zero_violations(self, tmp_path):
        from hyperspace_tpu.check import hlo_lint

        rng = np.random.default_rng(41)
        _write(
            str(tmp_path / "l"),
            {"k": rng.integers(0, 20, 1500).astype(np.int64), "v": rng.standard_normal(1500)},
        )
        _write(
            str(tmp_path / "r"),
            {"k2": np.arange(20, dtype=np.int64), "w": rng.standard_normal(20)},
        )
        sess = _mk_session(tmp_path, **{hst.keys.CHECK_HLO_ENABLED: True})
        q = (
            sess.read_parquet(str(tmp_path / "l"))
            .join(sess.read_parquet(str(tmp_path / "r")), on=col("k") == col("k2"))
            .filter(col("w") > 0.0)
            .select("k", "v", "w")
        )
        _stream_concat(sess, q.optimized_plan())
        families = {key.split("/", 1)[0] for key, _sig in hlo_lint._VERIFIED_SEEN}
        assert {"hash-build", "hash-probe", "fused-postjoin"} <= families
        assert hlo_lint.runtime_violations() == []


# --------------------------------------------------------------------------
# bucketed SMJ: pipelined streaming
# --------------------------------------------------------------------------


@pytest.fixture()
def smj_sides(tmp_path):
    """Two indexed sides so the bucketed SMJ applies; key skew leaves some
    buckets empty on one side."""
    rng = np.random.default_rng(51)
    n, m = 3000, 2200
    ldata = {
        "a": (rng.integers(0, 40, n) * 3).astype(np.int64),  # stride -> empty buckets
        "v": np.round(rng.standard_normal(n), 4),
    }
    rdata = {
        "b": (rng.integers(0, 55, m) * 3).astype(np.int64),
        "w": np.round(rng.standard_normal(m), 4),
    }
    _write(str(tmp_path / "l"), ldata)
    _write(str(tmp_path / "r"), rdata)
    sess = _mk_session(
        tmp_path,
        **{
            hst.keys.NUM_BUCKETS: 8,
            hst.keys.EXEC_JOIN_BROADCAST_MAX_BYTES: 0,  # isolate the SMJ path
        },
    )
    hs = hst.Hyperspace(sess)
    ldf = sess.read_parquet(str(tmp_path / "l"))
    rdf = sess.read_parquet(str(tmp_path / "r"))
    hs.create_index(ldf, hst.CoveringIndexConfig("sjL", ["a"], ["v"]))
    hs.create_index(rdf, hst.CoveringIndexConfig("sjR", ["b"], ["w"]))
    sess.enable_hyperspace()
    return sess, ldf, rdf, pd.DataFrame(ldata), pd.DataFrame(rdata)


class TestPipelinedSMJ:
    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_pipelined_equals_serial_equals_oracle(self, smj_sides, how):
        sess, ldf, rdf, lpd, rpd = smj_sides
        q = ldf.join(rdf, on=col("a") == col("b"), how=how).select("v", "w")
        plan = q.optimized_plan()
        exp = lpd.merge(
            rpd, left_on="a", right_on="b", how="outer" if how == "outer" else how
        )[["v", "w"]]
        pipelined = _stream_concat(sess, plan)
        sess.conf.set(hst.keys.EXEC_JOIN_PIPELINE_ENABLED, False)
        try:
            serial = _stream_concat(sess, plan)
        finally:
            sess.conf.set(hst.keys.EXEC_JOIN_PIPELINE_ENABLED, True)
        assert _norm(pipelined[["v", "w"]]) == _norm(exp)
        assert _norm(serial[["v", "w"]]) == _norm(exp)
        # determinism pin: both orders produce identical output dtypes
        assert list(pipelined.dtypes.items()) == list(serial.dtypes.items())

    def test_dispatch_stream_fold_matches(self, smj_sides):
        """The streaming-threshold path's incremental fold (no full
        list(...) materialization) returns the same batch."""
        sess, ldf, rdf, lpd, rpd = smj_sides
        q = ldf.join(rdf, on=col("a") == col("b"), how="outer").select("v", "w")
        sess.conf.set(hst.keys.EXEC_STREAM_JOIN_MIN_BYTES, 0)  # force streamed dispatch
        try:
            got = pd.DataFrame(q.collect())
        finally:
            sess.conf.set(
                hst.keys.EXEC_STREAM_JOIN_MIN_BYTES,
                hst.config.DEFAULTS[hst.keys.EXEC_STREAM_JOIN_MIN_BYTES],
            )
        exp = lpd.merge(rpd, left_on="a", right_on="b", how="outer")[["v", "w"]]
        assert _norm(got[["v", "w"]]) == _norm(exp)

    def test_midstream_close_releases_bucket_readers(self, smj_sides, monkeypatch):
        """Regression (pipeline cancel-safety): close() after one chunk must
        stop both sides' bucket decodes — queued readers are cancelled, not
        drained."""
        from hyperspace_tpu.exec import device as D

        sess, ldf, rdf, _lpd, _rpd = smj_sides
        calls = []
        orig = D._side_bucket_readers

        def spy(session, side, cols, keys):
            readers = orig(session, side, cols, keys)

            def wrap(b, fn):
                def run():
                    calls.append(b)
                    return fn()

                return run

            return {b: wrap(b, fn) for b, fn in readers.items()}

        monkeypatch.setattr(D, "_side_bucket_readers", spy)
        q = ldf.join(rdf, on=col("a") == col("b")).select("v", "w")
        join_node = L.collect(
            q.optimized_plan(), lambda p: isinstance(p, L.Join)
        )[0]
        gen = D.stream_bucketed_join(sess, join_node)
        next(gen)
        gen.close()
        n_after_close = len(calls)
        time.sleep(0.4)  # any still-running worker would keep decoding
        assert len(calls) == n_after_close, "decodes continued after close()"
        # 8 buckets x 2 sides fully drained would be 16: closing after one
        # chunk must leave the tail un-decoded (1 consumed + lookahead)
        assert n_after_close < 16, f"close() drained the whole stream ({n_after_close})"


class TestDtypeHintFallback:
    def test_dropped_hint_bumps_metric_and_trace(self):
        """An unresolvable output column no longer silently loses its dtype
        hint: the decision is surfaced as a device-fallback metric + trace."""
        from hyperspace_tpu.exec import device as D
        from hyperspace_tpu.obs.metrics import REGISTRY

        class _FakeJoin:
            output_columns = ["ghost"]

        lside = L.FileScan([], "parquet", ["a"])
        rside = L.FileScan([], "parquet", ["b"])

        def fallback_count():
            snap = REGISTRY.snapshot().get("hs_device_fallback_total")
            if not snap:
                return 0.0
            return sum(
                s["value"]
                for s in snap["series"]
                if s["labels"].get("op") == "join"
                and s["labels"].get("reason") == "dtype_hint"
            )

        before = fallback_count()
        hints = D._stream_join_dtype_hints(_FakeJoin(), lside, rside, ["a"], ["b"])
        assert hints == {}
        assert fallback_count() == before + 1


# --------------------------------------------------------------------------
# shared build sides
# --------------------------------------------------------------------------


class TestJoinBuildCache:
    def test_hit_miss_and_weigh(self):
        from hyperspace_tpu.serving.build_cache import JoinBuildCache

        c = JoinBuildCache(max_bytes=1000)
        built = []

        def builder():
            built.append(1)
            return {"x": 1}

        v1 = c.get_or_build("s1", "brandA", builder, lambda v: 100)
        v2 = c.get_or_build("s1", "brandA", builder, lambda v: 100)
        assert v1 is v2 and len(built) == 1
        assert c.stats()["hits"] == 1 and c.stats()["misses"] == 1

    def test_brand_rotation_invalidates(self):
        from hyperspace_tpu.serving.build_cache import JoinBuildCache

        c = JoinBuildCache(max_bytes=1000)
        c.get_or_build("s1", "brandA", lambda: "old", lambda v: 10)
        # new data version observed for the same structure: stale purged
        got = c.get_or_build("s1", "brandB", lambda: "new", lambda v: 10)
        assert got == "new"
        assert c.stats()["invalidations"] == 1
        assert len(c) == 1
        # the old brand can never be served again
        again = c.get_or_build("s1", "brandB", lambda: "newer", lambda v: 10)
        assert again == "new"

    def test_byte_budget_evicts_lru(self):
        from hyperspace_tpu.serving.build_cache import JoinBuildCache

        c = JoinBuildCache(max_bytes=250)
        c.get_or_build("s1", "b", lambda: "v1", lambda v: 100)
        c.get_or_build("s2", "b", lambda: "v2", lambda v: 100)
        c.get_or_build("s3", "b", lambda: "v3", lambda v: 100)  # evicts s1
        assert c.stats()["evictions"] == 1
        assert c.stats()["bytes"] == 200
        rebuilt = []
        c.get_or_build("s1", "b", lambda: rebuilt.append(1) or "v1b", lambda v: 100)
        assert rebuilt, "evicted entry must rebuild"

    def test_oversized_value_served_uncached(self):
        from hyperspace_tpu.serving.build_cache import JoinBuildCache

        c = JoinBuildCache(max_bytes=50)
        v = c.get_or_build("s1", "b", lambda: "big", lambda v: 500)
        assert v == "big" and len(c) == 0


class TestServingSharedBuilds:
    def test_build_cache_hits_under_serving(self, tmp_path):
        """Micro-batched requests joining the same dimension table pay ONE
        hash-table build: the second request hits the shared cache."""
        from hyperspace_tpu.serving import QueryServer

        rng = np.random.default_rng(61)
        _write(
            str(tmp_path / "fact"),
            {
                "k": rng.integers(0, 30, 2000).astype(np.int64),
                "v": rng.standard_normal(2000),
            },
        )
        _write(
            str(tmp_path / "dim"),
            {"k2": np.arange(30, dtype=np.int64), "w": rng.standard_normal(30)},
        )
        sess = _mk_session(tmp_path)
        fact = sess.read_parquet(str(tmp_path / "fact"))
        dim = sess.read_parquet(str(tmp_path / "dim"))
        before = _counter("hs_join_build_cache_hits_total")
        with QueryServer(sess, workers=2, result_cache_enabled=False) as srv:
            q = fact.join(dim, on=col("k") == col("k2")).select("k", "v", "w")
            futs = [srv.submit(q, timeout=60) for _ in range(4)]
            rows = [len(f.result(timeout=60)["k"]) for f in futs]
            assert len(set(rows)) == 1
            stats = srv.join_build_cache.stats()
        assert stats["hits"] >= 1, stats
        assert stats["misses"] >= 1
        assert _counter("hs_join_build_cache_hits_total") > before
        # detached after shutdown
        assert getattr(sess, "join_build_cache", None) is None
