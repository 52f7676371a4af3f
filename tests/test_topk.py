"""Streaming device top-k / order-aware planning (ORDER BY/LIMIT).

Pinned properties:
- streamed device top-k ≡ materialized host sort+slice ≡ pandas oracle,
  byte-identical, across asc/desc, multi-key, NULLs (LAST both directions),
  NaN floats, string keys with None, and ties (stable, input order);
- geometric candidate capacities keep hs_xla_compiles_total flat across
  chunk-size sweeps once the shape buckets are warm;
- the sharded (shard_map + one all_gather) path is byte-identical to the
  single-device path;
- ORDER BY covered by a covering index's within-bucket sort order eliminates
  the Sort into a streamed merge of sorted runs (dispatch proven by trace
  goldens; refusals explained in EXPLAIN WHY NOT);
- the running k-th-value threshold feeds row-group pruning (counters prove
  skipped groups) without changing results;
- a bare LIMIT stops decoding early and cancels queued prefetch decodes.
"""

import os
import threading
import time

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.exec import trace
from hyperspace_tpu.obs.metrics import REGISTRY

pytestmark = pytest.mark.topk


def _write_files(d, num_files=6, rows_per=800, seed=7):
    """Multi-file dataset with every ordering hazard: NaN floats, None
    strings, low-cardinality tie keys, and a pruning-friendly int column."""
    os.makedirs(d, exist_ok=True)
    rng = np.random.default_rng(seed)
    for i in range(num_files):
        k = rng.integers(0, 10_000, rows_per).astype(np.int64)
        v = np.round(rng.uniform(-100, 100, rows_per), 3)
        v[rng.choice(rows_per, 20, replace=False)] = np.nan
        name = np.array([f"name_{j % 31:02d}" for j in range(rows_per)], dtype=object)
        name[rng.choice(rows_per, 15, replace=False)] = None
        grp = rng.integers(0, 5, rows_per).astype(np.int64)
        t = pa.table({"k": k, "v": v, "name": name, "grp": grp})
        pq.write_table(t, os.path.join(d, f"part-{i:05d}.parquet"))
    return d


def _mk_session(tmp_path, **conf):
    base = {
        hst.keys.SYSTEM_PATH: str(tmp_path / "indexes"),
        hst.keys.NUM_BUCKETS: 8,
        hst.keys.EXEC_STREAM_CHUNK_BYTES: 1,  # one file per chunk
    }
    base.update(conf)
    sess = hst.Session(conf=base)
    hst.set_session(sess)
    return sess


def _oracle(data, keys, ascending, n):
    """The semantics contract: pandas stable sort, NULLS LAST both ways."""
    # dtype pinned per column: pandas 3 would infer its `str` dtype for an
    # object column and hand missing strings back as NaN, where the product
    # (and parquet) keep None
    pdf = pd.DataFrame({c: pd.Series(v, dtype=np.asarray(v).dtype) for c, v in data.items()})
    out = pdf.sort_values(list(keys), ascending=list(ascending), kind="stable", na_position="last")
    return out.head(n)


def _assert_batch_equals_frame(got, frame):
    assert set(got) == set(frame.columns)
    for c in frame.columns:
        np.testing.assert_array_equal(
            np.asarray(got[c]), frame[c].to_numpy(), err_msg=c
        )


CASES = [
    (("k",), (True,)),
    (("k",), (False,)),
    (("v",), (False,)),  # NaN floats, descending
    (("v", "k"), (False, True)),  # mixed directions, float primary
    (("name", "k"), (True, True)),  # string primary with None
    (("name", "v"), (False, True)),  # string descending + float tiebreak
]


class TestTopkVsOracle:
    @pytest.mark.parametrize("keys,asc", CASES, ids=["-".join(k) + str(a) for k, a in CASES])
    def test_streamed_device_topk_byte_identical(self, tmp_path, keys, asc):
        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(tmp_path)
        df = sess.read_parquet(data)
        q = df.order_by(*keys, ascending=list(asc)).limit(25)
        with trace.recording() as events:
            got = q.collect()
        assert ("topk", "device-topk-stream") in events
        # host path: same query with the top-k fold disabled
        sess.conf.set(hst.keys.EXEC_TOPK_ENABLED, False)
        host = q.collect()
        for c in host:
            np.testing.assert_array_equal(np.asarray(got[c]), np.asarray(host[c]), err_msg=c)
        # pandas oracle over the full materialized scan
        raw = df.collect()
        _assert_batch_equals_frame(got, _oracle(raw, keys, asc, 25))

    def test_stable_ties_match_input_order(self, tmp_path):
        """grp has 5 values over 4800 rows: LIMIT spans many full tie groups;
        the device rid plane must reproduce the stable host order exactly."""
        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(tmp_path)
        df = sess.read_parquet(data)
        q = df.order_by("grp").limit(1200)
        with trace.recording() as events:
            got = q.collect()
        assert ("topk", "device-topk-stream") in events
        raw = df.collect()
        _assert_batch_equals_frame(got, _oracle(raw, ("grp",), (True,), 1200))

    def test_limit_larger_than_rows(self, tmp_path):
        data = _write_files(str(tmp_path / "data"), num_files=2, rows_per=100)
        sess = _mk_session(tmp_path)
        df = sess.read_parquet(data)
        got = df.order_by("k").limit(3000).collect()
        raw = df.collect()
        _assert_batch_equals_frame(got, _oracle(raw, ("k",), (True,), 3000))
        assert len(got["k"]) == 200


class TestHostOrderPin:
    """The host Sort semantics the device path must reproduce: NULLS LAST in
    BOTH directions, ties stable in input order (pandas parity)."""

    @pytest.mark.parametrize("asc", [True, False])
    def test_full_sort_nulls_last_stable(self, tmp_path, asc):
        data = _write_files(str(tmp_path / "data"), num_files=2, rows_per=400)
        sess = _mk_session(tmp_path, **{hst.keys.EXEC_TOPK_ENABLED: False})
        df = sess.read_parquet(data)
        got = df.order_by("v", ascending=[asc]).collect()
        raw = df.collect()
        want = _oracle(raw, ("v",), (asc,), len(raw["v"]))
        _assert_batch_equals_frame(got, want)
        # NULLS LAST: the trailing rows are exactly the NaN rows
        n_nan = int(np.isnan(raw["v"]).sum())
        assert n_nan > 0 and np.isnan(np.asarray(got["v"][-n_nan:])).all()

    @pytest.mark.parametrize("asc", [True, False])
    def test_string_none_last(self, tmp_path, asc):
        data = _write_files(str(tmp_path / "data"), num_files=2, rows_per=400)
        sess = _mk_session(tmp_path, **{hst.keys.EXEC_TOPK_ENABLED: False})
        df = sess.read_parquet(data)
        got = df.order_by("name", ascending=[asc]).collect()
        raw = df.collect()
        _assert_batch_equals_frame(got, _oracle(raw, ("name",), (asc,), len(raw["name"])))
        n_none = sum(x is None for x in raw["name"])
        assert n_none > 0
        assert all(x is None for x in list(got["name"])[-n_none:])


class TestCompileFlatness:
    def test_chunk_size_sweep_mints_no_new_programs(self, tmp_path):
        """The plane-matrix program is keyed on (key count, capacity, shape
        bucket): once a sweep has warmed the buckets, re-running the sweep —
        and any limit that maps to the same capacity bucket — compiles
        nothing new."""
        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(tmp_path)
        df = sess.read_parquet(data)
        compiles = REGISTRY.counter("hs_xla_compiles_total", "")
        sweep = [1, 40_000, 10_000_000]  # files-per-chunk: 1, a few, all-in-one gate
        for nbytes in sweep:
            sess.conf.set(hst.keys.EXEC_STREAM_CHUNK_BYTES, nbytes)
            df.order_by("v", "k", ascending=[False, True]).limit(30).collect()
        warm = compiles.value
        for nbytes in sweep:
            sess.conf.set(hst.keys.EXEC_STREAM_CHUNK_BYTES, nbytes)
            df.order_by("v", "k", ascending=[False, True]).limit(30).collect()
            # a different k in the same geometric capacity bucket reuses too
            sess_got = df.order_by("v", "k", ascending=[False, True]).limit(21).collect()
            assert len(sess_got["k"]) == 21
        assert compiles.value == warm


class TestShardedTopk:
    def test_sharded_matches_single_device(self, tmp_path):
        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(
            tmp_path,
            **{hst.keys.PARALLEL_ENABLED: True, hst.keys.PARALLEL_MIN_ROWS: 1},
        )
        df = sess.read_parquet(data)
        q = df.order_by("v", "k", ascending=[False, True]).limit(40)
        with trace.recording() as events:
            sharded = q.collect()
        assert ("topk", "device-topk-stream-sharded") in events
        sess.conf.set(hst.keys.PARALLEL_ENABLED, False)
        with trace.recording() as events:
            single = q.collect()
        assert ("topk", "device-topk-stream") in events
        for c in single:
            np.testing.assert_array_equal(
                np.asarray(sharded[c]), np.asarray(single[c]), err_msg=c
            )


class TestSortElimination:
    def _indexed(self, tmp_path, sess):
        data = _write_files(str(tmp_path / "data"))
        df = sess.read_parquet(data)
        hs = hst.Hyperspace(sess)
        hs.create_index(df, hst.CoveringIndexConfig("ordIdx", ["k"], ["v", "grp"]))
        sess.enable_hyperspace()
        return df, hs

    def test_covered_order_streams_as_run_merge(self, tmp_path):
        sess = _mk_session(tmp_path)
        df, _ = self._indexed(tmp_path, sess)
        q = df.filter(hst.col("k") > 50).select("k", "v").order_by("k")
        with trace.recording() as events:
            got = q.collect()
        # dispatch golden: the Sort was eliminated, not executed
        assert trace.summarize(events).splitlines().count("sort: index-order-merge x1") == 1
        sess.disable_hyperspace()
        want = q.collect()
        for c in want:
            np.testing.assert_array_equal(np.asarray(got[c]), np.asarray(want[c]), err_msg=c)

    def test_covered_order_with_limit(self, tmp_path):
        sess = _mk_session(tmp_path)
        df, _ = self._indexed(tmp_path, sess)
        q = df.filter(hst.col("k") > 50).select("k", "v").order_by("k").limit(17)
        with trace.recording() as events:
            got = q.collect()
        assert any(d.startswith("index-order-merge-limit") for kk, d in events if kk == "sort")
        sess.disable_hyperspace()
        want = q.collect()
        for c in want:
            np.testing.assert_array_equal(np.asarray(got[c]), np.asarray(want[c]), err_msg=c)

    def test_descending_refusal_reason_and_why_not(self, tmp_path):
        sess = _mk_session(tmp_path)
        df, hs = self._indexed(tmp_path, sess)
        q = df.filter(hst.col("k") > 50).select("k", "v").order_by("k", ascending=[False])
        with trace.recording() as events:
            q.collect()
        reasons = [d for kk, d in events if kk == "sort" and d.startswith("merge-why-not")]
        assert reasons and "cannot ride the ascending index order" in reasons[0]
        text = hs.why_not(q, "ordIdx")
        assert "Sort elimination:" in text
        assert "cannot ride the ascending index order" in text

    def test_eliminated_sort_reported_in_why_not(self, tmp_path):
        sess = _mk_session(tmp_path)
        df, hs = self._indexed(tmp_path, sess)
        q = df.filter(hst.col("k") > 50).select("k", "v").order_by("k")
        text = hs.why_not(q, "ordIdx")
        assert "Sort elimination:" in text
        assert "eliminated — streamed merge of sorted index runs" in text


class TestDynamicThresholdPruning:
    def test_threshold_skips_rowgroups_without_changing_results(self, tmp_path):
        """Files carry disjoint sorted k ranges: after the first chunk the
        k-th candidate's value proves every later row group useless."""
        d = str(tmp_path / "data")
        os.makedirs(d)
        for i in range(6):
            k = np.arange(i * 1000, (i + 1) * 1000, dtype=np.int64)
            t = pa.table({"k": k, "v": k.astype(np.float64) / 3})
            pq.write_table(t, os.path.join(d, f"part-{i:05d}.parquet"), row_group_size=250)
        # serial decode: with prefetch on, a few chunks decode before the
        # first threshold lands, which blurs the skipped-row-group count
        sess = _mk_session(tmp_path, **{hst.keys.EXEC_PIPELINE_ENABLED: False})
        df = sess.read_parquet(d)
        q = df.order_by("k").limit(10)
        updates = REGISTRY.counter("hs_topk_threshold_updates_total", "")
        skipped = REGISTRY.counter("hs_rowgroups_skipped_total", "")
        u0, s0 = updates.value, skipped.value
        with trace.recording() as events:
            got = q.collect()
        assert ("topk", "device-topk-stream") in events
        assert updates.value > u0
        # after file 0 the threshold is k<=9: every row group of the other
        # 5 files (4 each) is provably above it
        assert skipped.value - s0 >= 20
        np.testing.assert_array_equal(np.asarray(got["k"]), np.arange(10, dtype=np.int64))

    def test_pushdown_disabled_still_correct(self, tmp_path):
        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(tmp_path, **{hst.keys.EXEC_TOPK_THRESHOLD_PUSHDOWN: False})
        df = sess.read_parquet(data)
        got = df.order_by("k").limit(12).collect()
        raw = df.collect()
        _assert_batch_equals_frame(got, _oracle(raw, ("k",), (True,), 12))


class TestEarlyLimit:
    def test_bare_limit_stops_decoding_and_cancels_queued(self, tmp_path):
        """A bare LIMIT satisfied by the first chunks must not decode the
        rest of the dataset, and closing the pipeline must CANCEL queued
        decode futures (not drain them)."""
        import hyperspace_tpu.exec.io as hio

        data = _write_files(str(tmp_path / "data"), num_files=10, rows_per=500)
        sess = _mk_session(tmp_path, **{hst.keys.EXEC_PIPELINE_DEPTH: 10})
        df = sess.read_parquet(data)

        calls = []
        lock = threading.Lock()
        real = hio.read_parquet_batch

        def slow(files, columns, **kw):
            with lock:
                calls.append(list(files))
            time.sleep(0.15)  # keep later futures queued behind the pool
            return real(files, columns, **kw)

        cancelled = REGISTRY.counter("hs_pipeline_cancelled_total", "")
        c0 = cancelled.value
        orig = hio.read_parquet_batch
        hio.read_parquet_batch = slow
        try:
            with trace.recording() as events:
                chunks = list(df.limit(700).to_local_iterator())
        finally:
            hio.read_parquet_batch = orig
        assert ("limit", "early-stop-stream") in events
        assert sum(len(b["k"]) for b in chunks) == 700
        # 2 files satisfy the limit; the 4-wide pool may start a few more,
        # but the tail must never decode
        assert len(calls) < 10
        assert cancelled.value > c0

    def test_streamed_limit_rows_match_materialized_prefix(self, tmp_path):
        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(tmp_path)
        df = sess.read_parquet(data)
        chunks = list(df.limit(1500).to_local_iterator())
        got = {c: np.concatenate([np.asarray(b[c]) for b in chunks]) for c in chunks[0]}
        raw = df.collect()
        for c in raw:
            np.testing.assert_array_equal(
                np.asarray(got[c]), np.asarray(raw[c])[:1500], err_msg=c
            )
        # collect() of the same plan agrees
        coll = df.limit(1500).collect()
        for c in raw:
            np.testing.assert_array_equal(np.asarray(coll[c]), np.asarray(got[c]), err_msg=c)


class TestGates:
    def test_disabled_falls_back_to_host_sort(self, tmp_path):
        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(tmp_path, **{hst.keys.EXEC_TOPK_ENABLED: False})
        df = sess.read_parquet(data)
        with trace.recording() as events:
            got = df.order_by("k").limit(9).collect()
        assert not any(kk == "topk" for kk, _ in events)
        raw = df.collect()
        _assert_batch_equals_frame(got, _oracle(raw, ("k",), (True,), 9))

    def test_limit_above_max_k_falls_back(self, tmp_path):
        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(tmp_path, **{hst.keys.EXEC_TOPK_MAX_K: 8})
        df = sess.read_parquet(data)
        with trace.recording() as events:
            got = df.order_by("k").limit(50).collect()
        assert ("topk", "device-topk-stream") not in events
        raw = df.collect()
        _assert_batch_equals_frame(got, _oracle(raw, ("k",), (True,), 50))


class TestServingBatcherTopk:
    def test_shared_scan_applies_topk_cap(self, session, tmp_path):
        from hyperspace_tpu.serving.batcher import execute_shared_scan, shared_scan_ops

        rng = np.random.default_rng(5)
        n = 2000
        pq.write_table(
            pa.table(
                {
                    "k": rng.integers(0, 500, n).astype(np.int64),
                    "v": rng.standard_normal(n),
                }
            ),
            tmp_path / "t.parquet",
        )
        session.read_parquet(str(tmp_path / "t.parquet")).create_or_replace_temp_view("t")
        sql = "SELECT k, v FROM t WHERE k > {lo} ORDER BY k, v LIMIT 20"
        template = session.sql(sql.format(lo=100)).plan
        got = shared_scan_ops(template)
        assert got is not None
        ops, leaf = got
        assert ops and ops[0][0] == "topk"
        bound = [session.sql(sql.format(lo=lo)).plan for lo in (100, 5, 400)]
        batches = execute_shared_scan(session, ops, leaf, bound)
        for lo, gotb in zip((100, 5, 400), batches):
            want = session.sql(sql.format(lo=lo)).collect()
            for c in want:
                np.testing.assert_array_equal(
                    np.asarray(gotb[c]), np.asarray(want[c]), err_msg=f"{lo}:{c}"
                )
