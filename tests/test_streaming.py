"""Out-of-core / streaming execution (round-5: the SF100 memory-wall work).

The reference runs at any scale because Spark's executors stream
(ref: HS/index/covering/JoinIndexRule.scala:604-705 works unchanged at
SF100); this framework owns its execution layer, so boundedness is a
property these tests pin explicitly:

- the covering-index BUILD decodes source files in ~batchRows groups and
  never materializes the full table (indexes/covering.py write());
- the bucketed JOIN streams bucket-by-bucket (exec/device.py);
- scan->filter->aggregate streams file chunks with partial-agg merge;
- the generic join spills to disk partitions above a byte threshold.
"""

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst


def _write_files(d, num_files=6, rows_per=1000, seed=7):
    os.makedirs(d, exist_ok=True)
    rng = np.random.default_rng(seed)
    for i in range(num_files):
        t = pa.table(
            {
                "k": rng.integers(0, 500, rows_per).astype(np.int64),
                "v": np.round(rng.uniform(0, 100, rows_per), 3),
                "name": np.array([f"row_{i}_{j % 37}" for j in range(rows_per)]),
            }
        )
        pq.write_table(t, os.path.join(d, f"part-{i:05d}.parquet"))
    return d


def _mk_session(tmp_path, **conf):
    base = {
        hst.keys.SYSTEM_PATH: str(tmp_path / "indexes"),
        hst.keys.NUM_BUCKETS: 8,
    }
    base.update(conf)
    sess = hst.Session(conf=base)
    hst.set_session(sess)
    return sess


class TestStreamingBuild:
    def test_grouped_build_matches_one_shot(self, tmp_path):
        """A build chunked to ~1.5 files per group must index the same rows
        (same per-bucket multiset, same query answers) as a one-shot build."""
        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(tmp_path, **{hst.keys.TPU_BUILD_BATCH_ROWS: 1500})
        hs = hst.Hyperspace(sess)
        df = sess.read_parquet(data)
        hs.create_index(df, hst.CoveringIndexConfig("s_idx", ["k"], ["v", "name"]))

        sess2 = hst.Session(
            conf={
                hst.keys.SYSTEM_PATH: str(tmp_path / "indexes2"),
                hst.keys.NUM_BUCKETS: 8,
                hst.keys.TPU_BUILD_BATCH_ROWS: 10_000_000,
            }
        )
        hst.set_session(sess2)
        hs2 = hst.Hyperspace(sess2)
        df2 = sess2.read_parquet(data)
        hs2.create_index(df2, hst.CoveringIndexConfig("s_idx", ["k"], ["v", "name"]))

        def bucket_rows(sysdir):
            from hyperspace_tpu.indexes.covering import bucket_of_file

            out = {}
            for root, _, files in os.walk(sysdir):
                for f in files:
                    if not f.endswith(".parquet"):
                        continue
                    b = bucket_of_file(os.path.join(root, f))
                    if b is None:
                        continue
                    t = pq.read_table(os.path.join(root, f))
                    out.setdefault(b, []).append(t)
            return {
                b: sorted(
                    zip(
                        *[
                            pa.concat_tables(ts).column(c).to_pylist()
                            for c in ("k", "v", "name")
                        ]
                    )
                )
                for b, ts in out.items()
            }

        chunked = bucket_rows(str(tmp_path / "indexes"))
        oneshot = bucket_rows(str(tmp_path / "indexes2"))
        assert set(chunked) == set(oneshot)
        for b in oneshot:
            assert chunked[b] == oneshot[b]

    def test_build_never_decodes_all_files_at_once(self, tmp_path):
        """Bounded-memory proxy: with batchRows below the table size, no
        single arrow_dataset() call during the build covers every file."""
        from hyperspace_tpu.sources.default import DefaultFileBasedRelation

        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(tmp_path, **{hst.keys.TPU_BUILD_BATCH_ROWS: 1500})
        hs = hst.Hyperspace(sess)
        df = sess.read_parquet(data)

        decodes = []  # files covered by each actual to_table() decode
        orig = DefaultFileBasedRelation.arrow_dataset

        class _DSProxy:
            def __init__(self, ds, nfiles):
                self._ds, self._nfiles = ds, nfiles

            def to_table(self, columns=None):
                decodes.append(self._nfiles)
                return self._ds.to_table(columns=columns)

            def __getattr__(self, a):
                return getattr(self._ds, a)

        def spy(self, files=None):
            return _DSProxy(orig(self, files), len(files) if files is not None else 6)

        DefaultFileBasedRelation.arrow_dataset = spy
        try:
            hs.create_index(df, hst.CoveringIndexConfig("b_idx", ["k"], ["v"]))
        finally:
            DefaultFileBasedRelation.arrow_dataset = orig
        assert decodes, "build never decoded the relation"
        assert max(decodes) < 6, f"a single decode covered all files: {decodes}"

    def test_schema_drift_across_files(self, tmp_path):
        """Per-file streaming reads must conform to the unified schema the
        one-shot dataset scan applied implicitly: older files with a
        narrower dtype (int32 vs int64) or a missing payload column still
        build one consistent index."""
        d = str(tmp_path / "data")
        os.makedirs(d)
        # the relation's unified schema resolves from the leading file, so
        # the evolved (wider) file sorts first; the trailing file predates
        # column v and stores k narrower (int32)
        new = pa.table(
            {
                "k": pa.array([2, 3, 4], type=pa.int64()),
                "v": pa.array([1.5, 2.5, 3.5]),
            }
        )
        pq.write_table(new, os.path.join(d, "part-00000.parquet"))
        old = pa.table({"k": pa.array([1, 2, 3], type=pa.int32())})
        pq.write_table(old, os.path.join(d, "part-00001.parquet"))
        sess = _mk_session(tmp_path, **{hst.keys.TPU_BUILD_BATCH_ROWS: 2})
        hs = hst.Hyperspace(sess)
        df = sess.read_parquet(d)
        hs.create_index(df, hst.CoveringIndexConfig("drift_idx", ["k"], ["v"]))
        sess.enable_hyperspace()
        q = df.filter(hst.col("k") == 2).select("v")
        assert "IndexScan" in q.optimized_plan().pretty()
        got = q.collect()["v"]
        # k==2 appears in both files: one NULL v (old file), one 1.5
        assert sorted(x for x in got if x == x) == [1.5]
        assert sum(1 for x in got if x != x) == 1

    def test_indexed_query_after_streaming_build(self, tmp_path):
        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(tmp_path, **{hst.keys.TPU_BUILD_BATCH_ROWS: 1100})
        hs = hst.Hyperspace(sess)
        df = sess.read_parquet(data)
        hs.create_index(df, hst.CoveringIndexConfig("q_idx", ["k"], ["v"]))
        sess.enable_hyperspace()
        q = df.filter(hst.col("k") == 123).select("v")
        assert "IndexScan" in q.optimized_plan().pretty()
        got = np.sort(q.collect()["v"])
        sess.disable_hyperspace()
        want = np.sort(q.collect()["v"])
        np.testing.assert_allclose(got, want)


def _join_fixture(tmp_path, how_many_left=4000, seed=11, skew_side=False):
    """Two parquet dirs with overlapping int keys; the right side's keys are
    restricted to a sub-range so some buckets are one-sided (exercising the
    streaming join's dtype hints on absent-side buckets)."""
    rng = np.random.default_rng(seed)
    ld = str(tmp_path / "left")
    rd = str(tmp_path / "right")
    os.makedirs(ld), os.makedirs(rd)
    for i in range(4):
        t = pa.table(
            {
                "lk": rng.integers(0, 400, how_many_left // 4).astype(np.int64),
                "lv": np.round(rng.uniform(0, 10, how_many_left // 4), 3),
                "ls": np.array([f"L{j % 13}" for j in range(how_many_left // 4)]),
            }
        )
        pq.write_table(t, os.path.join(ld, f"part-{i:05d}.parquet"))
    for i in range(2):
        hi = 60 if skew_side else 400  # narrow key range -> one-sided buckets
        t = pa.table(
            {
                "rk": rng.integers(0, hi, 900).astype(np.int64),
                "rv": np.round(rng.uniform(0, 5, 900), 3),
            }
        )
        pq.write_table(t, os.path.join(rd, f"part-{i:05d}.parquet"))
    return ld, rd


def _sorted_rows(batch):
    cols = sorted(batch)
    return sorted(
        zip(*[["\0N" if v != v else v for v in batch[c].tolist()] for c in cols])
    ), cols


class TestStreamingJoin:
    @pytest.mark.parametrize("how", ["inner", "left", "outer"])
    def test_streamed_equals_materialized(self, tmp_path, how):
        ld, rd = _join_fixture(tmp_path, skew_side=(how != "inner"))
        sess = _mk_session(tmp_path)
        hs = hst.Hyperspace(sess)
        left = sess.read_parquet(ld)
        right = sess.read_parquet(rd)
        hs.create_index(left, hst.CoveringIndexConfig("l_idx", ["lk"], ["lv", "ls"]))
        hs.create_index(right, hst.CoveringIndexConfig("r_idx", ["rk"], ["rv"]))
        sess.enable_hyperspace()
        q = left.join(right, on=hst.col("lk") == hst.col("rk"), how=how).select(
            "lk", "lv", "ls", "rv"
        )
        want = q.collect()
        from hyperspace_tpu.exec import trace

        sess.conf.set(hst.keys.EXEC_STREAM_JOIN_MIN_BYTES, 1)
        with trace.recording() as rec:
            got = q.collect()
        assert any("stream" in v for _, v in rec), rec
        grows, gcols = _sorted_rows(got)
        wrows, wcols = _sorted_rows(want)
        assert gcols == wcols
        assert grows == wrows

    def test_streamed_join_bounded_reads(self, tmp_path):
        """Memory-bound proxy: while streaming, no single parquet read spans
        more than one bucket's files of one side."""
        ld, rd = _join_fixture(tmp_path)
        sess = _mk_session(tmp_path)
        hs = hst.Hyperspace(sess)
        left = sess.read_parquet(ld)
        right = sess.read_parquet(rd)
        hs.create_index(left, hst.CoveringIndexConfig("lb_idx", ["lk"], ["lv"]))
        hs.create_index(right, hst.CoveringIndexConfig("rb_idx", ["rk"], ["rv"]))
        sess.enable_hyperspace()
        sess.conf.set(hst.keys.EXEC_STREAM_JOIN_MIN_BYTES, 1)
        q = left.join(right, on=hst.col("lk") == hst.col("rk")).select("lv", "rv")

        import hyperspace_tpu.exec.io as io_mod
        from hyperspace_tpu.indexes.covering import bucket_of_file

        spans = []
        orig = io_mod.read_parquet_batch

        def spy(files, columns=None, **kw):
            spans.append({bucket_of_file(f) for f in files})
            return orig(files, columns, **kw)

        io_mod.read_parquet_batch = spy
        try:
            q.collect()
        finally:
            io_mod.read_parquet_batch = orig
        multi = [s for s in spans if len(s - {None}) > 1]
        assert not multi, f"a read spanned several buckets: {multi}"


class TestStreamingAggregate:
    def _fixture(self, tmp_path, with_nulls=True):
        d = str(tmp_path / "agg")
        os.makedirs(d, exist_ok=True)
        rng = np.random.default_rng(3)
        for i in range(6):
            v = rng.uniform(0, 100, 800)
            if with_nulls:
                v[rng.integers(0, 800, 60)] = np.nan
            t = pa.table(
                {
                    "g": np.array([f"grp_{x}" for x in rng.integers(0, 7, 800)]),
                    "k": rng.integers(0, 50, 800).astype(np.int64),
                    "v": v,
                }
            )
            pq.write_table(t, os.path.join(d, f"part-{i:05d}.parquet"))
        sess = _mk_session(
            tmp_path,
            **{
                hst.keys.EXEC_STREAM_AGG_MIN_BYTES: 1,
                hst.keys.EXEC_STREAM_CHUNK_BYTES: 1,  # every file its own chunk
            },
        )
        return sess, sess.read_parquet(d)

    def _ab(self, sess, q):
        from hyperspace_tpu.exec import trace

        with trace.recording() as rec:
            got = q.collect()
        assert ("agg", "streamed-partial") in rec, trace.summarize(rec)
        sess.conf.set(hst.keys.EXEC_STREAM_AGG_MIN_BYTES, 1 << 60)
        want = q.collect()
        sess.conf.set(hst.keys.EXEC_STREAM_AGG_MIN_BYTES, 1)
        return got, want

    def test_global_aggregates(self, tmp_path):
        sess, df = self._fixture(tmp_path)
        q = df.agg(
            n=("*", "count"),
            s=("v", "sum"),
            mn=("v", "min"),
            mx=("v", "max"),
            a=("v", "avg"),
            cd=("k", "count_distinct"),
            sd=("v", "stddev_samp"),
        )
        got, want = self._ab(sess, q)
        for c in got:
            np.testing.assert_allclose(
                np.asarray(got[c], dtype=np.float64),
                np.asarray(want[c], dtype=np.float64),
                rtol=1e-9,
            )

    def test_grouped_aggregates(self, tmp_path):
        sess, df = self._fixture(tmp_path)
        q = df.group_by("g").agg(
            n=("*", "count"),
            s=("v", "sum"),
            a=("v", "avg"),
            mn=("v", "min"),
            mx=("v", "max"),
            cd=("k", "count_distinct"),
        )
        got, want = self._ab(sess, q)

        def keyed(b):
            cols = [c for c in b if c != "g"]
            return {
                g: tuple(round(float(b[c][i]), 6) for c in cols)
                for i, g in enumerate(b["g"])
            }

        assert keyed(got) == keyed(want)

    def test_filtered_grouped_sum_with_all_null_group(self, tmp_path):
        d = str(tmp_path / "agg2")
        os.makedirs(d)
        for i in range(3):
            t = pa.table(
                {
                    "g": np.array(["a", "b", "b"]),
                    "v": np.array(
                        [np.nan, np.nan, np.nan] if i < 2 else [np.nan, 2.0, 3.0]
                    ),
                }
            )
            pq.write_table(t, os.path.join(d, f"part-{i:05d}.parquet"))
        sess = _mk_session(
            tmp_path,
            **{hst.keys.EXEC_STREAM_AGG_MIN_BYTES: 1, hst.keys.EXEC_STREAM_CHUNK_BYTES: 1},
        )
        df = sess.read_parquet(d)
        q = df.group_by("g").agg(s=("v", "sum"))
        got, want = self._ab(sess, q)
        gm = dict(zip(got["g"], got["s"]))
        wm = dict(zip(want["g"], want["s"]))
        assert set(gm) == set(wm)
        for g in gm:  # all-NULL groups must stay NULL (SQL), not 0
            assert (gm[g] != gm[g]) == (wm[g] != wm[g])
            if gm[g] == gm[g]:
                assert round(float(gm[g]), 9) == round(float(wm[g]), 9)


class TestLocalIterator:
    def test_scan_chain_streams_chunks(self, tmp_path):
        data = _write_files(str(tmp_path / "data"))
        sess = _mk_session(tmp_path, **{hst.keys.EXEC_STREAM_CHUNK_BYTES: 1})
        df = sess.read_parquet(data)
        q = df.filter(hst.col("k") < 100).select("k", "v")
        chunks = list(q.to_local_iterator())
        assert len(chunks) > 1  # one per file group
        got = np.sort(np.concatenate([c["v"] for c in chunks]))
        want = np.sort(q.collect()["v"])
        np.testing.assert_allclose(got, want)

    def test_bucketed_join_streams_per_bucket(self, tmp_path):
        ld, rd = _join_fixture(tmp_path)
        sess = _mk_session(tmp_path)
        hs = hst.Hyperspace(sess)
        left = sess.read_parquet(ld)
        right = sess.read_parquet(rd)
        hs.create_index(left, hst.CoveringIndexConfig("li_idx", ["lk"], ["lv"]))
        hs.create_index(right, hst.CoveringIndexConfig("ri_idx", ["rk"], ["rv"]))
        sess.enable_hyperspace()
        q = left.join(right, on=hst.col("lk") == hst.col("rk")).select("lv", "rv")
        chunks = list(q.to_local_iterator())
        assert len(chunks) > 1  # per participating bucket
        got = np.sort(np.concatenate([c["rv"] for c in chunks]))
        want = np.sort(q.collect()["rv"])
        np.testing.assert_allclose(got, want)


class TestValueConsistentHashing:
    """A nullable int64 parquet column decodes as float64; bucket hashing
    must be VALUE-consistent across the two representations or the bucketed
    SMJ silently drops every match whose sides disagree (found by the
    TPC-DS q48 parity ratchet: 63 vs 216)."""

    def test_host_hash_int_float_consistency(self):
        from hyperspace_tpu.ops.hashing import numeric_hash32

        ints = np.array([0, 1, 3, -7, 2**40], dtype=np.int64)
        floats = ints.astype(np.float64)
        np.testing.assert_array_equal(numeric_hash32(ints), numeric_hash32(floats))
        # -0.0 == 0.0 under SQL/pandas equality: same hash
        assert numeric_hash32(np.array([-0.0]))[0] == numeric_hash32(np.array([0.0]))[0]
        # non-integral floats keep distinct hashes from nearby ints
        assert numeric_hash32(np.array([3.5]))[0] != numeric_hash32(np.array([3.0]))[0]

    def test_device_hash_matches_host_on_floats(self):
        import jax
        from hyperspace_tpu.ops.encode import encode_sort_columns
        from hyperspace_tpu.ops.hashing import numeric_hash32
        from hyperspace_tpu.ops.sort import _device_hash32
        from hyperspace_tpu.utils.x64 import ensure_x64

        ensure_x64()
        # every branch of the bit-field arithmetic: both shift directions
        # around 2^32 / 2^52, the 2^63 integral bound, dropped fraction bits,
        # subnormals, infinities and NaN payloads
        rng = np.random.default_rng(0)
        vals = np.concatenate([
            np.array([0.0, -0.0, 1.0, 3.0, -7.0, 3.5, np.nan, -np.nan, np.inf, -np.inf,
                      2.0**31, 2.0**32 + 1, -(2.0**32), 4294967296.5, 2.0**40, 2.0**51 + 0.5,
                      2.0**52 - 0.5, 2.0**52 + 1, 2.0**53 + 2, 2.0**62, -(2.0**62), 2.0**63,
                      -(2.0**63), 2.0**64, 5e-324, -5e-324, 2.2250738585072014e-308, 1e300]),
            np.array([0x7FF0000000000001, 0xFFF8000000000001], dtype=np.uint64).view(np.float64),
            rng.integers(-(2**62), 2**62, 2000).astype(np.float64),
            rng.integers(-(10**6), 10**6, 2000) + rng.choice([0.0, 0.5, 2.0**-30], 2000),
            rng.integers(0, 2**64, 4000, dtype=np.uint64).view(np.float64),
        ])
        keys, kinds, _ = encode_sort_columns([vals])
        got = np.asarray(jax.jit(lambda k: _device_hash32("f", k))(jax.numpy.asarray(keys[0])))
        want = numeric_hash32(vals)
        np.testing.assert_array_equal(got, want)

    def test_nullable_int_key_bucketed_join_parity(self, tmp_path):
        """End-to-end q48 shape: fact side with NULLs in the join key
        (decodes float64) joined to a dense int dimension key; indexed ==
        non-indexed."""
        ld = str(tmp_path / "fact")
        rd = str(tmp_path / "dim")
        os.makedirs(ld), os.makedirs(rd)
        rng = np.random.default_rng(48)
        fk = rng.integers(0, 12, 4000).astype(np.float64)
        fk[rng.integers(0, 4000, 300)] = np.nan  # NULL FKs
        pq.write_table(
            pa.table({"fk": fk, "qty": rng.integers(1, 100, 4000).astype(np.int64)}),
            os.path.join(ld, "part-00000.parquet"),
        )
        pq.write_table(
            pa.table(
                {
                    "dk": np.arange(12, dtype=np.int64),
                    "dv": np.array([f"d{i}" for i in range(12)]),
                }
            ),
            os.path.join(rd, "part-00000.parquet"),
        )
        sess = _mk_session(tmp_path)
        hs = hst.Hyperspace(sess)
        fact = sess.read_parquet(ld)
        dim = sess.read_parquet(rd)
        hs.create_index(fact, hst.CoveringIndexConfig("f_idx", ["fk"], ["qty"]))
        hs.create_index(dim, hst.CoveringIndexConfig("d_idx", ["dk"], ["dv"]))
        sess.enable_hyperspace()
        q = fact.join(dim, on=hst.col("fk") == hst.col("dk")).select("qty", "dv")
        assert "IndexScan" in q.optimized_plan().pretty()
        on = q.collect()
        sess.disable_hyperspace()
        off = q.collect()
        assert len(on["qty"]) == len(off["qty"])
        assert sorted(zip(on["qty"], on["dv"])) == sorted(zip(off["qty"], off["dv"]))

    def test_bucket_pruning_int_literal_on_nullable_column(self, tmp_path):
        """FilterIndexRule bucket pruning: an int literal must land in the
        same bucket the (float-decoded) stored values were hashed into."""
        d = str(tmp_path / "data")
        os.makedirs(d)
        rng = np.random.default_rng(9)
        k = rng.integers(0, 50, 5000).astype(np.float64)
        k[rng.integers(0, 5000, 400)] = np.nan
        pq.write_table(
            pa.table({"k": k, "v": rng.uniform(0, 1, 5000)}),
            os.path.join(d, "part-00000.parquet"),
        )
        sess = _mk_session(
            tmp_path, **{hst.keys.FILTER_RULE_USE_BUCKET_SPEC: True}
        )
        hs = hst.Hyperspace(sess)
        df = sess.read_parquet(d)
        hs.create_index(df, hst.CoveringIndexConfig("p_idx", ["k"], ["v"]))
        sess.enable_hyperspace()
        q = df.filter(hst.col("k") == 7).select("v")
        got = np.sort(q.collect()["v"])
        sess.disable_hyperspace()
        want = np.sort(q.collect()["v"])
        assert got.shape == want.shape and len(want) > 0
        np.testing.assert_allclose(got, want)


class TestBucketHashVersioning:
    def test_stale_hash_version_untrusts_layout(self, tmp_path):
        """An index stamped with an OLDER bucket-hash version must stop
        advertising its bucket layout (no SMJ, no pruning) while still
        serving correct index scans; a full refresh re-buckets and restores
        trust. (The round-5 value-consistent hash fix is version 2; v1
        indexes' placements are untrustworthy by construction.)"""
        import glob
        import json

        ld, rd = _join_fixture(tmp_path)
        sess = _mk_session(tmp_path, **{hst.keys.FILTER_RULE_USE_BUCKET_SPEC: True})
        hs = hst.Hyperspace(sess)
        left = sess.read_parquet(ld)
        right = sess.read_parquet(rd)
        hs.create_index(left, hst.CoveringIndexConfig("vl_idx", ["lk"], ["lv"]))
        hs.create_index(right, hst.CoveringIndexConfig("vr_idx", ["rk"], ["rv"]))
        sess.enable_hyperspace()
        q = left.join(right, on=hst.col("lk") == hst.col("rk")).select("lv", "rv")
        from hyperspace_tpu.exec import trace

        with trace.recording() as r0:
            want = q.collect()
        assert any("smj" in v for k, v in r0 if k == "join"), trace.summarize(r0)

        # doctor the LEFT index's log to claim the pre-fix hash version
        logs = glob.glob(
            os.path.join(str(tmp_path / "indexes"), "vl_idx", "_hyperspace_log", "*")
        )
        for p in logs:
            with open(p) as f:
                text = f.read()
            if "bucketHashVersion" in text:
                with open(p, "w") as f:
                    f.write(text.replace('"bucketHashVersion": "2"', '"bucketHashVersion": "1"'))

        sess2 = hst.Session(
            conf={
                hst.keys.SYSTEM_PATH: str(tmp_path / "indexes"),
                hst.keys.NUM_BUCKETS: 8,
                hst.keys.FILTER_RULE_USE_BUCKET_SPEC: True,
            }
        )
        hst.set_session(sess2)
        sess2.enable_hyperspace()
        left2 = sess2.read_parquet(ld)
        right2 = sess2.read_parquet(rd)
        q2 = left2.join(right2, on=hst.col("lk") == hst.col("rk")).select("lv", "rv")
        with trace.recording() as r1:
            got = q2.collect()
        assert not any("smj" in v for k, v in r1 if k == "join"), trace.summarize(r1)
        assert sorted(zip(got["lv"], got["rv"])) == sorted(zip(want["lv"], want["rv"]))
        # bucket-pruned filters must also stop pruning (results stay right)
        qf = left2.filter(hst.col("lk") == 7).select("lv")
        with trace.recording() as r2:
            fon = np.sort(qf.collect()["lv"])
        assert not any("bucket-pruned" in v for _, v in r2), trace.summarize(r2)
        sess2.disable_hyperspace()
        np.testing.assert_allclose(fon, np.sort(qf.collect()["lv"]))
        sess2.enable_hyperspace()

        # full refresh re-buckets with the current hash: trust restored
        # (refresh refuses no-op source sets, so append one small file)
        rng = np.random.default_rng(77)
        pq.write_table(
            pa.table(
                {
                    "lk": rng.integers(0, 400, 50).astype(np.int64),
                    "lv": np.round(rng.uniform(0, 10, 50), 3),
                    "ls": np.array([f"R{j}" for j in range(50)]),
                }
            ),
            os.path.join(ld, "part-late.parquet"),
        )
        hs2 = hst.Hyperspace(sess2)
        hs2.refresh_index("vl_idx", "full")
        sess2.disable_hyperspace()
        left_w = sess2.read_parquet(ld)
        qw = left_w.join(right2, on=hst.col("lk") == hst.col("rk")).select("lv", "rv")
        want = qw.collect()
        sess2.enable_hyperspace()
        left3 = sess2.read_parquet(ld)
        q3 = left3.join(right2, on=hst.col("lk") == hst.col("rk")).select("lv", "rv")
        with trace.recording() as r3:
            got3 = q3.collect()
        assert any("smj" in v for k, v in r3 if k == "join"), trace.summarize(r3)
        assert sorted(zip(got3["lv"], got3["rv"])) == sorted(
            zip(want["lv"], want["rv"])
        )


class TestRebucketCache:
    def test_hybrid_appends_rebucket_once(self, tmp_path):
        """Hybrid scan re-buckets the appended files on the first query;
        repeats hit the cache; a NEW append invalidates (round-5 VERDICT
        item 4; ref: CoveringIndexRuleUtils.scala:357-417)."""
        ld, rd = _join_fixture(tmp_path)
        sess = _mk_session(tmp_path)
        hs = hst.Hyperspace(sess)
        left = sess.read_parquet(ld)
        right = sess.read_parquet(rd)
        hs.create_index(left, hst.CoveringIndexConfig("hl_idx", ["lk"], ["lv"]))
        hs.create_index(right, hst.CoveringIndexConfig("hr_idx", ["rk"], ["rv"]))
        # append AFTER indexing -> hybrid scan with a Repartition side
        rng = np.random.default_rng(5)
        pq.write_table(
            pa.table(
                {
                    "lk": rng.integers(0, 400, 200).astype(np.int64),
                    "lv": np.round(rng.uniform(0, 10, 200), 3),
                    "ls": np.array([f"A{j}" for j in range(200)]),
                }
            ),
            os.path.join(ld, "part-appended.parquet"),
        )
        sess.conf.set(hst.keys.HYBRID_SCAN_ENABLED, True)
        sess.conf.set(hst.keys.HYBRID_SCAN_MAX_APPENDED_RATIO, 0.9)
        sess.enable_hyperspace()
        left2 = sess.read_parquet(ld)
        q = left2.join(right, on=hst.col("lk") == hst.col("rk")).select("lv", "rv")
        from hyperspace_tpu.exec import device as D
        from hyperspace_tpu.exec import trace

        D.clear_device_cache()
        with trace.recording() as r1:
            want = q.collect()
        assert ("rebucket", "computed") in r1, trace.summarize(r1)
        with trace.recording() as r2:
            got = q.collect()
        assert ("rebucket", "cached") in r2, trace.summarize(r2)
        assert ("rebucket", "computed") not in r2
        assert sorted(zip(got["lv"], got["rv"])) == sorted(zip(want["lv"], want["rv"]))
        # a second append must invalidate
        pq.write_table(
            pa.table(
                {
                    "lk": np.array([7, 7, 7], dtype=np.int64),
                    "lv": np.array([1.0, 2.0, 3.0]),
                    "ls": np.array(["x", "y", "z"]),
                }
            ),
            os.path.join(ld, "part-appended2.parquet"),
        )
        left3 = sess.read_parquet(ld)
        q3 = left3.join(right, on=hst.col("lk") == hst.col("rk")).select("lv", "rv")
        with trace.recording() as r3:
            got3 = q3.collect()
        assert ("rebucket", "computed") in r3, trace.summarize(r3)
        sess.disable_hyperspace()
        want3 = q3.collect()
        assert sorted(zip(got3["lv"], got3["rv"])) == sorted(
            zip(want3["lv"], want3["rv"])
        )


class TestPartitionedGenericJoin:
    @pytest.mark.parametrize("how", ["inner", "left", "outer"])
    def test_matches_unpartitioned(self, tmp_path, how):
        ld, rd = _join_fixture(tmp_path, skew_side=(how == "outer"))
        sess = _mk_session(tmp_path)  # no indexes -> generic merge path
        # the broadcast hash join would claim these small sides first; this
        # test targets the partitioned generic merge specifically
        sess.conf.set(hst.keys.EXEC_JOIN_BROADCAST_MAX_BYTES, 0)
        left = sess.read_parquet(ld)
        right = sess.read_parquet(rd)
        q = left.join(right, on=hst.col("lk") == hst.col("rk"), how=how).select(
            "lk", "lv", "rv"
        )
        want = q.collect()
        from hyperspace_tpu.exec import trace

        sess.conf.set(hst.keys.EXEC_JOIN_SPILL_MIN_ROWS, 500)
        with trace.recording() as rec:
            got = q.collect()
        assert any("partitioned" in v for _, v in rec), trace.summarize(rec)
        grows, _ = _sorted_rows(got)
        wrows, _ = _sorted_rows(want)
        assert grows == wrows
