"""End-to-end query-rewrite tests
(ref: src/test/scala/.../index/E2EHyperspaceRulesTest.scala:75-1016).

Verification pattern mirrors the reference's ``verifyIndexUsage``: check which
files the rewritten plan scans (index files vs source files), and that query
results are identical with Hyperspace on vs off.
"""

import numpy as np
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.plan import logical as L


def sort_batch(batch):
    order = np.lexsort([np.asarray(v).astype("U64") if v.dtype == object else v for v in reversed(list(batch.values()))])
    return {k: v[order] for k, v in batch.items()}


def assert_batches_equal(a, b):
    assert sorted(a.keys()) == sorted(b.keys())
    a, b = sort_batch(a), sort_batch(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=f"column {k}")


def scanned_files(plan):
    files = []
    for node in L.collect(plan, lambda p: True):
        if isinstance(node, L.IndexScan):
            files.extend(node.files)
        elif isinstance(node, L.FileScan):
            files.extend(node.files)
        elif isinstance(node, L.Scan):
            files.extend(fi.name for fi in node.relation.all_file_infos())
    return files


@pytest.fixture()
def hs(session):
    return hst.Hyperspace(session)


class TestFilterIndexRule:
    def test_filter_query_uses_index(self, session, hs, sample_parquet):
        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("filterIdx", ["c1"], ["c2"]))

        query = df.filter(hst.col("c1") == 7).select("c2")
        baseline = query.collect()

        session.enable_hyperspace()
        plan = query.optimized_plan()
        assert any(isinstance(p, L.IndexScan) for p in L.collect(plan, lambda p: True)), plan.pretty()
        # every scanned file is index data, not source data
        entry = hs._manager.get_index("filterIdx")
        index_files = set(entry.content.files)
        assert set(scanned_files(plan)) <= index_files
        assert_batches_equal(query.collect(), baseline)

    def test_case_insensitive_columns(self, session, hs, sample_parquet):
        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("ciIdx", ["C1"], ["C2"]))
        session.enable_hyperspace()
        query = df.filter(hst.col("c1") == 7).select("c2")
        plan = query.optimized_plan()
        assert any(isinstance(p, L.IndexScan) for p in L.collect(plan, lambda p: True))

    def test_no_index_when_column_not_covered(self, session, hs, sample_parquet):
        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("smallIdx", ["c1"], ["c2"]))
        session.enable_hyperspace()
        # query needs c3, which the index does not include
        query = df.filter(hst.col("c1") == 7).select("c3")
        plan = query.optimized_plan()
        assert not any(isinstance(p, L.IndexScan) for p in L.collect(plan, lambda p: True))

    def test_disable_hyperspace_no_rewrite(self, session, hs, sample_parquet):
        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("offIdx", ["c1"], ["c2"]))
        session.disable_hyperspace()
        plan = df.filter(hst.col("c1") == 7).select("c2").optimized_plan()
        assert not any(isinstance(p, L.IndexScan) for p in L.collect(plan, lambda p: True))

    def test_bucket_pruning_reads_fewer_files(self, session, hs, sample_parquet):
        session.conf.set(hst.keys.FILTER_RULE_USE_BUCKET_SPEC, True)
        session.conf.set(hst.keys.NUM_BUCKETS, 8)
        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("bpIdx", ["c1"], ["c2"]))
        session.enable_hyperspace()
        query = df.filter(hst.col("c1") == 7).select("c2")
        plan = query.optimized_plan()
        scans = [p for p in L.collect(plan, lambda p: True) if isinstance(p, L.IndexScan)]
        assert scans and scans[0].pruned_buckets is not None
        assert len(scans[0].pruned_buckets) == 1
        entry = hs._manager.get_index("bpIdx")
        assert len(scans[0].files) < len(entry.content.files)
        baseline_session_result = df.filter(hst.col("c1") == 7).select("c2")
        session.disable_hyperspace()
        assert_batches_equal(query.collect(), baseline_session_result.collect())
        session.enable_hyperspace()
        assert_batches_equal(query.collect(), baseline_session_result.collect())


class TestJoinIndexRule:
    def test_join_query_uses_both_indexes(self, session, hs, sample_parquet, tmp_path):
        import pyarrow as pa
        import pyarrow.parquet as pq

        # build a second table keyed by c1
        rng = np.random.default_rng(7)
        dim = pa.table(
            {
                "c1": np.arange(100, dtype=np.int64),
                "v": rng.standard_normal(100),
            }
        )
        dim_root = tmp_path / "dim"
        dim_root.mkdir()
        pq.write_table(dim, dim_root / "part-00000.parquet")

        fact = session.read_parquet(sample_parquet)
        dim_df = session.read_parquet(str(dim_root))
        hs.create_index(fact, hst.CoveringIndexConfig("factIdx", ["c1"], ["c2"]))
        hs.create_index(dim_df, hst.CoveringIndexConfig("dimIdx", ["c1"], ["v"]))

        query = fact.select("c1", "c2").join(dim_df.select("c1", "v"), on="c1")
        baseline = query.collect()

        session.enable_hyperspace()
        plan = query.optimized_plan()
        index_scans = [p for p in L.collect(plan, lambda p: True) if isinstance(p, L.IndexScan)]
        assert len(index_scans) == 2, plan.pretty()
        assert {s.entry.name for s in index_scans} == {"factIdx", "dimIdx"}
        # both sides share the bucket layout -> shuffle-free join
        assert index_scans[0].bucket_spec is not None
        assert index_scans[0].bucket_spec.num_buckets == index_scans[1].bucket_spec.num_buckets
        assert_batches_equal(query.collect(), baseline)

    def test_join_not_applied_without_matching_index(self, session, hs, sample_parquet, tmp_path):
        import pyarrow as pa
        import pyarrow.parquet as pq

        dim = pa.table({"c1": np.arange(100, dtype=np.int64), "v": np.arange(100, dtype=np.float64)})
        dim_root = tmp_path / "dim2"
        dim_root.mkdir()
        pq.write_table(dim, dim_root / "part-00000.parquet")

        fact = session.read_parquet(sample_parquet)
        dim_df = session.read_parquet(str(dim_root))
        hs.create_index(fact, hst.CoveringIndexConfig("factOnly", ["c1"], ["c2"]))
        session.enable_hyperspace()
        plan = fact.select("c1", "c2").join(dim_df.select("c1", "v"), on="c1").optimized_plan()
        index_scans = [p for p in L.collect(plan, lambda p: True) if isinstance(p, L.IndexScan)]
        # join rule can't pair; filter rule doesn't match (no filter); no rewrite of join sides
        assert len(index_scans) == 0


class TestIndexManagement:
    def test_lifecycle(self, session, hs, sample_parquet):
        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("lcIdx", ["c1"], ["c2"]))
        assert hs._manager.get_index("lcIdx").state == "ACTIVE"

        hs.delete_index("lcIdx")
        assert hs._manager.get_index("lcIdx").state == "DELETED"

        hs.restore_index("lcIdx")
        assert hs._manager.get_index("lcIdx").state == "ACTIVE"

        hs.delete_index("lcIdx")
        hs.vacuum_index("lcIdx")
        assert hs._manager.get_index("lcIdx").state == "DOESNOTEXIST"

        # after vacuum, the name is reusable
        hs.create_index(df, hst.CoveringIndexConfig("lcIdx", ["c1"], ["c2"]))
        assert hs._manager.get_index("lcIdx").state == "ACTIVE"

    def test_create_duplicate_fails(self, session, hs, sample_parquet):
        from hyperspace_tpu.actions.base import HyperspaceActionException

        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("dupIdx", ["c1"], ["c2"]))
        with pytest.raises(HyperspaceActionException):
            hs.create_index(df, hst.CoveringIndexConfig("dupIdx", ["c1"], ["c2"]))

    def test_deleted_index_not_applied(self, session, hs, sample_parquet):
        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("delIdx", ["c1"], ["c2"]))
        hs.delete_index("delIdx")
        session.enable_hyperspace()
        plan = df.filter(hst.col("c1") == 7).select("c2").optimized_plan()
        assert not any(isinstance(p, L.IndexScan) for p in L.collect(plan, lambda p: True))

    def test_indexes_listing(self, session, hs, sample_parquet):
        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("idxA", ["c1"], ["c2"]))
        hs.create_index(df, hst.CoveringIndexConfig("idxB", ["c2"], ["c3"]))
        listing = hs.indexes()
        assert set(listing["name"]) == {"idxA", "idxB"}
        assert set(listing["state"]) == {"ACTIVE"}

    def test_index_stats_extended(self, session, hs, sample_parquet):
        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("statIdx", ["c1"], ["c2"]))
        stats = hs.index("statIdx")
        assert stats["numIndexFiles"] > 0
        assert stats["sizeInBytes"] > 0


class TestCoveringIndexData:
    def test_index_rows_match_source(self, session, hs, sample_parquet):
        """Row parity vs host oracle (the pandas/duckdb-oracle pattern from
        SURVEY.md §7 stage 4)."""
        import pyarrow.dataset as pads

        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("parityIdx", ["c1"], ["c2"]))
        entry = hs._manager.get_index("parityIdx")
        index_table = pads.dataset(entry.content.files, format="parquet").to_table()
        source = pads.dataset(
            [fi.name for fi in df.plan.relation.all_file_infos()], format="parquet"
        ).to_table(columns=["c1", "c2"])
        assert index_table.num_rows == source.num_rows
        a = np.sort(index_table.column("c1").to_numpy(), kind="stable")
        b = np.sort(source.column("c1").to_numpy(), kind="stable")
        np.testing.assert_array_equal(a, b)

    def test_buckets_are_sorted_and_hash_consistent(self, session, hs, sample_parquet):
        import pyarrow.parquet as pq

        from hyperspace_tpu.indexes.covering import bucket_of_file
        from hyperspace_tpu.ops.hashing import bucket_of_key_literal

        session.conf.set(hst.keys.NUM_BUCKETS, 8)
        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("sortedIdx", ["c1"], ["c2"]))
        entry = hs._manager.get_index("sortedIdx")
        for f in entry.content.files:
            b = bucket_of_file(f)
            assert b is not None and 0 <= b < 8
            vals = pq.read_table(f).column("c1").to_numpy()
            assert np.all(np.diff(vals) >= 0), f"bucket {b} not sorted"
            for v in np.unique(vals):
                assert bucket_of_key_literal(v, "num", 8) == b


class TestColumnPruning:
    """Column pruning pushes required columns to the scans so the join rule
    sees minimal per-side requirements (Catalyst's ColumnPruning runs before
    the reference's rules; ref: JoinIndexRule.scala:419-448)."""

    def test_self_join_over_wide_table_uses_index(self, session, hs, sample_parquet):
        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("wideJoinIdx", ["c2"], ["c1"]))
        q = df.join(df, on=["c2"]).select("c1")
        baseline = q.collect()
        session.enable_hyperspace()
        plan = q.optimized_plan()
        assert any(isinstance(p, L.IndexScan) for p in L.collect(plan, lambda p: True)), plan.pretty()
        assert_batches_equal(q.collect(), baseline)

    def test_right_side_duplicate_column_survives_pruning(self, session, hs, sample_parquet):
        """Selecting a '#r'-renamed right-side column must keep working when
        pruning drops the other side's duplicate."""
        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("dupJoinIdx", ["c1"], ["c3"]))
        q = df.join(df, on=["c1"]).select("c3#r")
        baseline = q.collect()
        session.enable_hyperspace()
        assert_batches_equal(q.collect(), baseline)

    def test_filter_over_computed_column_still_rewrites_interior(self, session, hs, sample_parquet):
        """A filter over a computed column pins the chain top (it cannot move
        below the Compute), but the interior Filter->Scan must still rewrite
        to the index — the optimizer's chain-top shortcut must not skip it."""
        from hyperspace_tpu.plan import logical as L
        from hyperspace_tpu.plan.dataframe import DataFrame

        hs.create_index(
            session.read_parquet(sample_parquet),
            hst.CoveringIndexConfig("computedIdx", ["c1"], ["c2", "c3", "c4"]),
        )
        session.enable_hyperspace()
        df = session.read_parquet(sample_parquet).filter(hst.col("c1") == 7)
        computed = DataFrame(
            L.Compute([("dbl", hst.col("c2") * 2)], df.plan), session
        ).filter(hst.col("dbl") > 100).select("dbl")
        plan = computed.optimized_plan()
        assert any(
            isinstance(p, L.IndexScan) for p in L.collect(plan, lambda x: True)
        ), plan.pretty()
        on = np.sort(computed.collect()["dbl"])
        session.disable_hyperspace()
        off = np.sort(computed.collect()["dbl"])
        assert np.array_equal(on, off)

    def test_no_rewrite_returns_untouched_plan(self, session, hs, sample_parquet):
        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("unusedIdx", ["c1"], ["c2"]))
        session.enable_hyperspace()
        q = df.filter(hst.col("c3") > 100.0)
        text = hs.explain(q, mode="console")
        assert "<----" not in text  # no spurious plan diff when nothing applied


def test_pushed_conjunct_keeps_single_row_cross_join(session, tmp_path):
    """A single-row derived table that gets a WHERE conjunct pushed onto it
    (wrapping it in Filter) must still cross-join via the single-row path
    (code-review regression: _is_single_row must unwrap Filter)."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    root = tmp_path / "t1"
    root.mkdir()
    pq.write_table(
        pa.table({"k": np.arange(10, dtype=np.int64), "x": np.arange(10, dtype=np.int64) * 2}),
        root / "p.parquet",
    )
    session.read_parquet(str(root)).create_or_replace_temp_view("tt")
    got = session.sql(
        "SELECT k FROM tt, (SELECT max(x) AS m FROM tt) s WHERE s.m > 0 AND tt.k < s.m"
    ).collect()
    assert sorted(got["k"].tolist()) == list(range(10))
