"""Aggregation over (possibly index-rewritten) plans.

The reference delegates aggregation to Spark SQL around its indexed scans;
here the dataframe facade provides group_by/agg directly, and index rewrites
apply beneath the Aggregate node untouched (ScoreBasedIndexPlanOptimizer
recurses through it — rules/score.py).
"""

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.plan import logical as L


@pytest.fixture()
def hs(session):
    return hst.Hyperspace(session)


@pytest.fixture()
def data(tmp_path):
    d = tmp_path / "agg"
    d.mkdir()
    rng = np.random.default_rng(0)
    for i in range(2):
        pq.write_table(
            pa.table(
                {
                    "dept": rng.integers(0, 8, 1500).astype(np.int64),
                    "region": np.array([f"r{v}" for v in rng.integers(0, 3, 1500)]),
                    "amount": np.round(rng.uniform(0, 100, 1500), 4),
                    "qty": rng.integers(1, 10, 1500).astype(np.int64),
                }
            ),
            d / f"p{i}.parquet",
        )
    return str(d)


def as_pandas(batch):
    return pd.DataFrame({k: v for k, v in batch.items()})


class TestAggregates:
    def test_global_aggregates(self, session, data):
        df = session.read_parquet(data)
        out = df.agg(total=("amount", "sum"), n=("*", "count"), hi=("amount", "max"))
        got = out.collect()
        ref = df.to_pandas()
        assert got["n"][0] == len(ref)
        assert np.isclose(got["total"][0], ref["amount"].sum())
        assert np.isclose(got["hi"][0], ref["amount"].max())

    def test_group_by_aggregates_match_pandas(self, session, data):
        df = session.read_parquet(data)
        out = df.group_by("dept").agg(
            total=("amount", "sum"), n=("*", "count"), avg_q=("qty", "avg")
        ).collect()
        ref = (
            df.to_pandas()
            .groupby("dept")
            .agg(total=("amount", "sum"), n=("amount", "size"), avg_q=("qty", "mean"))
            .reset_index()
            .sort_values("dept")
        )
        got = as_pandas(out).sort_values("dept").reset_index(drop=True)
        assert np.array_equal(got["dept"].to_numpy(), ref["dept"].to_numpy())
        assert np.allclose(got["total"].to_numpy(), ref["total"].to_numpy())
        assert np.array_equal(got["n"].to_numpy(), ref["n"].to_numpy())
        assert np.allclose(got["avg_q"].to_numpy(), ref["avg_q"].to_numpy())

    def test_multi_key_and_string_key_grouping(self, session, data):
        df = session.read_parquet(data)
        out = as_pandas(df.group_by("dept", "region").count().collect())
        ref = df.to_pandas().groupby(["dept", "region"]).size().reset_index(name="count")
        merged = out.merge(ref, on=["dept", "region"], suffixes=("_got", "_ref"))
        assert len(merged) == len(ref) == len(out)
        assert np.array_equal(merged["count_got"].to_numpy(), merged["count_ref"].to_numpy())

    def test_shorthand_methods(self, session, data):
        df = session.read_parquet(data)
        got = df.group_by("dept").sum("qty").collect()
        ref = df.to_pandas().groupby("dept")["qty"].sum()
        for d, v in zip(got["dept"], got["sum(qty)"]):
            assert v == ref[d]

    def test_index_rewrite_fires_below_aggregate(self, session, hs, data):
        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        df = session.read_parquet(data)
        hs.create_index(df, hst.CoveringIndexConfig("aggIdx", ["dept"], ["amount"]))
        session.enable_hyperspace()
        q = df.filter(hst.col("dept") == 3).group_by("dept").agg(total=("amount", "sum"))
        plan = q.optimized_plan()
        scans = [p for p in L.collect(plan, lambda p: True) if isinstance(p, L.IndexScan)]
        assert scans, plan.pretty()
        on = q.collect()
        session.disable_hyperspace()
        off = q.collect()
        session.enable_hyperspace()
        assert np.allclose(np.sort(on["total"]), np.sort(off["total"]))

    def test_aggregate_over_indexed_join(self, session, hs, data, tmp_path):
        rroot = tmp_path / "r"
        rroot.mkdir()
        pq.write_table(
            pa.table(
                {
                    "dept": np.arange(8, dtype=np.int64),
                    "budget": np.round(np.linspace(100, 800, 8), 2),
                }
            ),
            rroot / "p.parquet",
        )
        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        ldf = session.read_parquet(data)
        rdf = session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("aggJL", ["dept"], ["amount"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("aggJR", ["dept"], ["budget"]))
        session.enable_hyperspace()
        q = ldf.join(rdf, on=["dept"]).group_by("dept").agg(
            spend=("amount", "sum"), budget=("budget", "max")
        )
        plan = q.optimized_plan()
        scans = [p for p in L.collect(plan, lambda p: True) if isinstance(p, L.IndexScan)]
        assert len(scans) == 2, plan.pretty()
        on = as_pandas(q.collect()).sort_values("dept").reset_index(drop=True)
        session.disable_hyperspace()
        off = as_pandas(q.collect()).sort_values("dept").reset_index(drop=True)
        session.enable_hyperspace()
        assert np.allclose(on["spend"], off["spend"])
        assert np.allclose(on["budget"], off["budget"])

    def test_int64_min_join_sum_no_silent_overflow(self, session, hs, tmp_path):
        """A join-aggregate input containing int64.min must not slip past the
        fused path's overflow guard (np.abs(int64.min) wraps negative): the
        plan falls back to the exact path and the sums stay correct."""
        from hyperspace_tpu.exec.device import _int_magnitude

        lo = np.iinfo(np.int64).min
        assert _int_magnitude(np.array([lo, 5], dtype=np.int64)) == 2 ** 63
        # the old formula was negative, bypassing the guard entirely
        assert int(np.abs(np.array([lo], dtype=np.int64)).max()) < 0

        lroot, rroot = tmp_path / "l", tmp_path / "r"
        lroot.mkdir(), rroot.mkdir()
        pq.write_table(
            pa.table(
                {
                    "dept": np.array([0, 0, 1, 1], dtype=np.int64),
                    "amount": np.array([lo, 3, 7, 11], dtype=np.int64),
                }
            ),
            lroot / "p.parquet",
        )
        pq.write_table(
            pa.table(
                {
                    "dept": np.array([0, 1], dtype=np.int64),
                    "budget": np.array([10, 20], dtype=np.int64),
                }
            ),
            rroot / "p.parquet",
        )
        session.conf.set(hst.keys.NUM_BUCKETS, 2)
        ldf = session.read_parquet(str(lroot))
        rdf = session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("ovL", ["dept"], ["amount"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("ovR", ["dept"], ["budget"]))
        session.enable_hyperspace()
        got = as_pandas(
            ldf.join(rdf, on=["dept"]).group_by("dept").agg(s=("amount", "sum")).collect()
        ).sort_values("dept")
        assert got["s"].tolist() == [lo + 3, 18]

    def test_order_by_and_limit(self, session, data):
        df = session.read_parquet(data)
        out = as_pandas(
            df.group_by("dept")
            .agg(total=("amount", "sum"))
            .order_by("total", ascending=False)
            .limit(3)
            .collect()
        )
        ref = (
            df.to_pandas()
            .groupby("dept")["amount"]
            .sum()
            .sort_values(ascending=False)
            .head(3)
        )
        assert len(out) == 3
        assert np.allclose(out["total"].to_numpy(), ref.to_numpy())
        assert np.array_equal(out["dept"].to_numpy(), ref.index.to_numpy())

    def test_order_by_multi_key_mixed_direction_stable(self, session, tmp_path):
        d = tmp_path / "sortd"
        d.mkdir()
        pq.write_table(
            pa.table(
                {
                    "a": np.array([2, 1, 2, 1, 2, 1], dtype=np.int64),
                    "b": np.array(["x", "y", "x", "y", "z", "x"]),
                    "i": np.arange(6, dtype=np.int64),
                }
            ),
            d / "p.parquet",
        )
        df = session.read_parquet(str(d))
        out = as_pandas(df.order_by("a", "b", ascending=[True, False]).collect())
        ref = (
            df.to_pandas()
            .sort_values(["a", "b"], ascending=[True, False], kind="stable")
            .reset_index(drop=True)
        )
        assert np.array_equal(out["a"].to_numpy(), ref["a"].to_numpy())
        assert np.array_equal(out["b"].to_numpy().astype(str), ref["b"].to_numpy().astype(str))
        assert np.array_equal(out["i"].to_numpy(), ref["i"].to_numpy())  # stability

    def test_order_by_nan_last_both_directions(self, session, tmp_path):
        d = tmp_path / "nansort"
        d.mkdir()
        pq.write_table(
            pa.table({"x": np.array([1.0, np.nan, 3.0, np.nan, 2.0]), "i": np.arange(5, dtype=np.int64)}),
            d / "p.parquet",
        )
        df = session.read_parquet(str(d))
        asc = df.order_by("x").collect()["x"]
        desc = df.order_by("x", ascending=False).collect()["x"]
        assert np.array_equal(asc[:3], [1.0, 2.0, 3.0]) and np.isnan(asc[3:]).all()
        assert np.array_equal(desc[:3], [3.0, 2.0, 1.0]) and np.isnan(desc[3:]).all()

    def test_index_rewrite_survives_order_by_limit(self, session, hs, data):
        """order_by/limit at the plan root must not block column pruning and
        with it the covering-index rewrite underneath."""
        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        df = session.read_parquet(data)
        hs.create_index(df, hst.CoveringIndexConfig("sortIdx", ["dept"], ["amount"]))
        session.enable_hyperspace()
        q = (
            df.filter(hst.col("dept") == 3)
            .group_by("dept")
            .agg(total=("amount", "sum"))
            .order_by("total")
            .limit(1)
        )
        plan = q.optimized_plan()
        scans = [p for p in L.collect(plan, lambda p: True) if isinstance(p, L.IndexScan)]
        assert scans, plan.pretty()
        on = q.collect()
        session.disable_hyperspace()
        off = q.collect()
        session.enable_hyperspace()
        assert np.allclose(on["total"], off["total"])

    def test_invalid_fn_rejected(self, session, data):
        df = session.read_parquet(data)
        with pytest.raises(ValueError, match="Unsupported aggregate"):
            df.group_by("dept").agg(x=("amount", "median"))
        with pytest.raises(ValueError, match="only \\('\\*', 'count'\\)"):
            df.agg(total=("*", "sum"))
        with pytest.raises(ValueError, match="Duplicate aggregate output"):
            df.group_by("dept").agg(dept=("amount", "sum"))

    def test_device_fused_filter_aggregate(self, session, hs, data):
        """Global aggregates over a filtered index scan run as one fused
        device program (only scalars come back); results match the host
        path bit-for-bit on counts/int sums and to fp tolerance otherwise."""
        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        df = session.read_parquet(data)
        hs.create_index(df, hst.CoveringIndexConfig("devAgg", ["dept"], ["amount", "qty"]))
        session.enable_hyperspace()
        q = df.filter(hst.col("dept") == 3).agg(
            n=("*", "count"),
            total=("amount", "sum"),
            qsum=("qty", "sum"),
            lo=("amount", "min"),
            hi=("amount", "max"),
            mean=("amount", "avg"),
        )
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        dev = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 1 << 40)
        host = q.collect()
        assert dev["n"][0] == host["n"][0]
        assert dev["qsum"][0] == host["qsum"][0]  # int sum exact
        for k in ("total", "lo", "hi", "mean"):
            assert np.isclose(dev[k][0], host[k][0]), k

    def test_device_aggregate_with_nulls(self, session, hs, tmp_path):
        d = tmp_path / "nullagg"
        d.mkdir()
        vals = np.array([1.0, np.nan, 3.0, np.nan, 5.0] * 40)
        pq.write_table(
            pa.table({"g": np.tile(np.arange(4, dtype=np.int64), 50), "x": vals}),
            d / "p.parquet",
        )
        session.conf.set(hst.keys.NUM_BUCKETS, 2)
        df = session.read_parquet(str(d))
        hs.create_index(df, hst.CoveringIndexConfig("nullAgg", ["g"], ["x"]))
        session.enable_hyperspace()
        q = df.filter(hst.col("g") == 1).agg(
            nx=("x", "count"), total=("x", "sum"), mean=("x", "avg")
        )
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        dev = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 1 << 40)
        host = q.collect()
        assert dev["nx"][0] == host["nx"][0]  # NaNs skipped in count(col)
        assert np.isclose(dev["total"][0], host["total"][0])
        assert np.isclose(dev["mean"][0], host["mean"][0])

    def test_device_aggregate_empty_match(self, session, hs, data):
        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        df = session.read_parquet(data)
        hs.create_index(df, hst.CoveringIndexConfig("emptyAgg", ["dept"], ["amount"]))
        session.enable_hyperspace()
        q = df.filter(hst.col("dept") == 999).agg(n=("*", "count"), lo=("amount", "min"))
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        dev = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 1 << 40)
        host = q.collect()
        assert dev["n"][0] == host["n"][0] == 0
        assert np.isnan(dev["lo"][0]) and np.isnan(host["lo"][0])

    def test_device_aggregate_all_nan_match(self, session, hs, tmp_path):
        """Filter matches rows whose aggregate column is entirely NaN: the
        device path must yield NaN for min/max/avg (pandas semantics), not
        inf/-inf/0."""
        d = tmp_path / "allnan"
        d.mkdir()
        pq.write_table(
            pa.table(
                {
                    "g": np.array([1] * 10 + [2] * 10, dtype=np.int64),
                    "x": np.array([np.nan] * 10 + [5.0] * 10),
                }
            ),
            d / "p.parquet",
        )
        session.conf.set(hst.keys.NUM_BUCKETS, 2)
        df = session.read_parquet(str(d))
        hs.create_index(df, hst.CoveringIndexConfig("allNanAgg", ["g"], ["x"]))
        session.enable_hyperspace()
        q = df.filter(hst.col("g") == 1).agg(
            lo=("x", "min"), hi=("x", "max"), mean=("x", "avg"), total=("x", "sum")
        )
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        dev = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 1 << 40)
        host = q.collect()
        for k in ("lo", "hi", "mean"):
            assert np.isnan(dev[k][0]) and np.isnan(host[k][0]), k
        # SQL: SUM over zero non-null values is NULL (not pandas' 0)
        assert np.isnan(dev["total"][0]) and np.isnan(host["total"][0])

    def test_device_declines_bare_count_star(self, session, hs, data):
        """count(*) with no predicate has no device-resident columns — the
        device path declines (a zero-column program would report 0 rows) and
        the host answers from the already-read batch."""
        from hyperspace_tpu.exec import device as D
        from hyperspace_tpu.plan import logical as L

        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        df = session.read_parquet(data)
        batch = {"dept": np.arange(10, dtype=np.int64)}
        with pytest.raises(D.DeviceUnsupported):
            cols = D.ScanColumns(session, None, [], lambda: batch)
            D.device_scan_aggregate(session, cols, None, (), [], [("n", "count", None)])
        # end to end: correct count either way
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        n_dev = df.agg(n=("*", "count")).collect()["n"][0]
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 1 << 40)
        n_host = df.agg(n=("*", "count")).collect()["n"][0]
        assert n_dev == n_host == 3000

    def test_group_by_nested_key(self, session, tmp_path):
        d = tmp_path / "nestedagg"
        d.mkdir()
        t = pa.table(
            {
                "nested": pa.array([{"city": f"c{i % 3}"} for i in range(60)]),
                "v": np.arange(60, dtype=np.int64),
            }
        )
        pq.write_table(t, d / "p.parquet")
        df = session.read_parquet(str(d))
        out = as_pandas(df.group_by("nested.city").sum("v").collect())
        assert len(out) == 3
        assert out["sum(v)"].sum() == np.arange(60).sum()
