"""Device execution path tests on the virtual 8-device CPU mesh.

The device path must agree bit-for-bit with the host executor on every
supported pattern, and silently fall back for anything else — the same
"never break a query" contract as ApplyHyperspace
(ref: HS/index/rules/ApplyHyperspace.scala:59-63).
"""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.exec import batch as B
from hyperspace_tpu.exec import device as D
from hyperspace_tpu.exec.file_identity import scan_identity
from hyperspace_tpu.plan import logical as L
from hyperspace_tpu.plan.expr import col, lit


def sort_batch(batch):
    order = np.lexsort(
        [np.asarray(v).astype("U64") if v.dtype == object else v for v in reversed(list(batch.values()))]
    )
    return {k: v[order] for k, v in batch.items()}


def assert_batches_equal(a, b):
    assert sorted(a.keys()) == sorted(b.keys())
    assert B.num_rows(a) == B.num_rows(b)
    a, b = sort_batch(a), sort_batch(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=f"column {k}")


@pytest.fixture()
def hs(session):
    return hst.Hyperspace(session)


def run_both(session, query):
    """Collect with device execution on and off; both must agree."""
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
    # force the device path even on tiny test batches (the row threshold
    # exists for latency, not correctness)
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
    dev = query.collect()
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
    host = query.collect()
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
    assert_batches_equal(dev, host)
    return dev


class TestDeviceFilter:
    def test_numeric_predicates(self, session, hs, sample_parquet):
        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("devIdx", ["c1"], ["c2", "c3"]))
        session.enable_hyperspace()
        for cond in [
            col("c1") == 7,
            (col("c1") > 20) & (col("c1") <= 60),
            (col("c1") == 3) | (col("c2") < 100),
            col("c1").isin(1, 5, 9),
            ~(col("c1") == 7),
            (col("c1") + col("c2")) % 7 == 0,
        ]:
            q = df.filter(cond).select("c2")
            out = run_both(session, q)
            assert B.num_rows(out) > 0

    def test_string_predicates(self, session, hs, sample_parquet):
        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("strIdx", ["c4"], ["c1"]))
        session.enable_hyperspace()
        for cond in [
            col("c4") == "name_5",
            col("c4") < "name_2",
            col("c4") >= "name_30",
            col("c4").isin("name_1", "name_36", "does_not_exist"),
            col("c4") != "name_0",
        ]:
            q = df.filter(cond).select("c1")
            run_both(session, q)

    def test_absent_string_literal_matches_nothing(self, session, hs, sample_parquet):
        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("strIdx2", ["c4"], ["c1"]))
        session.enable_hyperspace()
        q = df.filter(col("c4") == "zzz_not_there").select("c1")
        out = run_both(session, q)
        assert B.num_rows(out) == 0

    def test_mixed_type_predicates_fall_back_to_host(self, session, hs, sample_parquet):
        # string column vs int literal, and mixed-type IN: host-defined
        # semantics — device path must decline (not crash, not diverge)
        df = session.read_parquet(sample_parquet)
        hs.create_index(df, hst.CoveringIndexConfig("mixIdx", ["c4"], ["c1"]))
        session.enable_hyperspace()
        codecs = {"c4": D.ColumnCodec("string", uniques=np.array(["a"])), "c1": D.ColumnCodec("numeric")}
        with pytest.raises(D.DeviceUnsupported):
            D.compile_predicate(col("c4") == lit(5), codecs)
        with pytest.raises(D.DeviceUnsupported):
            D.compile_predicate(col("c4").isin("a", 5), codecs)
        with pytest.raises(D.DeviceUnsupported):
            D.compile_predicate(col("c1").isin("a", 5), codecs)
        # end-to-end: query still succeeds via host fallback
        q = df.filter(col("c4").isin("name_1", 5)).select("c1")
        run_both(session, q)

    def test_string_ne_with_nulls_matches_host(self, session, hs, tmp_path):
        root = tmp_path / "nulls"
        root.mkdir()
        pq.write_table(
            pa.table({"s": pa.array(["a", None, "b", "a"], type=pa.string()), "v": np.arange(4, dtype=np.int64)}),
            root / "p.parquet",
        )
        df = session.read_parquet(str(root))
        hs = hst.Hyperspace(session)
        hs.create_index(df, hst.CoveringIndexConfig("nullIdx", ["s"], ["v"]))
        session.enable_hyperspace()
        q = df.filter(col("s") != "a").select("v")
        out = run_both(session, q)
        # SQL three-valued semantics: NULL != 'a' is NULL (unknown), so the
        # null row is filtered out on device and host alike
        assert set(out["v"].tolist()) == {2}
        # and NOT must not resurrect it: NOT(s = 'a') is NULL for the null row
        q2 = df.filter(~(col("s") == "a")).select("v")
        out2 = run_both(session, q2)
        assert set(out2["v"].tolist()) == {2}

    def test_nat_dates_three_valued_on_device(self, session, hs, tmp_path):
        """NaT (NULL date) comparisons are unknown on device exactly as on
        host: != and NOT(=) must not keep the NaT row, IS NULL must find it."""
        root = tmp_path / "nat"
        root.mkdir()
        days = np.array(["2024-01-01", "NaT", "2024-03-01"], dtype="datetime64[D]")
        pq.write_table(
            pa.table({"d": days, "v": np.arange(3, dtype=np.int64)}),
            root / "p.parquet",
        )
        df = session.read_parquet(str(root))
        hs = hst.Hyperspace(session)
        hs.create_index(df, hst.CoveringIndexConfig("natIdx", ["d"], ["v"]))
        session.enable_hyperspace()
        q = df.filter(col("d") != np.datetime64("2024-01-01")).select("v")
        out = run_both(session, q)
        assert set(out["v"].tolist()) == {2}
        q2 = df.filter(~(col("d") == np.datetime64("2024-01-01"))).select("v")
        out2 = run_both(session, q2)
        assert set(out2["v"].tolist()) == {2}
        q3 = df.filter(col("d").is_null()).select("v")
        out3 = run_both(session, q3)
        assert set(out3["v"].tolist()) == {1}

    def test_predicate_compiler_rejects_host_only(self, session):
        from hyperspace_tpu.plan.expr import input_file_name

        codecs = {"a": D.ColumnCodec("numeric")}
        with pytest.raises(D.DeviceUnsupported):
            D.compile_predicate(input_file_name() == "x", codecs)

    def test_datetime_predicates(self, session, hs, tmp_path):
        root = tmp_path / "dates"
        root.mkdir()
        base = np.datetime64("2020-01-01")
        n = 500
        rng = np.random.default_rng(0)
        table = pa.table(
            {
                "d": base + rng.integers(0, 365, n).astype("timedelta64[D]"),
                "v": rng.integers(0, 100, n).astype(np.int64),
            }
        )
        pq.write_table(table, root / "part-00000.parquet")
        df = session.read_parquet(str(root))
        hs = hst.Hyperspace(session)
        hs.create_index(df, hst.CoveringIndexConfig("dateIdx", ["d"], ["v"]))
        session.enable_hyperspace()
        q = df.filter((col("d") >= lit(np.datetime64("2020-06-01"))) & (col("d") < lit(np.datetime64("2020-07-01")))).select("v")
        run_both(session, q)


class TestCompilerDateCompareAndCase:
    """The predicate compiler's two extensions of PR 43: a compare of two
    datetime columns of one unit, and ``CASE`` with boolean conditions as a
    numeric expression (a computed aggregate input)."""

    @staticmethod
    def _encoded(batch):
        import jax.numpy as jnp

        D.ensure_x64()
        cols, codecs = {}, {}
        for name, arr in batch.items():
            enc, codecs[name] = D.encode_column(arr)
            cols[name] = jnp.asarray(enc)
        return cols, codecs

    @pytest.fixture()
    def dates(self):
        a = np.array(["2024-01-05", "2024-02-01", "NaT", "2024-03-09", "2024-03-09"], dtype="datetime64[D]")
        b = np.array(["2024-01-06", "2024-01-31", "2024-01-01", "NaT", "2024-03-09"], dtype="datetime64[D]")
        return {"a": a, "b": b, "s": a.astype("datetime64[s]")}

    @pytest.mark.parametrize("op", ["<", "<=", "=", "!=", ">", ">="])
    def test_two_date_columns_of_one_unit_compare_on_the_device(self, dates, op):
        from hyperspace_tpu.plan.expr import BinaryOp, as_bool_mask

        cond = BinaryOp(op, col("a"), col("b"))
        cols, codecs = self._encoded(dates)
        fn, lits = D.compile_predicate(cond, codecs)
        got = np.asarray(fn(cols, lits))
        a, b = dates["a"], dates["b"]
        known = ~(np.isnat(a) | np.isnat(b))  # a NaT on either side is unknown: never kept
        want = {"<": a < b, "<=": a <= b, "=": a == b, "!=": a != b, ">": a > b, ">=": a >= b}[op] & known
        assert got.tolist() == want.tolist()
        assert got[known].tolist() == as_bool_mask(cond.eval(dates))[known].tolist(), "the host's answer where both are dates"

    def test_not_of_a_date_column_compare_keeps_no_nat_row(self, dates):
        cols, codecs = self._encoded(dates)
        fn, lits = D.compile_predicate(~(col("a") < col("b")), codecs)
        assert np.asarray(fn(cols, lits)).tolist() == [False, True, False, False, True]

    @pytest.mark.parametrize("cond", [lambda: col("a") < col("s"), lambda: col("a") < col("b") + lit(1), lambda: col("a") < col("n")])
    def test_two_units_or_date_arithmetic_are_refused_as_before(self, dates, cond):
        batch = dict(dates, n=np.arange(5, dtype=np.int64))
        _cols, codecs = self._encoded(batch)
        with pytest.raises(D.DeviceUnsupported):
            D.compile_predicate(cond(), codecs)

    @pytest.fixture()
    def rows(self):
        return {
            "p": np.array(["1-URGENT", "3-MEDIUM", None, "2-HIGH", "5-LOW", "1-URGENT"], dtype=object),
            "q": np.array([5, 7, 11, 13, 17, 19], dtype=np.int64),
            "x": np.array([0.5, np.nan, 1.5, 2.5, 3.5, 4.5]),
            "d": np.array(["2024-01-05", "2024-02-01", "NaT", "2024-03-09", "2024-03-09", "2024-04-01"], dtype="datetime64[D]"),
        }

    def _computed(self, rows, expr):
        cols, codecs = self._encoded(rows)
        fn, lits, skeleton = D.compile_computes([("c", expr)], codecs)
        assert "Case(" in skeleton
        return np.asarray(fn(cols, lits)["c"])

    def test_case_over_string_codes_with_or_and_ne(self, rows):
        """Q12's two inputs: a NULL priority is in neither count."""
        from hyperspace_tpu.plan.expr import Case

        high = Case([((col("p") == lit("1-URGENT")) | (col("p") == lit("2-HIGH")), lit(1))], lit(0))
        low = Case([((col("p") != lit("1-URGENT")) & (col("p") != lit("2-HIGH")), lit(1))], lit(0))
        for expr in (high, low):
            got = self._computed(rows, expr)
            assert got.dtype == np.int64 and got.tolist() == np.asarray(expr.eval(rows)).tolist()
        assert self._computed(rows, high).tolist() == [1, 0, 0, 1, 0, 1] and self._computed(rows, low).tolist() == [0, 1, 0, 0, 1, 0]

    def test_the_first_true_branch_wins_and_values_may_be_expressions(self, rows):
        from hyperspace_tpu.plan.expr import Case

        expr = Case([(col("q") > lit(12), col("q") * lit(2)), (col("q") > lit(6), col("x") + lit(100.0)),
                     (col("d") < lit(np.datetime64("2024-01-10")), lit(-1))], col("q"))
        got = self._computed(rows, expr)
        np.testing.assert_array_equal(got, np.asarray(expr.eval(rows), dtype=np.float64))
        assert got.tolist()[0] == -1.0 and np.isnan(got[1]) and got.tolist()[2:] == [101.5, 26.0, 34.0, 38.0]

    def test_a_case_without_else_is_null(self, rows):
        from hyperspace_tpu.plan.expr import Case

        for otherwise in (None, lit(None)):
            expr = Case([(col("p") == lit("1-URGENT"), col("q"))], otherwise)
            got = self._computed(rows, expr)
            assert got.dtype == np.float64
            np.testing.assert_array_equal(got, np.asarray(expr.eval(rows), dtype=np.float64))
            assert got.tolist()[0] == 5.0 and np.isnan(got[1:5]).all() and got[5] == 19.0

    @pytest.mark.parametrize("expr", [
        lambda C: C([(col("q") > lit(6), lit("big"))], lit("small")),  # a string's value
        lambda C: C([(col("q") > lit(6), col("d"))], col("d")),  # a date's value has a unit, no arithmetic
        lambda C: C([(col("p") == col("p"), lit(1))], lit(0)),  # a string column against a column
    ])
    def test_a_case_outside_the_language_is_refused(self, rows, expr):
        from hyperspace_tpu.plan.expr import Case

        _cols, codecs = self._encoded(rows)
        with pytest.raises(D.DeviceUnsupported):
            D.compile_computes([("c", expr(Case))], codecs)

    def test_like_and_functions_are_outside_the_language_before_any_type_is_known(self):
        from hyperspace_tpu.plan.expr import Case, Like

        assert D.in_device_language(Case([((col("a") == lit("x")) | col("b").is_null(), col("c") * lit(2))], lit(0)))
        assert not D.in_device_language(Case([(Like(col("a"), "PROMO%"), col("c"))], lit(0)))

    def test_case_as_a_computed_input_of_the_scan_tiers_program(self, session):
        """``sf10-report``'s tier takes it too: a grouped count by CASE over
        one scan's resident columns, through ``device_scan_aggregate``."""
        from hyperspace_tpu.plan.expr import Case

        rng = np.random.default_rng(43)
        n = 5000
        batch = {
            "mode": rng.choice(np.array(["AIR", "MAIL", "SHIP"], dtype=object), n),
            "pri": rng.choice(np.array(["1-URGENT", "2-HIGH", "3-MEDIUM", None], dtype=object), n),
            "qty": rng.integers(1, 51, n).astype(np.int64),
        }
        computes = [("hi", Case([((col("pri") == lit("1-URGENT")) | (col("pri") == lit("2-HIGH")), col("qty"))], lit(0)))]
        aggs = [("high_qty", "sum", "hi"), ("n", "count", None)]
        D.clear_device_cache()
        cols = D.ScanColumns(session, (("mem://case", 1, n),), ["mode", "pri", "qty"], lambda: batch)
        got = D.device_scan_aggregate(session, cols, col("qty") > lit(10), computes, ["mode"], aggs)
        keep = batch["qty"] > 10
        hi = np.where(np.isin(batch["pri"].astype(str), ["1-URGENT", "2-HIGH"]), batch["qty"], 0)
        for mode, high_qty, count in zip(got["mode"], got["high_qty"], got["n"]):
            rows = keep & (batch["mode"] == mode)
            assert (int(high_qty), int(count)) == (int(hi[rows].sum()), int(rows.sum())), mode
        assert sorted(got["mode"]) == ["AIR", "MAIL", "SHIP"] and got["high_qty"].dtype == np.int64
        glob = D.device_scan_aggregate(session, cols, col("qty") > lit(10), computes, [], aggs)
        assert int(glob["high_qty"][0]) == int(hi[keep].sum()) and int(glob["n"][0]) == int(keep.sum())


def _link_counters():
    """(h2d bytes of scan columns, resident-column hits, misses) so far."""
    from hyperspace_tpu.obs.metrics import REGISTRY

    return (
        REGISTRY.counter("hs_h2d_bytes_total", "", site="filter-cols").value,
        REGISTRY.counter("hs_device_cache_lookups_total", "", result="hit").value,
        REGISTRY.counter("hs_device_cache_lookups_total", "", result="miss").value,
    )


def _growth(before):
    return tuple(a - b for a, b in zip(_link_counters(), before))


def _at(value):
    """The rows with ``k == value`` asked for as a closed range: a whole
    read for the device path. (An equality on the bucket column reads its one
    bucket and is answered on the host: tests/test_bucket_pruning.py.)"""
    return (col("k") >= value) & (col("k") <= value)


def _column_keys(column):
    """Scan keys under which ``column`` is resident in the device cache."""
    return [k[0] for k in D._device_cache.keys() if len(k) == 3 and k[1] == column]


class TestResidentColumnKey:
    """A device-resident column is keyed by the rows of the batch it was made
    from — the files and the row groups the read kept — never by the
    predicate (executor._pruned_scan_key). Two batches under one key hold the
    same rows in the same order."""

    @pytest.fixture()
    def indexed(self, session, hs, tmp_path):
        root = tmp_path / "resident"
        root.mkdir()
        rng = np.random.default_rng(26)
        n = 4000
        pq.write_table(
            pa.table(
                {
                    "k": rng.integers(0, 200, n).astype(np.int64),
                    "v": np.arange(n, dtype=np.int64),
                    "d": np.datetime64("2024-01-01") + rng.integers(0, 90, n).astype("timedelta64[D]"),
                }
            ),
            root / "p.parquet",
        )
        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        df = session.read_parquet(str(root))
        hs.create_index(df, hst.CoveringIndexConfig("resIdx", ["k"], ["v", "d"]))
        session.enable_hyperspace()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        df.create_or_replace_temp_view("resident_t")
        D.clear_device_cache()
        return df

    @pytest.mark.parametrize("leaf", ["index-scan", "file-scan"])
    def test_scan_identity_is_the_logs_for_an_index_and_stat_for_files(
        self, session, indexed, tmp_path, monkeypatch, leaf
    ):
        """An IndexScan's identity is what its log entry recorded at commit,
        with no stat per query; any other scan's files are stat'ed, so an
        in-place rewrite changes the identity."""
        import os

        import hyperspace_tpu.exec.file_identity as E

        stats = []
        real_stat = os.stat
        monkeypatch.setattr(os, "stat", lambda p, *a, **k: (stats.append(p), real_stat(p, *a, **k))[1])
        if leaf == "index-scan":
            plan = indexed.filter(_at(90)).select("v").optimized_plan()
            (scan,) = [p for p in L.collect(plan, lambda x: True) if isinstance(p, L.IndexScan)]
            del stats[:]
            ident = E.scan_identity(scan)
            assert stats == []
            assert ident == tuple(fi.key for fi in scan.entry.content.file_infos())
            assert [part[0] for part in ident] == list(scan.files)
            # a file its entry never committed is stat'ed, and only that file
            stray = L.IndexScan(scan.entry, ["k"], None, files=[scan.files[0], __file__])
            assert E.scan_identity(stray)[1][0] == __file__ and stats == [__file__]
            assert E.scan_identity(stray)[0] == ident[0]
        else:
            f = str(tmp_path / "plain.parquet")
            pq.write_table(pa.table({"x": np.arange(10, dtype=np.int64)}), f)
            scan = L.FileScan([f], "parquet", ["x"])
            before = E.scan_identity(scan)
            assert stats == [f] and before[0][0] == f
            pq.write_table(pa.table({"x": np.arange(11, dtype=np.int64)}), f)
            assert E.scan_identity(scan) != before
            os.remove(f)
            assert E.scan_identity(scan) is None

    @pytest.mark.parametrize("how", ["bare-session", "query-server"])
    def test_second_literal_finds_the_column_resident(self, session, indexed, how):
        """Two literals over one index scan: every bucket file spans the key
        range, so the bare session's row-group pruning keeps everything, and
        the served path's bucket cache never prunes; both share the plain
        key, and only the first query uploads."""
        from hyperspace_tpu.serving import QueryServer

        pdf = indexed.collect()

        def ask(run, lit_value):
            before = _link_counters()
            got = run(lit_value)
            want = np.sort(pdf["v"][pdf["k"] == lit_value])
            np.testing.assert_array_equal(np.sort(got["v"]), want)
            return _growth(before)

        def check(run):
            up, hits, misses = ask(run, 90)
            assert up > 0 and (hits, misses) == (0, 1)
            for lit_value in (91, 117):
                assert ask(run, lit_value) == (0, 1, 0)
            (key,) = _column_keys("k")
            assert all(len(part) == 3 and part[0].endswith(".parquet") for part in key)

        if how == "bare-session":
            check(lambda v: indexed.filter(_at(v)).select("v").collect())
        else:
            with QueryServer(session, workers=2) as srv:
                check(lambda v: srv.query(f"SELECT v FROM resident_t WHERE k >= {v} AND k <= {v}"))

    @pytest.mark.parametrize("reader", ["native-rg-scan", "per-file"])
    def test_equal_counts_different_rows_never_alias(self, session, tmp_path, monkeypatch, reader):
        """The case the brand exists for: two predicates prune one file to
        500 rows each — different rows. They get different keys and right
        answers; two predicates that keep the same groups share a column;
        a whole read has the plain key. Both readers of exec/io.py report
        what they kept. The control at the end shows the scenario bites:
        keyed on the file set alone it answers wrongly."""
        import hyperspace_tpu.exec.executor as E

        if reader == "per-file":
            monkeypatch.setenv("HS_NATIVE_RG", "0")

        f = str(tmp_path / "two_groups.parquet")
        pq.write_table(
            pa.table(
                {
                    "x": np.arange(1000, dtype=np.int64),
                    "v": np.arange(1000, dtype=np.int64) * 7,
                }
            ),
            f,
            row_group_size=500,
        )
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)

        def ask(lo, hi):
            from hyperspace_tpu.exec import io as IO

            IO.clear_io_cache()  # a host-cached whole file would answer unpruned
            cond = (col("x") >= lit(lo)) & (col("x") < lit(hi))
            plan = L.Filter(cond, L.FileScan([f], "parquet", ["x", "v"]))
            return np.sort(E.Executor(session).execute(plan)["v"])

        D.clear_device_cache()
        np.testing.assert_array_equal(ask(10, 20), np.arange(10, 20) * 7)
        np.testing.assert_array_equal(ask(510, 520), np.arange(510, 520) * 7)
        first, second = _column_keys("x")
        assert first[:-1] == second[:-1] == scan_identity(L.FileScan([f], "parquet", ["x"]))
        assert {first[-1], second[-1]} == {("rg-kept", ((f, (0,)),)), ("rg-kept", ((f, (1,)),))}
        # another predicate that keeps group 0 shares its column
        before = _link_counters()
        np.testing.assert_array_equal(ask(100, 400), np.arange(100, 400) * 7)
        assert _growth(before) == (0, 1, 0)
        # a read that prunes nothing has the plain key
        np.testing.assert_array_equal(ask(400, 600), np.arange(400, 600) * 7)
        assert scan_identity(L.FileScan([f], "parquet", ["x"])) in _column_keys("x")

        # control: the file set alone as the key aliases the two 500-row batches
        monkeypatch.setattr(E, "_pruned_scan_key", lambda key, kept: key)
        D.clear_device_cache()
        ask(10, 20)
        assert ask(510, 520).size == 0  # evaluated over rows 0..499: wrong
        D.clear_device_cache()

    @pytest.mark.parametrize("how", ["purge", "refresh"])
    def test_purge_and_refresh_drop_the_resident_column(self, session, hs, indexed, tmp_path, how):
        q = indexed.filter(_at(90)).select("v")
        first = q.collect()
        (key,) = _column_keys("k")
        files = [part[0] for part in key]
        if how == "purge":
            # a key that carries a kept signature is found by its file triples too
            D._device_cache_put((key + (("rg-kept", ((files[0], (0,)),)),), "k", "fp"), ("a", None, 1), 8)
            assert D.purge_device_cache_files([files[0]]) == 2
            assert _column_keys("k") == []
            before = _link_counters()
            assert_batches_equal(q.collect(), first)
            up, hits, misses = _growth(before)
            assert up > 0 and (hits, misses) == (0, 1)
        else:
            pq.write_table(
                pa.table(
                    {
                        "k": np.full(5, 90, dtype=np.int64),
                        "v": np.arange(10_000, 10_005, dtype=np.int64),
                        "d": np.full(5, np.datetime64("2024-06-01")),
                    }
                ),
                tmp_path / "resident" / "p1.parquet",
            )
            hs.refresh_index("resIdx", "full")
            q2 = session.read_parquet(str(tmp_path / "resident")).filter(_at(90)).select("v")
            before = _link_counters()
            got = q2.collect()
            assert B.num_rows(got) == B.num_rows(first) + 5
            assert _growth(before)[2] == 1  # new files, new key: the old column is not served
            assert not set(files) & {part[0] for k in _column_keys("k") for part in k}

    def test_range_aggregate_fallback_shares_the_filters_key(self, session, indexed):
        """max over a datetime column is outside the fused aggregate program,
        so Aggregate falls back to _filter_mask over the batch its gate read:
        same scan, same kept signature, the column the plain filter left."""
        from hyperspace_tpu.exec import trace

        cond = (col("k") >= 50) & (col("k") < 60)
        indexed.filter(cond).select("v").collect()
        before = _link_counters()
        with trace.recording() as events:
            got = indexed.filter((col("k") >= 70) & (col("k") < 80)).agg(
                last=("d", "max"), n=("*", "count")
            ).collect()
        assert ("agg", "device-fused-scan") not in events and ("filter", "device") in events
        assert _growth(before) == (0, 1, 0)
        assert len(_column_keys("k")) == 1
        pdf = indexed.collect()
        sel = (pdf["k"] >= 70) & (pdf["k"] < 80)
        assert int(got["n"][0]) == int(sel.sum()) and got["last"][0] == pdf["d"][sel].max()


class TestDeviceJoin:
    @pytest.fixture()
    def two_tables(self, tmp_path):
        rng = np.random.default_rng(7)
        n1, n2 = 3000, 1000
        left = pa.table(
            {
                "k": rng.integers(0, 400, n1).astype(np.int64),
                "lv": rng.standard_normal(n1),
            }
        )
        right = pa.table(
            {
                "k": rng.integers(0, 400, n2).astype(np.int64),
                "rv": rng.integers(0, 10, n2).astype(np.int64),
            }
        )
        lroot, rroot = tmp_path / "left", tmp_path / "right"
        lroot.mkdir()
        rroot.mkdir()
        for i in range(3):
            pq.write_table(left.slice(i * 1000, 1000), lroot / f"part-{i:05d}.parquet")
        pq.write_table(right, rroot / "part-00000.parquet")
        return str(lroot), str(rroot)

    def test_bucketed_join_device_equals_host(self, session, hs, two_tables):
        lpath, rpath = two_tables
        session.conf.set(hst.keys.NUM_BUCKETS, 16)
        ldf = session.read_parquet(lpath)
        rdf = session.read_parquet(rpath)
        hs.create_index(ldf, hst.CoveringIndexConfig("lIdx", ["k"], ["lv"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("rIdx", ["k"], ["rv"]))
        session.enable_hyperspace()

        q = ldf.join(rdf, on="k").select("k", "lv", "rv")
        plan = q.optimized_plan()
        scans = [p for p in L.collect(plan, lambda p: True) if isinstance(p, L.IndexScan)]
        assert len(scans) == 2, plan.pretty()
        # joined result identical with device exec on/off, and vs no index at all
        dev = run_both(session, q)
        session.disable_hyperspace()
        baseline = q.collect()
        assert_batches_equal(dev, baseline)
        assert B.num_rows(dev) > 0

    def test_join_with_duplicate_keys_both_sides(self, session, hs, tmp_path):
        # many-to-many expansion must match pandas merge exactly
        lroot, rroot = tmp_path / "l2", tmp_path / "r2"
        lroot.mkdir()
        rroot.mkdir()
        pq.write_table(
            pa.table({"k": np.array([1, 1, 2, 3, 3, 3], dtype=np.int64), "a": np.arange(6, dtype=np.int64)}),
            lroot / "part-00000.parquet",
        )
        pq.write_table(
            pa.table({"k": np.array([1, 1, 3, 4], dtype=np.int64), "b": np.arange(4, dtype=np.int64)}),
            rroot / "part-00000.parquet",
        )
        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        ldf = session.read_parquet(str(lroot))
        rdf = session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("dupL", ["k"], ["a"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("dupR", ["k"], ["b"]))
        session.enable_hyperspace()
        q = ldf.join(rdf, on="k").select("k", "a", "b")
        out = run_both(session, q)
        # 1 matches 2 rows ×2 left rows, 3 matches 1 row ×3 left rows = 7
        assert B.num_rows(out) == 2 * 2 + 3 * 1

    def test_join_after_incremental_refresh_resorts_buckets(self, session, hs, tmp_path):
        # incremental refresh merges delta files into existing buckets
        # (UpdateMode.Merge) leaving them only piecewise sorted; the device
        # join must re-sort before searchsorted
        lroot, rroot = tmp_path / "l4", tmp_path / "r4"
        lroot.mkdir()
        rroot.mkdir()
        rng = np.random.default_rng(3)
        pq.write_table(
            pa.table({"k": rng.integers(0, 50, 400).astype(np.int64), "a": np.arange(400, dtype=np.int64)}),
            lroot / "part-00000.parquet",
        )
        pq.write_table(
            pa.table({"k": np.arange(50, dtype=np.int64), "b": np.arange(50, dtype=np.int64)}),
            rroot / "part-00000.parquet",
        )
        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        ldf = session.read_parquet(str(lroot))
        rdf = session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("incL", ["k"], ["a"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("incR", ["k"], ["b"]))
        # append more rows and refresh incrementally -> multi-file buckets
        pq.write_table(
            pa.table({"k": rng.integers(0, 50, 400).astype(np.int64), "a": np.arange(400, 800, dtype=np.int64)}),
            lroot / "part-00001.parquet",
        )
        hs.refresh_index("incL", "incremental")
        session.enable_hyperspace()
        # re-read: relations snapshot their file list at construction (as
        # Spark's InMemoryFileIndex does), so the post-append source needs a
        # fresh scan for signatures to line up with the refreshed index
        ldf = session.read_parquet(str(lroot))
        q = ldf.join(rdf, on="k").select("k", "a", "b")
        plan = q.optimized_plan()
        scans = [p for p in L.collect(plan, lambda p: True) if isinstance(p, L.IndexScan)]
        assert len(scans) == 2, plan.pretty()
        assert any(len(s.files) > 4 for s in scans)  # merged buckets have >1 file
        out = run_both(session, q)
        session.disable_hyperspace()
        assert_batches_equal(out, q.collect())

    def test_empty_join_result_preserves_dtypes(self, session, hs, tmp_path):
        lroot, rroot = tmp_path / "l5", tmp_path / "r5"
        lroot.mkdir()
        rroot.mkdir()
        pq.write_table(
            pa.table({"k": np.array([1, 2], dtype=np.int64), "a": np.array([10, 20], dtype=np.int64)}),
            lroot / "p.parquet",
        )
        pq.write_table(
            pa.table({"k": np.array([5, 6], dtype=np.int64), "b": np.array([1.5, 2.5])}),
            rroot / "p.parquet",
        )
        session.conf.set(hst.keys.NUM_BUCKETS, 2)
        ldf = session.read_parquet(str(lroot))
        rdf = session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("eL", ["k"], ["a"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("eR", ["k"], ["b"]))
        session.enable_hyperspace()
        out = ldf.join(rdf, on="k").select("k", "a", "b").collect()
        assert B.num_rows(out) == 0
        assert out["k"].dtype == np.int64
        assert out["a"].dtype == np.int64
        assert out["b"].dtype == np.float64

    def test_host_bucketed_join_matches_device_and_pandas(self, session, hs, two_tables):
        """host_bucketed_join is the default production path below the
        deviceMinRows threshold — its spans must agree with both the device
        SMJ and the independent pandas merge."""
        from hyperspace_tpu.exec import device as D

        lpath, rpath = two_tables
        session.conf.set(hst.keys.NUM_BUCKETS, 16)
        ldf = session.read_parquet(lpath)
        rdf = session.read_parquet(rpath)
        hs.create_index(ldf, hst.CoveringIndexConfig("hjL", ["k"], ["lv"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("hjR", ["k"], ["rv"]))
        session.enable_hyperspace()
        q = ldf.join(rdf, on="k").select("k", "lv", "rv")
        plan = q.optimized_plan()
        joins = [p for p in L.collect(plan, lambda p: True) if isinstance(p, L.Join)]
        assert joins, plan.pretty()

        host_out = D.host_bucketed_join(session, joins[0])
        dev_out = D.device_bucketed_join(session, joins[0])
        assert_batches_equal(host_out, dev_out)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
        pandas_out = q.collect()  # kill switch -> pandas merge
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        # the raw join node also outputs the right key copy (k#r); the public
        # query's Project drops it
        host_proj = {k: v for k, v in host_out.items() if k in pandas_out}
        assert_batches_equal(host_proj, pandas_out)
        assert B.num_rows(host_out) > 0

    def test_join_threshold_dispatch(self, session, hs, two_tables, monkeypatch):
        """Above deviceMinRows the device path runs; below it the host path
        runs — same results either way through the public API."""
        lpath, rpath = two_tables
        session.conf.set(hst.keys.NUM_BUCKETS, 16)
        ldf = session.read_parquet(lpath)
        rdf = session.read_parquet(rpath)
        hs.create_index(ldf, hst.CoveringIndexConfig("tdL", ["k"], ["lv"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("tdR", ["k"], ["rv"]))
        session.enable_hyperspace()
        q = ldf.join(rdf, on="k").select("k", "lv", "rv")

        from hyperspace_tpu.exec import device as D

        calls = []
        real_dev, real_host = D.device_bucketed_join, D.host_bucketed_join
        monkeypatch.setattr(D, "device_bucketed_join", lambda *a, **k: calls.append("dev") or real_dev(*a, **k))
        monkeypatch.setattr(D, "host_bucketed_join", lambda *a, **k: calls.append("host") or real_host(*a, **k))

        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        low = q.collect()
        assert calls[-1] == "dev"
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 1 << 40)
        high = q.collect()
        assert calls[-1] == "host"
        assert_batches_equal(low, high)

    def test_expand_pairs_promotes_mixed_bucket_dtypes(self):
        """A nullable int column decodes as float64 (with NaN) only in the
        buckets whose files hold nulls; the preallocated output must promote
        across buckets instead of truncating into the first bucket's dtype."""
        from hyperspace_tpu.exec.device import _expand_join_pairs

        class FakeJoin:
            output_columns = ["k", "val"]
            how = "inner"

        lbuckets = {
            0: {"k": np.array([1, 2], dtype=np.int64), "val": np.array([10, 20], dtype=np.int64)},
            1: {"k": np.array([3], dtype=np.int64), "val": np.array([np.nan], dtype=np.float64)},
        }
        rbuckets = {
            0: {"k": np.array([1, 2], dtype=np.int64)},
            1: {"k": np.array([3], dtype=np.int64)},
        }

        def span_of(b):
            lk = lbuckets[b]["k"]
            rk = rbuckets[b]["k"]
            return np.searchsorted(rk, lk, "left"), np.searchsorted(rk, lk, "right")

        out = _expand_join_pairs(FakeJoin(), lbuckets, rbuckets, 2, ["k", "val"], ["k"], span_of)
        assert out["val"].dtype == np.float64
        assert np.isnan(out["val"][-1])
        np.testing.assert_array_equal(out["val"][:2], [10.0, 20.0])

    def test_string_key_join_via_rank_encoding(self, session, hs, tmp_path):
        lroot, rroot = tmp_path / "l3", tmp_path / "r3"
        lroot.mkdir()
        rroot.mkdir()
        keys_l = np.array(["a", "b", "c", "a"], dtype=object)
        keys_r = np.array(["a", "c"], dtype=object)
        pq.write_table(pa.table({"k": keys_l.astype(str), "a": np.arange(4, dtype=np.int64)}), lroot / "p.parquet")
        pq.write_table(pa.table({"k": keys_r.astype(str), "b": np.arange(2, dtype=np.int64)}), rroot / "p.parquet")
        session.conf.set(hst.keys.NUM_BUCKETS, 2)
        ldf = session.read_parquet(str(lroot))
        rdf = session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("sL", ["k"], ["a"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("sR", ["k"], ["b"]))
        session.enable_hyperspace()
        q = ldf.join(rdf, on="k").select("k", "a", "b")
        out = run_both(session, q)
        assert B.num_rows(out) == 3  # a×2 matches + c×1

    def test_string_key_rides_device_span_program(self, session, hs, tmp_path, monkeypatch):
        """String keys reach the DEVICE span program via the shared rank
        encodings (they used to always take the host rank path)."""
        rng = np.random.default_rng(31)
        lroot, rroot = tmp_path / "sl", tmp_path / "sr"
        lroot.mkdir(), rroot.mkdir()
        n = 500
        pq.write_table(
            pa.table({"k": np.array([f"u{v}" for v in rng.integers(0, 60, n)]),
                      "a": rng.standard_normal(n)}),
            lroot / "p.parquet",
        )
        pq.write_table(
            pa.table({"k": np.array([f"u{v}" for v in range(60)]),
                      "b": rng.standard_normal(60)}),
            rroot / "p.parquet",
        )
        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("dsL", ["k"], ["a"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("dsR", ["k"], ["b"]))
        session.enable_hyperspace()

        called = {"n": 0}
        real = D.device_bucketed_join

        def spy(*a, **kw):
            called["n"] += 1
            return real(*a, **kw)

        monkeypatch.setattr(D, "device_bucketed_join", spy)
        monkeypatch.setattr("hyperspace_tpu.exec.device.device_bucketed_join", spy)
        q = ldf.join(rdf, on="k").select("k", "a", "b")
        out = run_both(session, q)
        assert called["n"] >= 1, "device span program must serve string keys"
        # cross-check against pandas ground truth
        import pandas as pd

        lt = pq.read_table(lroot / "p.parquet").to_pandas()
        rt = pq.read_table(rroot / "p.parquet").to_pandas()
        want = lt.merge(rt, on="k")
        assert B.num_rows(out) == len(want)

    def test_composite_key_rides_device_span_program(self, session, hs, tmp_path, monkeypatch):
        rng = np.random.default_rng(33)
        lroot, rroot = tmp_path / "cl", tmp_path / "cr"
        lroot.mkdir(), rroot.mkdir()
        n = 400
        pq.write_table(
            pa.table({
                "k1": rng.integers(0, 12, n).astype(np.int64),
                "k2": np.array([f"s{v}" for v in rng.integers(0, 6, n)]),
                "a": rng.standard_normal(n)}),
            lroot / "p.parquet",
        )
        pq.write_table(
            pa.table({
                "k1": np.repeat(np.arange(12, dtype=np.int64), 6),
                "k2": np.array([f"s{v}" for v in list(range(6)) * 12]),
                "b": rng.standard_normal(72)}),
            rroot / "p.parquet",
        )
        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("dcL", ["k1", "k2"], ["a"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("dcR", ["k1", "k2"], ["b"]))
        session.enable_hyperspace()

        called = {"n": 0}
        real = D.device_bucketed_join

        def spy(*a, **kw):
            called["n"] += 1
            return real(*a, **kw)

        monkeypatch.setattr("hyperspace_tpu.exec.device.device_bucketed_join", spy)
        q = ldf.join(rdf, on=["k1", "k2"]).select("k1", "k2", "a", "b")
        out = run_both(session, q)
        assert called["n"] >= 1, "device span program must serve composite keys"
        import pandas as pd

        lt = pq.read_table(lroot / "p.parquet").to_pandas()
        rt = pq.read_table(rroot / "p.parquet").to_pandas()
        want = lt.merge(rt, on=["k1", "k2"])
        assert B.num_rows(out) == len(want)


class TestDeviceMaterialization:
    """Inner-join pair expansion + numeric gather on device: the host
    receives final columns only (SURVEY §2.9 device-local merge-join)."""

    @pytest.fixture()
    def joined(self, session, hs, tmp_path):
        rng = np.random.default_rng(41)
        lroot, rroot = tmp_path / "ml", tmp_path / "mr"
        lroot.mkdir(), rroot.mkdir()
        n = 800
        pq.write_table(
            pa.table({
                "k": rng.integers(0, 50, n).astype(np.int64),
                "amount": np.round(rng.uniform(0, 100, n), 3),
                "day": np.datetime64("2024-01-01") + rng.integers(0, 90, n).astype("timedelta64[D]"),
                "tag": np.array([f"t{v}" for v in rng.integers(0, 7, n)]),
            }),
            lroot / "p.parquet",
        )
        pq.write_table(
            pa.table({
                "k": np.arange(50, dtype=np.int64),
                "w": rng.standard_normal(50),
            }),
            rroot / "p.parquet",
        )
        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("mL", ["k"], ["amount", "day", "tag"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("mR", ["k"], ["w"]))
        session.enable_hyperspace()
        return ldf.join(rdf, on="k").select("k", "amount", "day", "tag", "w"), lroot, rroot

    def test_device_materialization_runs_and_matches(self, session, joined, monkeypatch):
        import pandas as pd

        q, lroot, rroot = joined
        called = {"n": 0}
        real = D._device_materialize_inner

        def spy(*a, **kw):
            called["n"] += 1
            return real(*a, **kw)

        monkeypatch.setattr("hyperspace_tpu.exec.device._device_materialize_inner", spy)
        out = run_both(session, q)  # device == host already asserted inside
        assert called["n"] >= 1, "device materialization must have served the join"
        lt = pq.read_table(lroot / "p.parquet").to_pandas()
        rt = pq.read_table(rroot / "p.parquet").to_pandas()
        want = lt.merge(rt, on="k")
        assert B.num_rows(out) == len(want)
        assert np.isclose(np.sort(out["amount"]).sum(), want["amount"].sum())
        assert out["day"].dtype.kind == "M" and out["tag"].dtype == object

    def test_flag_off_reverts_to_host_expansion(self, session, joined, monkeypatch):
        q, _, _ = joined
        session.conf.set(hst.keys.TPU_JOIN_DEVICE_MATERIALIZE, False)
        try:
            called = {"n": 0}

            def spy(*a, **kw):
                called["n"] += 1
                raise AssertionError("must not run with the flag off")

            monkeypatch.setattr("hyperspace_tpu.exec.device._device_materialize_inner", spy)
            out = run_both(session, q)
            assert called["n"] == 0
            assert B.num_rows(out) > 0
        finally:
            session.conf.set(hst.keys.TPU_JOIN_DEVICE_MATERIALIZE, True)

    def test_outer_join_stays_on_host_gather(self, session, hs, tmp_path, monkeypatch):
        lroot, rroot = tmp_path / "ol", tmp_path / "or"
        lroot.mkdir(), rroot.mkdir()
        pq.write_table(
            pa.table({"k": np.array([1, 2, 3], dtype=np.int64), "a": np.arange(3.0)}),
            lroot / "p.parquet",
        )
        pq.write_table(
            pa.table({"k": np.array([2, 3, 4], dtype=np.int64), "b": np.arange(3.0)}),
            rroot / "p.parquet",
        )
        session.conf.set(hst.keys.NUM_BUCKETS, 2)
        ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("oL", ["k"], ["a"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("oR", ["k"], ["b"]))
        session.enable_hyperspace()

        def boom(*a, **kw):
            raise AssertionError("outer joins must not take device materialization")

        monkeypatch.setattr("hyperspace_tpu.exec.device._device_materialize_inner", boom)
        q = ldf.join(rdf, on="k", how="left").select("k", "a", "b")
        out = run_both(session, q)
        assert B.num_rows(out) == 3


class TestHybridBucketedJoin:
    """Hybrid-scan sides (BucketUnion of index + re-bucketed appends, with
    lineage NOT-IN deletes) now ride the shuffle-free bucketed-SMJ fast path
    instead of the generic pandas merge (ref: the reference keeps its
    exchange-free SMJ under hybrid scan via on-the-fly re-bucketing,
    CoveringIndexRuleUtils.scala:357-417)."""

    @pytest.fixture()
    def hybrid_join_env(self, session, hs, tmp_path):
        session.conf.set(hst.keys.NUM_BUCKETS, 8)
        session.conf.set(hst.keys.HYBRID_SCAN_ENABLED, True)
        session.conf.set(hst.keys.LINEAGE_ENABLED, True)
        rng = np.random.default_rng(21)
        lroot, rroot = tmp_path / "fact", tmp_path / "dim"
        lroot.mkdir(), rroot.mkdir()
        n = 600
        pq.write_table(
            pa.table({"k": rng.integers(0, 40, n).astype(np.int64), "a": rng.standard_normal(n)}),
            lroot / "p0.parquet",
        )
        pq.write_table(
            pa.table({"k": np.arange(40, dtype=np.int64), "b": rng.standard_normal(40)}),
            rroot / "p0.parquet",
        )
        fact, dim = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
        hs.create_index(fact, hst.CoveringIndexConfig("factIdx", ["k"], ["a"]))
        hs.create_index(dim, hst.CoveringIndexConfig("dimIdx2", ["k"], ["b"]))
        # append to the fact side AFTER indexing -> hybrid scan kicks in
        pq.write_table(
            pa.table({"k": rng.integers(0, 40, 100).astype(np.int64), "a": rng.standard_normal(100)}),
            lroot / "p1.parquet",
        )
        return str(lroot), str(rroot)

    def _join(self, session, lroot, rroot):
        fact, dim = session.read_parquet(lroot), session.read_parquet(rroot)
        return fact.join(dim, on=hst.col("k") == hst.col("k")).select("a", "b")

    def test_hybrid_side_takes_bucketed_path(self, session, hybrid_join_env):
        lroot, rroot = hybrid_join_env
        session.enable_hyperspace()
        q = self._join(session, lroot, rroot)
        plan = q.optimized_plan()
        joins = L.collect(plan, lambda p: isinstance(p, L.Join))
        assert joins, plan.pretty()
        assert any(
            isinstance(p, L.BucketUnion) for p in L.collect(plan, lambda x: True)
        ), plan.pretty()
        compat = D.join_sides_compatible(joins[0])
        assert compat is not None, "hybrid side must be bucket-compatible"
        # and the dispatch executes without DeviceUnsupported
        got = D.dispatch_bucketed_join(session, joins[0])
        assert B.num_rows(got) == 700  # every fact row matches exactly one dim row

    def test_hybrid_join_results_match_plain(self, session, hybrid_join_env):
        lroot, rroot = hybrid_join_env
        session.enable_hyperspace()
        q = self._join(session, lroot, rroot)
        indexed = q.collect()
        session.disable_hyperspace()
        plain = q.collect()
        assert_batches_equal(indexed, plain)

    def test_hybrid_join_with_deletes(self, session, hs, hybrid_join_env, tmp_path):
        import os

        lroot, rroot = hybrid_join_env
        # delete one source file; lineage NOT-IN filters its rows from the index
        os.remove(os.path.join(lroot, "p0.parquet"))
        session.enable_hyperspace()
        q = self._join(session, lroot, rroot)
        indexed = q.collect()
        session.disable_hyperspace()
        plain = q.collect()
        assert_batches_equal(indexed, plain)
        assert indexed["a"].shape[0] == 100  # only the appended rows remain


class TestCompositeKeyBucketedJoin:
    """Composite (multi-column) and string join keys ride the host span path
    via shared dense rank encoding instead of falling back to a generic merge
    (the reference's JoinIndexRule accepts multi-column equi-joins,
    HS/index/covering/JoinIndexRule.scala:419-448)."""

    @pytest.fixture()
    def composite_env(self, session, hs, tmp_path):
        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        rng = np.random.default_rng(31)
        lroot, rroot = tmp_path / "cl", tmp_path / "cr"
        lroot.mkdir(), rroot.mkdir()
        n = 400
        pq.write_table(
            pa.table(
                {
                    "k1": rng.integers(0, 10, n).astype(np.int64),
                    "k2": np.array([f"g{i % 7}" for i in range(n)]),
                    "a": rng.standard_normal(n),
                }
            ),
            lroot / "p.parquet",
        )
        m = 70
        pq.write_table(
            pa.table(
                {
                    "k1": rng.integers(0, 10, m).astype(np.int64),
                    "k2": np.array([f"g{i % 7}" for i in range(m)]),
                    "b": rng.standard_normal(m),
                }
            ),
            rroot / "p.parquet",
        )
        ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("cL", ["k1", "k2"], ["a"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("cR", ["k1", "k2"], ["b"]))
        session.enable_hyperspace()
        return ldf, rdf

    def test_composite_key_takes_bucketed_path(self, session, composite_env):
        ldf, rdf = composite_env
        q = ldf.join(rdf, on=["k1", "k2"]).select("a", "b")
        plan = q.optimized_plan()
        joins = L.collect(plan, lambda p: isinstance(p, L.Join))
        assert joins and D.join_sides_compatible(joins[0]) is not None, plan.pretty()
        got = D.dispatch_bucketed_join(session, joins[0])
        assert B.num_rows(got) > 0

    def test_composite_key_results_match_pandas(self, session, composite_env):
        ldf, rdf = composite_env
        q = ldf.join(rdf, on=["k1", "k2"]).select("a", "b")
        indexed = q.collect()
        session.disable_hyperspace()
        plain = q.collect()
        assert_batches_equal(indexed, plain)

    def test_composite_ranks_order_and_equality(self):
        l1 = np.array([1, 1, 2, 2], dtype=np.int64)
        l2 = np.array(["a", "b", "a", "a"], dtype=object)
        r1 = np.array([1, 2, 3], dtype=np.int64)
        r2 = np.array(["b", "a", "z"], dtype=object)
        lr, rr = D._composite_ranks([l1, l2], [r1, r2])
        # equal tuples share ranks across sides
        assert lr[1] == rr[0]   # (1,'b')
        assert lr[2] == rr[1] == lr[3]  # (2,'a')
        # lexicographic order preserved
        assert lr[0] < lr[1] < lr[2] < rr[2]


def test_composite_rank_cache_respects_filter_changes(session, tmp_path):
    """Deleting a source file adds a lineage NOT-IN filter over UNCHANGED
    index files; the composite rank cache must key on the filter too, not
    just file identity (stale ranks would crash or join deleted rows)."""
    import os

    hs = hst.Hyperspace(session)
    session.conf.set(hst.keys.NUM_BUCKETS, 4)
    session.conf.set(hst.keys.HYBRID_SCAN_ENABLED, True)
    session.conf.set(hst.keys.LINEAGE_ENABLED, True)
    rng = np.random.default_rng(41)
    lroot, rroot = tmp_path / "rl", tmp_path / "rr"
    lroot.mkdir(), rroot.mkdir()
    for i in range(2):
        pq.write_table(
            pa.table(
                {
                    "k1": rng.integers(0, 6, 200).astype(np.int64),
                    "k2": np.array([f"s{j % 5}" for j in range(200)]),
                    "a": rng.standard_normal(200),
                }
            ),
            lroot / f"p{i}.parquet",
        )
    pq.write_table(
        pa.table(
            {
                "k1": np.repeat(np.arange(6, dtype=np.int64), 5),
                "k2": np.array([f"s{j % 5}" for j in range(30)]),
                "b": rng.standard_normal(30),
            }
        ),
        rroot / "p.parquet",
    )
    ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
    hs.create_index(ldf, hst.CoveringIndexConfig("rcL", ["k1", "k2"], ["a"]))
    hs.create_index(rdf, hst.CoveringIndexConfig("rcR", ["k1", "k2"], ["b"]))
    session.enable_hyperspace()
    q = ldf.join(rdf, on=["k1", "k2"]).select("a", "b")
    first = q.collect()  # warms the rank cache

    os.remove(str(lroot / "p0.parquet"))
    ldf2 = session.read_parquet(str(lroot))
    q2 = ldf2.join(rdf, on=["k1", "k2"]).select("a", "b")
    second = q2.collect()
    session.disable_hyperspace()
    plain = q2.collect()
    assert_batches_equal(second, plain)
    assert B.num_rows(second) < B.num_rows(first)


def test_join_input_device_cache_reuses_and_invalidates(session, tmp_path):
    """The HBM-resident join-input cache (key matrices + payload rectangles)
    must serve repeat executions without re-transfer — repeat results stay
    identical — and must MISS when the underlying index data changes (a
    refresh after an append writes new files, so the file-identity key
    changes; a stale hit would silently drop the appended rows)."""
    hs = hst.Hyperspace(session)
    session.conf.set(hst.keys.NUM_BUCKETS, 4)
    rng = np.random.default_rng(17)
    lroot, rroot = tmp_path / "cl", tmp_path / "cr"
    lroot.mkdir(), rroot.mkdir()
    pq.write_table(
        pa.table(
            {
                "k": rng.integers(0, 40, 500).astype(np.int64),
                "a": rng.standard_normal(500),
            }
        ),
        lroot / "p0.parquet",
    )
    pq.write_table(
        pa.table(
            {
                "k": np.arange(40, dtype=np.int64),
                "b": rng.standard_normal(40),
            }
        ),
        rroot / "p.parquet",
    )
    ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
    hs.create_index(ldf, hst.CoveringIndexConfig("ccL", ["k"], ["a"]))
    hs.create_index(rdf, hst.CoveringIndexConfig("ccR", ["k"], ["b"]))
    session.enable_hyperspace()
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)

    D.clear_device_cache()
    q = ldf.join(rdf, on="k").select("k", "a", "b")
    first = q.collect()
    keymat_keys = [k for k in D._device_cache.keys() if k[0] == "join-keymats"]
    assert keymat_keys, "first execution should populate the join-input cache"
    second = q.collect()  # served from the HBM-resident entries
    assert_batches_equal(first, second)
    # the cached reply must ALSO equal the host path's answer
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
    assert_batches_equal(second, q.collect())
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)

    # append + full refresh -> new index files -> the old entries are stale
    # by KEY (not by mutation); the fresh execution must see the new rows
    pq.write_table(
        pa.table(
            {
                "k": rng.integers(0, 40, 300).astype(np.int64),
                "a": rng.standard_normal(300),
            }
        ),
        lroot / "p1.parquet",
    )
    hs.refresh_index("ccL", "full")
    ldf2 = session.read_parquet(str(lroot))
    q2 = ldf2.join(rdf, on="k").select("k", "a", "b")
    third = q2.collect()
    assert B.num_rows(third) > B.num_rows(first)
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
    assert_batches_equal(third, q2.collect())
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)


def test_span_byte_budget_routes_to_host_spans(session, tmp_path):
    """Above joinDeviceSpanMaxBytes the dispatch must choose the host span
    walk (zero transfer) even when the row count clears deviceMinRows; the
    answer must not change."""
    from hyperspace_tpu.exec import trace

    hs = hst.Hyperspace(session)
    session.conf.set(hst.keys.NUM_BUCKETS, 8)
    rng = np.random.default_rng(29)
    lroot, rroot = tmp_path / "sl", tmp_path / "sr"
    lroot.mkdir(), rroot.mkdir()
    pq.write_table(
        pa.table(
            {
                "k": rng.integers(0, 100, 2000).astype(np.int64),
                "lv": rng.standard_normal(2000),
            }
        ),
        lroot / "p.parquet",
    )
    pq.write_table(
        pa.table(
            {
                "k": np.arange(100, dtype=np.int64),
                "rv": rng.standard_normal(100),
            }
        ),
        rroot / "p.parquet",
    )
    ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
    hs.create_index(ldf, hst.CoveringIndexConfig("sbL", ["k"], ["lv"]))
    hs.create_index(rdf, hst.CoveringIndexConfig("sbR", ["k"], ["rv"]))
    session.enable_hyperspace()
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
    q = ldf.join(rdf, on="k").select("k", "lv", "rv")

    with trace.recording() as dev_events:
        device_ans = q.collect()
    assert ("join", "device-smj") in dev_events

    session.conf.set(hst.keys.TPU_JOIN_DEVICE_SPAN_MAX_BYTES, 1)
    with trace.recording() as host_events:
        host_ans = q.collect()
    assert ("join", "host-span-smj") in host_events
    assert_batches_equal(device_ans, host_ans)
    session.conf.set(hst.keys.TPU_JOIN_DEVICE_SPAN_MAX_BYTES, 256 << 20)


def test_materialize_byte_budget_routes_to_host_expansion(session, tmp_path):
    """Above joinDeviceMaterializeMaxBytes the device join must keep its
    span computation but expand pairs on host (no whole-output download);
    results stay identical to the device-materialized answer."""
    hs = hst.Hyperspace(session)
    session.conf.set(hst.keys.NUM_BUCKETS, 4)
    rng = np.random.default_rng(23)
    lroot, rroot = tmp_path / "bl", tmp_path / "br"
    lroot.mkdir(), rroot.mkdir()
    pq.write_table(
        pa.table(
            {
                "k": rng.integers(0, 30, 400).astype(np.int64),
                "a": rng.standard_normal(400),
            }
        ),
        lroot / "p.parquet",
    )
    pq.write_table(
        pa.table(
            {
                "k": np.arange(30, dtype=np.int64),
                "b": rng.standard_normal(30),
            }
        ),
        rroot / "p.parquet",
    )
    ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
    hs.create_index(ldf, hst.CoveringIndexConfig("bbL", ["k"], ["a"]))
    hs.create_index(rdf, hst.CoveringIndexConfig("bbR", ["k"], ["b"]))
    session.enable_hyperspace()
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
    q = ldf.join(rdf, on="k").select("k", "a", "b")

    D.clear_device_cache()
    device_mat = q.collect()  # default budget: device materialization
    assert any(k[0] == "join-paymats" for k in D._device_cache.keys())
    session.conf.set(hst.keys.TPU_JOIN_DEVICE_MATERIALIZE_MAX_BYTES, 1)
    D.clear_device_cache()
    host_exp = q.collect()  # 400 pairs * 8B >> 1 byte -> host expansion
    # the budget must fire BEFORE the payload rectangles ever transfer, so
    # the paymats cache stays empty on the capped route (this is also what
    # catches the budget check regressing to dead code)
    assert not any(k[0] == "join-paymats" for k in D._device_cache.keys())
    assert_batches_equal(device_mat, host_exp)
    session.conf.set(
        hst.keys.TPU_JOIN_DEVICE_MATERIALIZE_MAX_BYTES,
        256 * 1024 * 1024,
    )


class TestOuterBucketedJoin:
    """left/right/full outer equi-joins ride the span path too; unmatched
    rows null-fill the opposite side exactly like the pandas-merge fallback
    (ints promote to float64 NaN)."""

    @pytest.fixture()
    def outer_env(self, session, hs, tmp_path):
        session.conf.set(hst.keys.NUM_BUCKETS, 4)
        lroot, rroot = tmp_path / "ol", tmp_path / "or"
        lroot.mkdir(), rroot.mkdir()
        # keys 0..9 on the left, 5..14 on the right: both sides have
        # unmatched rows, and some buckets exist on only one side
        pq.write_table(
            pa.table({"k": np.arange(10, dtype=np.int64), "a": np.arange(10, dtype=np.int64) * 10}),
            lroot / "p.parquet",
        )
        pq.write_table(
            pa.table({"k": np.arange(5, 15, dtype=np.int64), "b": np.arange(10, dtype=np.int64) * 7}),
            rroot / "p.parquet",
        )
        ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("oL", ["k"], ["a"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("oR", ["k"], ["b"]))
        session.enable_hyperspace()
        return ldf, rdf

    @pytest.mark.parametrize("how", ["right", "outer"])
    def test_using_key_coalesces_across_sides(self, session, outer_env, how):
        """Spark's df.join(other, on="k") coalesces the USING key: unmatched
        right rows must show the RIGHT side's key under "k", not NULL — on
        the bucketed span path AND the generic pandas-merge fallback."""
        ldf, rdf = outer_env
        q = ldf.join(rdf, on="k", how=how).select("k", "a", "b")

        def keys_of(batch):
            ks = np.asarray(batch["k"], dtype=np.float64)
            assert not np.isnan(ks).any(), "USING key must never be NULL here"
            return sorted(ks.astype(np.int64).tolist())

        span_keys = keys_of(run_both(session, q))  # indexed bucketed paths
        session.disable_hyperspace()
        generic_keys = keys_of(q.collect())  # generic merge fallback
        session.enable_hyperspace()
        assert span_keys == generic_keys
        # right keys 5..14 all present (10..14 match nothing on the left)
        assert set(range(5, 15)) <= set(span_keys)

    @pytest.mark.parametrize("how,expected_rows", [("left", 10), ("right", 10), ("outer", 15), ("inner", 5)])
    def test_outer_join_matches_pandas(self, session, outer_env, how, expected_rows):
        ldf, rdf = outer_env
        q = ldf.join(rdf, on="k", how=how).select("a", "b")
        plan = q.optimized_plan()
        joins = L.collect(plan, lambda p: isinstance(p, L.Join))
        assert joins and D.join_sides_compatible(joins[0]) is not None
        via_spans = D.dispatch_bucketed_join(session, joins[0])
        assert B.num_rows(via_spans) == expected_rows
        session.disable_hyperspace()
        plain = q.collect()
        session.enable_hyperspace()
        assert_batches_equal({c: via_spans[c] for c in ("a", "b")}, plain)
        # and the full query (with projection) agrees end to end
        assert_batches_equal(q.collect(), plain)

    def test_outer_join_null_duplication(self, session, hs, tmp_path):
        """Duplicate matches + unmatched rows in one bucket."""
        session.conf.set(hst.keys.NUM_BUCKETS, 2)
        lroot, rroot = tmp_path / "dl", tmp_path / "dr"
        lroot.mkdir(), rroot.mkdir()
        pq.write_table(
            pa.table({"k": np.array([1, 1, 2, 9], dtype=np.int64), "a": np.arange(4, dtype=np.int64)}),
            lroot / "p.parquet",
        )
        pq.write_table(
            pa.table({"k": np.array([1, 1, 8], dtype=np.int64), "b": np.arange(3, dtype=np.int64)}),
            rroot / "p.parquet",
        )
        ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("dL", ["k"], ["a"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("dR", ["k"], ["b"]))
        session.enable_hyperspace()
        for how in ("left", "right", "outer"):
            q = ldf.join(rdf, on="k", how=how).select("a", "b")
            got = q.collect()
            session.disable_hyperspace()
            plain = q.collect()
            session.enable_hyperspace()
            assert_batches_equal(got, plain)


def test_left_join_right_side_fully_deleted(session, tmp_path):
    """Right side is a hybrid scan whose lineage NOT-IN filter empties every
    bucket (source file deleted): the left join must null-fill, not crash."""
    import os

    hs = hst.Hyperspace(session)
    session.conf.set(hst.keys.NUM_BUCKETS, 2)
    session.conf.set(hst.keys.HYBRID_SCAN_ENABLED, True)
    session.conf.set(hst.keys.HYBRID_SCAN_MAX_DELETED_RATIO, 1.0)
    session.conf.set(hst.keys.LINEAGE_ENABLED, True)
    lroot, rroot = tmp_path / "fl", tmp_path / "fr"
    lroot.mkdir(), rroot.mkdir()
    pq.write_table(
        pa.table({"k": np.arange(6, dtype=np.int64), "a": np.arange(6, dtype=np.int64)}),
        lroot / "p.parquet",
    )
    pq.write_table(
        pa.table({"k": np.arange(6, dtype=np.int64), "b": np.arange(6, dtype=np.int64) * 2}),
        rroot / "p0.parquet",
    )
    pq.write_table(
        pa.table({"k": np.arange(6, 9, dtype=np.int64), "b": np.arange(3, dtype=np.int64)}),
        rroot / "p1.parquet",
    )
    ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
    hs.create_index(ldf, hst.CoveringIndexConfig("flL", ["k"], ["a"]))
    hs.create_index(rdf, hst.CoveringIndexConfig("flR", ["k"], ["b"]))
    os.remove(str(rroot / "p0.parquet"))  # all left-matching right rows gone
    session.enable_hyperspace()
    rdf2 = session.read_parquet(str(rroot))
    q = ldf.join(rdf2, on="k", how="left").select("a", "b")
    got = q.collect()
    session.disable_hyperspace()
    plain = q.collect()
    assert_batches_equal(got, plain)
    assert np.isnan(got["b"]).all()  # nothing matches after the delete


def test_outer_join_bool_payload_matches_pandas(session, tmp_path):
    """Nullable bool columns promote to object True/False/NaN, matching the
    pandas-merge fallback, so both execution paths agree."""
    hs = hst.Hyperspace(session)
    session.conf.set(hst.keys.NUM_BUCKETS, 2)
    lroot, rroot = tmp_path / "bl", tmp_path / "br"
    lroot.mkdir(), rroot.mkdir()
    pq.write_table(
        pa.table({"k": np.array([1, 2, 9], dtype=np.int64), "a": np.arange(3, dtype=np.int64)}),
        lroot / "p.parquet",
    )
    pq.write_table(
        pa.table({"k": np.array([1, 2], dtype=np.int64), "flag": np.array([True, False])}),
        rroot / "p.parquet",
    )
    ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
    hs.create_index(ldf, hst.CoveringIndexConfig("bL", ["k"], ["a"]))
    hs.create_index(rdf, hst.CoveringIndexConfig("bR", ["k"], ["flag"]))
    session.enable_hyperspace()
    q = ldf.join(rdf, on="k", how="left").select("a", "flag")
    got = q.collect()
    session.disable_hyperspace()
    plain = q.collect()
    assert got["flag"].dtype == plain["flag"].dtype == object
    ga = sorted(got["flag"], key=str)
    pa_ = sorted(plain["flag"], key=str)
    assert [str(x) for x in ga] == [str(x) for x in pa_]


def test_outer_join_duration_payload_nulls(session, tmp_path):
    """Duration (timedelta64) payload columns null-fill with NaT on outer
    joins instead of crashing on a NaN assignment."""
    hs = hst.Hyperspace(session)
    session.conf.set(hst.keys.NUM_BUCKETS, 2)
    lroot, rroot = tmp_path / "tl", tmp_path / "tr"
    lroot.mkdir(), rroot.mkdir()
    pq.write_table(
        pa.table({"k": np.array([1, 9], dtype=np.int64), "a": np.array([1, 2], dtype=np.int64)}),
        lroot / "p.parquet",
    )
    pq.write_table(
        pa.table(
            {
                "k": np.array([1], dtype=np.int64),
                "dur": pa.array([np.timedelta64(5, "s")], type=pa.duration("s")),
            }
        ),
        rroot / "p.parquet",
    )
    ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
    hs.create_index(ldf, hst.CoveringIndexConfig("tL", ["k"], ["a"]))
    hs.create_index(rdf, hst.CoveringIndexConfig("tR", ["k"], ["dur"]))
    session.enable_hyperspace()
    got = ldf.join(rdf, on="k", how="left").select("a", "dur").collect()
    assert got["a"].shape[0] == 2
    assert np.isnat(got["dur"]).sum() == 1


class TestFusedJoinAggregate:
    """Global aggregates over a bucketed join compute from match spans
    without materializing the pair expansion; results must equal the
    materialize-then-aggregate path exactly."""

    @pytest.fixture()
    def agg_env(self, session, hs, tmp_path):
        session.conf.set(hst.keys.NUM_BUCKETS, 8)
        rng = np.random.default_rng(51)
        lroot, rroot = tmp_path / "al", tmp_path / "ar"
        lroot.mkdir(), rroot.mkdir()
        n = 2000
        pq.write_table(
            pa.table(
                {
                    "k": rng.integers(0, 100, n).astype(np.int64),
                    "qty": rng.integers(1, 50, n).astype(np.int64),
                    "price": rng.uniform(1, 100, n),
                }
            ),
            lroot / "p.parquet",
        )
        pq.write_table(
            pa.table(
                {
                    "k": rng.integers(0, 100, 300).astype(np.int64),
                    "fx": rng.uniform(0.5, 1.5, 300),
                }
            ),
            rroot / "p.parquet",
        )
        ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("agL", ["k"], ["qty", "price"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("agR", ["k"], ["fx"]))
        session.enable_hyperspace()
        return ldf, rdf

    def _check(self, session, q):
        fused = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
        plain = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        assert sorted(fused.keys()) == sorted(plain.keys())
        for k in fused:
            np.testing.assert_allclose(fused[k], plain[k], rtol=1e-9, err_msg=k)
        return fused

    def test_count_and_sums_both_sides(self, session, agg_env):
        ldf, rdf = agg_env
        j = ldf.join(rdf, on="k")
        q = j.agg(n=("*", "count"), s_left=("price", "sum"), s_right=("fx", "sum"),
                  m_left=("qty", "avg"), m_right=("fx", "avg"))
        got = self._check(session, q)
        assert int(got["n"][0]) > 0

    def test_min_max_left(self, session, agg_env):
        ldf, rdf = agg_env
        q = ldf.join(rdf, on="k").agg(lo=("price", "min"), hi=("price", "max"))
        self._check(session, q)

    def test_min_right_falls_back(self, session, agg_env):
        ldf, rdf = agg_env
        q = ldf.join(rdf, on="k").agg(lo=("fx", "min"))
        self._check(session, q)  # materialized fallback still correct

    def test_fused_path_is_taken(self, session, agg_env):
        from hyperspace_tpu.plan import logical as L

        ldf, rdf = agg_env
        q = ldf.join(rdf, on="k").agg(n=("*", "count"))
        plan = q.optimized_plan()
        joins = L.collect(plan, lambda p: isinstance(p, L.Join))
        aggs = [p for p in L.collect(plan, lambda p: isinstance(p, L.Aggregate))]
        got = D.aggregate_over_bucketed_join(session, aggs[0], joins[0])
        expanded = D.dispatch_bucketed_join(session, joins[0])
        assert int(got["n"][0]) == B.num_rows(expanded)

    def test_empty_join_aggregates(self, session, hs, tmp_path):
        session.conf.set(hst.keys.NUM_BUCKETS, 2)
        lroot, rroot = tmp_path / "el", tmp_path / "er"
        lroot.mkdir(), rroot.mkdir()
        pq.write_table(pa.table({"k": np.array([1], dtype=np.int64), "v": np.array([1.0])}), lroot / "p.parquet")
        pq.write_table(pa.table({"k": np.array([2], dtype=np.int64), "w": np.array([2.0])}), rroot / "p.parquet")
        ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("eL", ["k"], ["v"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("eR", ["k"], ["w"]))
        session.enable_hyperspace()
        q = ldf.join(rdf, on="k").agg(n=("*", "count"), s=("v", "sum"), m=("w", "avg"))
        self._check(session, q)


def test_executor_routes_aggregate_through_fused_path(session, tmp_path, monkeypatch):
    """The executor wiring (not just the device function) must dispatch
    Aggregate-over-Join to the fused path."""
    hs = hst.Hyperspace(session)
    session.conf.set(hst.keys.NUM_BUCKETS, 4)
    lroot, rroot = tmp_path / "wl", tmp_path / "wr"
    lroot.mkdir(), rroot.mkdir()
    pq.write_table(pa.table({"k": np.arange(50, dtype=np.int64), "v": np.arange(50, dtype=np.float64)}), lroot / "p.parquet")
    pq.write_table(pa.table({"k": np.arange(50, dtype=np.int64), "w": np.arange(50, dtype=np.float64)}), rroot / "p.parquet")
    ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
    hs.create_index(ldf, hst.CoveringIndexConfig("wL", ["k"], ["v"]))
    hs.create_index(rdf, hst.CoveringIndexConfig("wR", ["k"], ["w"]))
    session.enable_hyperspace()
    calls = {"n": 0}
    real = D.aggregate_over_bucketed_join

    def counting(sess_, agg_, join_, **kw):
        calls["n"] += 1
        return real(sess_, agg_, join_, **kw)

    monkeypatch.setattr(D, "aggregate_over_bucketed_join", counting)
    got = ldf.join(rdf, on="k").agg(s=("v", "sum")).collect()
    assert calls["n"] == 1, "fused path was not taken by the executor"
    assert got["s"][0] == float(np.arange(50).sum())


def test_empty_join_float_sum_dtype(session, tmp_path):
    """SUM of a float column over an empty join stays float64, matching the
    materialized path."""
    hs = hst.Hyperspace(session)
    session.conf.set(hst.keys.NUM_BUCKETS, 2)
    lroot, rroot = tmp_path / "fl2", tmp_path / "fr2"
    lroot.mkdir(), rroot.mkdir()
    pq.write_table(pa.table({"k": np.array([1], dtype=np.int64), "v": np.array([1.5])}), lroot / "p.parquet")
    pq.write_table(pa.table({"k": np.array([2], dtype=np.int64), "w": np.array([2.5])}), rroot / "p.parquet")
    ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
    hs.create_index(ldf, hst.CoveringIndexConfig("fL2", ["k"], ["v"]))
    hs.create_index(rdf, hst.CoveringIndexConfig("fR2", ["k"], ["w"]))
    session.enable_hyperspace()
    got = ldf.join(rdf, on="k").agg(s=("v", "sum")).collect()
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
    plain = ldf.join(rdf, on="k").agg(s=("v", "sum")).collect()
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
    assert got["s"].dtype == plain["s"].dtype == np.float64
    # SQL: SUM over an empty join is NULL (not 0) on both paths
    assert np.isnan(got["s"][0]) and np.isnan(plain["s"][0])


class TestGroupedFusedJoinAggregate:
    """GROUP BY the join key over a bucketed join fuses via segment
    reductions; results must equal the materialize-then-groupby path
    (compared as key->value maps — output order is not part of the
    contract)."""

    @pytest.fixture()
    def genv(self, session, hs, tmp_path):
        session.conf.set(hst.keys.NUM_BUCKETS, 8)
        rng = np.random.default_rng(61)
        lroot, rroot = tmp_path / "gl", tmp_path / "gr"
        lroot.mkdir(), rroot.mkdir()
        n = 3000
        pq.write_table(
            pa.table(
                {
                    "k": rng.integers(0, 60, n).astype(np.int64),
                    "qty": rng.integers(1, 9, n).astype(np.int64),
                    "price": rng.uniform(1, 50, n),
                }
            ),
            lroot / "p.parquet",
        )
        pq.write_table(
            pa.table(
                {
                    "k": rng.integers(0, 80, 400).astype(np.int64),  # some keys unmatched
                    "fx": rng.uniform(0.5, 1.5, 400),
                }
            ),
            rroot / "p.parquet",
        )
        ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("gL", ["k"], ["qty", "price"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("gR", ["k"], ["fx"]))
        session.enable_hyperspace()
        return ldf, rdf

    def _maps(self, batch, keys=("k",)):
        names = [c for c in batch if c not in keys]
        out = {}
        for i in range(len(batch[names[0]])):
            kk = tuple(batch[k][i] for k in keys)
            out[kk] = tuple(np.round(float(batch[n][i]), 6) for n in names)
        return out

    def test_grouped_parity(self, session, genv):
        ldf, rdf = genv
        q = ldf.join(rdf, on="k").group_by("k").agg(
            n=("*", "count"), s=("price", "sum"), sq=("qty", "sum"),
            a=("fx", "avg"), c=("fx", "count"))
        fused = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
        plain = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        assert self._maps(fused) == self._maps(plain)
        assert fused["sq"].dtype == np.int64  # exact int sums

    def test_grouped_path_is_taken(self, session, genv, monkeypatch):
        ldf, rdf = genv
        calls = {"n": 0}
        real = D._grouped_aggregate_over_join

        def counting(*a, **kw):
            calls["n"] += 1
            return real(*a, **kw)

        monkeypatch.setattr(D, "_grouped_aggregate_over_join", counting)
        ldf.join(rdf, on="k").group_by("k").agg(n=("*", "count")).collect()
        assert calls["n"] == 1

    def test_group_by_non_key_falls_back(self, session, genv):
        ldf, rdf = genv
        q = ldf.join(rdf, on="k").group_by("qty").agg(n=("*", "count"))
        fused = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
        plain = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        assert self._maps(fused, keys=("qty",)) == self._maps(plain, keys=("qty",))


class TestQ3ShapeFusion:
    """Round-5 generalization: GROUP BY join key + right-side payload keys
    with a computed aggregate input — TPC-H q3's exact shape — fuses
    without pair materialization when the right side is unique per key."""

    @pytest.fixture
    def q3env(self, session, hs, tmp_path):
        session.conf.set(hst.keys.NUM_BUCKETS, 8)
        rng = np.random.default_rng(3)
        lroot, rroot = tmp_path / "li3", tmp_path / "o3"
        lroot.mkdir(), rroot.mkdir()
        n = 4000
        base = np.datetime64("1994-01-01")
        pq.write_table(
            pa.table(
                {
                    "l_ok": rng.integers(0, 500, n).astype(np.int64),
                    "l_price": np.round(rng.uniform(10, 1000, n), 2),
                    "l_disc": np.round(rng.uniform(0, 0.1, n), 2),
                }
            ),
            lroot / "p.parquet",
        )
        pq.write_table(
            pa.table(
                {
                    "o_ok": np.arange(500, dtype=np.int64),  # UNIQUE per key
                    "o_date": base + rng.integers(0, 300, 500).astype("timedelta64[D]"),
                    "o_prio": rng.integers(0, 3, 500).astype(np.int64),
                }
            ),
            rroot / "p.parquet",
        )
        ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("q3L", ["l_ok"], ["l_price", "l_disc"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("q3R", ["o_ok"], ["o_date", "o_prio"]))
        session.enable_hyperspace()
        return ldf, rdf

    def _rows(self, batch):
        import datetime

        def norm(v):
            if isinstance(v, float):
                return f"{v:.5f}"
            if isinstance(v, (datetime.date, datetime.datetime)):
                # the fused path preserves the decoded datetime64 unit ([D]);
                # the pandas roundtrip of the materialized path yields ns —
                # same instant, different repr
                import pandas as pd

                return pd.Timestamp(v).isoformat()
            return str(v)

        cols = sorted(batch)
        return sorted(zip(*[[norm(v) for v in batch[c].tolist()] for c in cols]))

    def test_q3_group_keys_and_computed_input_fuse(self, session, q3env):
        ldf, rdf = q3env
        ldf.create_or_replace_temp_view("li3")
        rdf.create_or_replace_temp_view("o3")
        q = session.sql(
            """
            select l_ok, sum(l_price * (1 - l_disc)) as rev, o_date, o_prio,
                   count(*) as n
            from li3 join o3 on l_ok = o_ok
            group by l_ok, o_date, o_prio
            """
        )
        from hyperspace_tpu.exec import trace

        with trace.recording() as rec:
            fused = q.collect()
        assert ("agg", "fused-bucketed-join") in rec, trace.summarize(rec)
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
        plain = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        assert self._rows(fused) == self._rows(plain)

    def test_right_extra_over_non_unique_right_falls_back(self, session, hs, tmp_path):
        session.conf.set(hst.keys.NUM_BUCKETS, 8)
        rng = np.random.default_rng(5)
        lroot, rroot = tmp_path / "nl", tmp_path / "nr"
        lroot.mkdir(), rroot.mkdir()
        pq.write_table(
            pa.table(
                {
                    "a": rng.integers(0, 30, 2000).astype(np.int64),
                    "v": rng.uniform(0, 10, 2000),
                }
            ),
            lroot / "p.parquet",
        )
        pq.write_table(
            pa.table(
                {
                    "b": rng.integers(0, 30, 300).astype(np.int64),  # dupes
                    "tag": rng.integers(0, 4, 300).astype(np.int64),
                }
            ),
            rroot / "p.parquet",
        )
        ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
        hs.create_index(ldf, hst.CoveringIndexConfig("nL", ["a"], ["v"]))
        hs.create_index(rdf, hst.CoveringIndexConfig("nR", ["b"], ["tag"]))
        session.enable_hyperspace()
        q = (
            ldf.join(rdf, on=hst.col("a") == hst.col("b"))
            .group_by("a", "tag")
            .agg(s=("v", "sum"), n=("*", "count"))
        )
        fused = q.collect()  # falls back to materialization internally
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
        plain = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        assert self._rows(fused) == self._rows(plain)

    def test_group_without_join_key_merges_across_buckets(self, session, q3env):
        """Group keys that don't pin the join key recur across buckets;
        the final merge must fold them into one row per group."""
        ldf, rdf = q3env
        q = (
            ldf.join(rdf, on=hst.col("l_ok") == hst.col("o_ok"))
            .group_by("o_prio")
            .agg(s=("l_price", "sum"), n=("*", "count"))
        )
        fused = q.collect()
        assert len(fused["o_prio"]) == len(set(fused["o_prio"].tolist()))
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
        plain = q.collect()
        session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
        assert self._rows(fused) == self._rows(plain)


def test_grouped_fused_repeated_key_granularity(session, tmp_path):
    """Grouping by l.a and r.a of a composite (a,b) join groups COARSER
    than the join-key runs; round 5's final-merge generalization fuses it
    correctly (pre-round-5 this shape was rejected to the materialized
    path). Results must equal the materialized path at the right
    granularity."""
    hs = hst.Hyperspace(session)
    session.conf.set(hst.keys.NUM_BUCKETS, 2)
    rng = np.random.default_rng(71)
    lroot, rroot = tmp_path / "rl2", tmp_path / "rr2"
    lroot.mkdir(), rroot.mkdir()
    n = 400
    pq.write_table(
        pa.table({"a": rng.integers(0, 5, n).astype(np.int64),
                  "b": rng.integers(0, 5, n).astype(np.int64),
                  "v": rng.standard_normal(n)}), lroot / "p.parquet")
    pq.write_table(
        pa.table({"a": rng.integers(0, 5, 60).astype(np.int64),
                  "b": rng.integers(0, 5, 60).astype(np.int64),
                  "w": rng.standard_normal(60)}), rroot / "p.parquet")
    ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
    hs.create_index(ldf, hst.CoveringIndexConfig("rkL", ["a", "b"], ["v"]))
    hs.create_index(rdf, hst.CoveringIndexConfig("rkR", ["a", "b"], ["w"]))
    session.enable_hyperspace()
    j = ldf.join(rdf, on=["a", "b"])
    q = j.group_by("a", "a#r").agg(n=("*", "count"))
    fused = q.collect()
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
    plain = q.collect()
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
    assert fused["n"].shape == plain["n"].shape
    a = {(x, y): int(c) for x, y, c in zip(fused["a"], fused["a#r"], fused["n"])}
    b = {(x, y): int(c) for x, y, c in zip(plain["a"], plain["a#r"], plain["n"])}
    assert a == b


def test_grouped_fused_empty_join_dtypes(session, tmp_path):
    hs = hst.Hyperspace(session)
    session.conf.set(hst.keys.NUM_BUCKETS, 2)
    lroot, rroot = tmp_path / "zl", tmp_path / "zr"
    lroot.mkdir(), rroot.mkdir()
    pq.write_table(pa.table({"k": np.array([1, 3], dtype=np.int64), "v": np.array([5, 6], dtype=np.int64)}), lroot / "p.parquet")
    pq.write_table(pa.table({"k": np.array([2, 4], dtype=np.int64), "w": np.array([7, 8], dtype=np.int64)}), rroot / "p.parquet")
    ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
    hs.create_index(ldf, hst.CoveringIndexConfig("zL", ["k"], ["v"]))
    hs.create_index(rdf, hst.CoveringIndexConfig("zR", ["k"], ["w"]))
    session.enable_hyperspace()
    q = ldf.join(rdf, on="k").group_by("k").agg(s=("v", "sum"))
    fused = q.collect()
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
    plain = q.collect()
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
    assert fused["k"].shape[0] == plain["k"].shape[0] == 0
    assert fused["k"].dtype == plain["k"].dtype == np.int64
    assert fused["s"].dtype == plain["s"].dtype == np.int64


def test_grouped_fused_name_collision_with_key(session, tmp_path):
    """A non-key column sharing a join key's name must not be mistaken for
    the key: group_by over it falls back and returns ITS values."""
    hs = hst.Hyperspace(session)
    session.conf.set(hst.keys.NUM_BUCKETS, 2)
    lroot, rroot = tmp_path / "nc_l", tmp_path / "nc_r"
    lroot.mkdir(), rroot.mkdir()
    pq.write_table(
        pa.table({"a": np.array([1, 2], dtype=np.int64), "v": np.array([0.5, 1.5])}),
        lroot / "p.parquet",
    )
    # right joins on 'b'; its non-key column 'a' holds DIFFERENT values
    pq.write_table(
        pa.table({"b": np.array([1, 2], dtype=np.int64), "a": np.array([100, 200], dtype=np.int64)}),
        rroot / "p.parquet",
    )
    ldf, rdf = session.read_parquet(str(lroot)), session.read_parquet(str(rroot))
    hs.create_index(ldf, hst.CoveringIndexConfig("ncL", ["a"], ["v"]))
    hs.create_index(rdf, hst.CoveringIndexConfig("ncR", ["b"], ["a"]))
    session.enable_hyperspace()
    q = ldf.join(rdf, on=hst.col("a") == hst.col("b")).group_by("a#r").agg(n=("*", "count"))
    fused = q.collect()
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
    plain = q.collect()
    session.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
    assert sorted(fused["a#r"].tolist()) == sorted(plain["a#r"].tolist()) == [100, 200]


_CELLS = np.array(["MAIL", "AIR", "SHIP", "REG AIR", "TRUCK", "FOB", "RAIL", "", "\u00e9", "a\0", "a", None], dtype=object)


@pytest.mark.parametrize("cells", [
    _CELLS[np.random.default_rng(1).integers(0, 7, 5000)],        # strings alone
    _CELLS[np.random.default_rng(2).integers(0, 12, 5000)],       # NULLs, "", a trailing NUL, non-ASCII
    _CELLS[np.random.default_rng(3).integers(0, 12, 1)],
    _CELLS[:0],
    np.array([None, None], dtype=object),
    np.array(["b", "a", "b"]),                                    # a fixed-width unicode column
    np.array([b"b", b"a"]),                                       # bytes: every cell's str()
    np.array(["x", 1, 1.5, None, float("nan"), "1"], dtype=object),  # cells that are neither str nor None
], ids=["strings", "nulls", "one", "empty", "all-null", "unicode", "bytes", "mixed"])
def test_factorize_strings_equals_the_sort_of_all_cells(cells):
    from hyperspace_tpu.ops.encode import _factorize_by_sort, factorize_strings

    got, want = factorize_strings(cells), _factorize_by_sort(cells.astype(object))
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and np.array_equal(g, w)
    codes, uniques, null_mask = got
    assert codes.dtype == np.int64 and np.array_equal(codes < 0, null_mask)
    assert np.array_equal(uniques, np.sort(uniques)) and len(set(uniques.tolist())) == len(uniques)
