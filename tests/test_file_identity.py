"""What identifies a file: the log entry for an index file, a stat for any other.

``exec/file_identity.py`` is the one place the query path asks for a scan
file's ``(path, size, mtime)``. Held here:

- plans over committed indexes make no ``os.stat`` of an index data file;
- the same plans over source files stat every file exactly once a
  ``read_parquet_batch`` call;
- a source file rewritten in place still misses every cache;
- after a refresh, an optimize or a vacuum and rebuild at the same path no
  cache answers from before the commit (compared with pandas);
- ``hs_file_identity_total{source}`` says which of the two answered.
"""

import contextlib
import os
import threading

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.exec import device as D
from hyperspace_tpu.exec import io as IO
from hyperspace_tpu.exec import trace
from hyperspace_tpu.exec.file_identity import file_identities, scan_identity
from hyperspace_tpu.obs.metrics import REGISTRY
from hyperspace_tpu.plan import logical as L
from hyperspace_tpu.serving import QueryServer

NUM_BUCKETS = 8
DAY0 = np.datetime64("1994-01-01")
MODES = np.array(["MAIL", "SHIP", "AIR", "RAIL"], dtype=object)
SEGMENTS = np.array(["BUILDING", "MACHINERY"], dtype=object)


def _tables(n_orders=400, seed=32, key0=0):
    rng = np.random.default_rng(seed)
    ok = np.arange(key0, key0 + n_orders, dtype=np.int64)
    orders = pd.DataFrame(
        {
            "o_orderkey": ok,
            "o_custkey": rng.integers(0, 40, n_orders).astype(np.int64),
            "o_flag": np.where(rng.integers(0, 2, n_orders) == 1, "1-URGENT", "5-LOW").astype(object),
        }
    )
    n_li = 4 * n_orders
    lineitem = pd.DataFrame(
        {
            "l_orderkey": rng.choice(ok, n_li).astype(np.int64),
            "l_shipdate": (DAY0 + rng.integers(0, 60, n_li).astype("timedelta64[D]")),
            "l_shipmode": MODES[rng.integers(0, 4, n_li)],
            "l_quantity": rng.integers(1, 50, n_li).astype(np.int64),
            "l_price": rng.integers(100, 10_000, n_li).astype(np.int64),
        }
    )
    customer = pd.DataFrame(
        {
            "c_custkey": np.arange(40, dtype=np.int64),
            "c_segment": SEGMENTS[np.arange(40) % 2],
        }
    )
    return {"orders": orders, "lineitem": lineitem, "customer": customer}


def _arrow(frame):
    cols = {}
    for c in frame.columns:
        v = frame[c].to_numpy()
        if v.dtype.kind == "M":
            cols[c] = pa.array(v.astype("datetime64[D]"))  # date32
        elif v.dtype == object:
            cols[c] = pa.array(v, pa.string())
        else:
            cols[c] = pa.array(v)
    return pa.table(cols)


def _write(frame, root, parts=2, first=0):
    os.makedirs(root, exist_ok=True)
    table = _arrow(frame)
    step = -(-len(frame) // parts)
    for i in range(parts):
        pq.write_table(table.slice(i * step, step), os.path.join(root, f"part-{first + i:05d}.parquet"))


INDEXES = {
    "li_ok": ("lineitem", ["l_orderkey"], ["l_shipdate", "l_shipmode", "l_quantity", "l_price"]),
    "li_sd": ("lineitem", ["l_shipdate"], ["l_price", "l_quantity", "l_orderkey"]),
    "o_ok": ("orders", ["o_orderkey"], ["o_custkey", "o_flag"]),
}


def _lake(root, tables, indexes=None):
    conf = {
        hst.keys.SYSTEM_PATH: str(root / "indexes"),
        hst.keys.NUM_BUCKETS: NUM_BUCKETS,
        hst.keys.TPU_QUERY_DEVICE_MIN_ROWS: 0,
    }
    sess = hst.Session(conf=conf)
    hst.set_session(sess)
    hs = hst.Hyperspace(sess)
    for name, frame in tables.items():
        _write(frame, str(root / name))
        sess.read_parquet(str(root / name)).create_or_replace_temp_view(name)
    for name, (table, indexed, included) in (indexes or INDEXES).items():
        hs.create_index(
            sess.read_parquet(str(root / table)), hst.CoveringIndexConfig(name, indexed, included)
        )
    sess.enable_hyperspace()
    return sess, hs


# -- the plans (the benchmark's shapes at toy size) and their pandas oracles --

LOOKUP = "SELECT l_price, l_quantity FROM lineitem WHERE l_orderkey = 7"
RANGE_AGG = (
    "SELECT SUM(l_price * l_quantity) AS revenue, COUNT(*) AS n FROM lineitem "
    "WHERE l_shipdate >= date '1994-01-08' AND l_shipdate < date '1994-01-15'"
)
Q12 = (
    "select l_shipmode, sum(case when o_flag = '1-URGENT' then 1 else 0 end) as high, "
    "sum(case when o_flag != '1-URGENT' then 1 else 0 end) as low "
    "from orders, lineitem where o_orderkey = l_orderkey and l_shipmode in ('MAIL', 'SHIP') "
    "group by l_shipmode order by l_shipmode"
)
JOIN_ROWS = (  # the join's rows are wanted, not an aggregate over them: the bucketed join tiers
    "select l_orderkey, l_price, o_flag from orders, lineitem "
    "where o_orderkey = l_orderkey and l_shipmode in ('MAIL', 'SHIP')"
)
Q3 = (
    "select l_orderkey, sum(l_price) as revenue from customer, orders, lineitem "
    "where c_segment = 'BUILDING' and c_custkey = o_custkey and l_orderkey = o_orderkey "
    "and l_shipdate > date '1994-01-20' group by l_orderkey order by l_orderkey"
)
Q6 = (
    "select sum(l_price * l_quantity) as revenue from lineitem "
    "where l_shipdate >= date '1994-01-10' and l_shipdate < date '1994-02-10' and l_quantity < 24"
)


def _oracle(sql, t):
    li, o, c = t["lineitem"], t["orders"], t["customer"]
    day = lambda s: np.datetime64(s)  # noqa: E731
    if sql == LOOKUP:
        return li[li.l_orderkey == 7][["l_price", "l_quantity"]]
    if sql == RANGE_AGG:
        m = li[(li.l_shipdate >= day("1994-01-08")) & (li.l_shipdate < day("1994-01-15"))]
        return pd.DataFrame({"revenue": [(m.l_price * m.l_quantity).sum()], "n": [len(m)]})
    if sql == Q12:
        j = li[li.l_shipmode.isin(["MAIL", "SHIP"])].merge(o, left_on="l_orderkey", right_on="o_orderkey")
        g = j.assign(high=(j.o_flag == "1-URGENT").astype(int), low=(j.o_flag != "1-URGENT").astype(int))
        return g.groupby("l_shipmode", as_index=False)[["high", "low"]].sum().sort_values("l_shipmode")
    if sql == JOIN_ROWS:
        j = li[li.l_shipmode.isin(["MAIL", "SHIP"])].merge(o, left_on="l_orderkey", right_on="o_orderkey")
        return j[["l_orderkey", "l_price", "o_flag"]]
    if sql == Q3:
        j = c[c.c_segment == "BUILDING"].merge(o, left_on="c_custkey", right_on="o_custkey")
        j = j.merge(li[li.l_shipdate > day("1994-01-20")], left_on="o_orderkey", right_on="l_orderkey")
        g = j.groupby("l_orderkey", as_index=False)["l_price"].sum()
        return g.rename(columns={"l_price": "revenue"}).sort_values("l_orderkey")
    if sql == Q6:
        m = li[(li.l_shipdate >= day("1994-01-10")) & (li.l_shipdate < day("1994-02-10")) & (li.l_quantity < 24)]
        return pd.DataFrame({"revenue": [(m.l_price * m.l_quantity).sum()]})
    raise AssertionError(sql)


def _same(got, want, ordered):
    want = want.reset_index(drop=True)
    assert sorted(got) == sorted(want.columns)
    got = pd.DataFrame({c: np.asarray(got[c]) for c in want.columns})
    if not ordered:
        got = got.sort_values(list(want.columns)).reset_index(drop=True)
        want = want.sort_values(list(want.columns)).reset_index(drop=True)
    assert len(got) == len(want)
    for c in want.columns:
        np.testing.assert_array_equal(got[c].to_numpy(), want[c].to_numpy(), err_msg=c)


def _clear_caches():
    IO.clear_io_cache()
    D.clear_device_cache()
    D._RANK_CACHE.clear()
    D._REBUCKET_CACHE.clear()
    D._FOOTER_ROWS_CACHE.clear()


@contextlib.contextmanager
def _stats_recorded(monkeypatch):
    """Every path handed to ``os.stat`` (``os.path.exists``/``getsize`` go
    through it too), from any thread."""
    seen = []
    real = os.stat

    def spy(p, *a, **k):
        seen.append(os.fspath(p) if isinstance(p, (str, os.PathLike)) else p)
        return real(p, *a, **k)

    with monkeypatch.context() as m:
        m.setattr(os, "stat", spy)
        yield seen


def _index_data_files(seen, root):
    under = str(root / "indexes") + os.sep
    return [p for p in seen if isinstance(p, str) and p.startswith(under) and p.endswith(".parquet")]


def _identity_counts():
    return tuple(
        REGISTRY.counter("hs_file_identity_total", "", source=s).value for s in ("log", "stat")
    )


@pytest.fixture(scope="module")
def lake(tmp_path_factory):
    root = tmp_path_factory.mktemp("file_identity")
    tables = _tables()
    sess, hs = _lake(root, tables)
    yield sess, hs, tables, root
    hst.set_session(None)


SHAPES = {
    # name: (sql, served, cold, ordered, dispatch event the bare session must record)
    "point-lookup-cold": (LOOKUP, True, True, False, None),
    "point-lookup-hot": (LOOKUP, True, False, False, None),
    "range-aggregate": (RANGE_AGG, True, True, True, None),
    "bucketed-join-q12": (Q12, False, True, True, ("agg", ("device-join-scan",))),  # since PR 43 the resident join-aggregate
    "bucketed-join-rows": (JOIN_ROWS, False, True, False, ("join", ("device-smj", "host-span-smj"))),
    "generic-merge-join-q3": (Q3, False, True, True, ("join", ("generic-merge",))),
    "scan-aggregate-q6": (Q6, False, True, True, None),
}


@pytest.mark.parametrize("shape", list(SHAPES))
def test_plans_over_committed_indexes_stat_no_index_file(lake, monkeypatch, shape):
    sess, _hs, tables, root = lake
    hst.set_session(sess)
    sql, served, cold, ordered, event = SHAPES[shape]
    assert L.collect(sess.sql(sql).optimized_plan(), lambda p: isinstance(p, L.IndexScan))
    if shape == "generic-merge-join-q3":
        # nothing may broadcast: the joins fall through to the pandas merge
        monkeypatch.setattr(type(sess.conf), "join_broadcast_max_bytes", property(lambda self: 0))
    before = _identity_counts()
    if served:
        with QueryServer(sess, workers=2) as srv:
            if not cold:
                srv.query(sql)
            else:
                _clear_caches()
            with _stats_recorded(monkeypatch) as seen:
                got = srv.query(sql)
    else:
        _clear_caches()
        with _stats_recorded(monkeypatch) as seen, trace.recording() as events:
            got = sess.sql(sql).collect()
        if event is not None:
            kind, details = event
            assert any(k == kind and v in details for k, v in events), trace.summarize(events)
    assert _index_data_files(seen, root) == []
    _same(got, _oracle(sql, tables), ordered)
    log, stat = (a - b for a, b in zip(_identity_counts(), before))
    if cold:
        assert log > 0  # the seam was asked, and answered from the log
    if shape != "generic-merge-join-q3":  # q3 reads customer's source files
        assert stat == 0


# -- source files: one stat a file a read_parquet_batch call -----------------


@pytest.fixture()
def stats_in_reads(monkeypatch):
    """Wraps ``read_parquet_batch`` and ``os.stat``: for every call, the files
    it was given and the paths stat'ed on the calling thread while inside it
    (the seam runs before the decode pool is asked for anything)."""
    calls = []
    here = threading.local()
    real_read, real_stat = IO.read_parquet_batch, os.stat

    def stat(p, *a, **k):
        cur = getattr(here, "cur", None)
        if cur is not None:
            cur.append(p)
        return real_stat(p, *a, **k)

    def read(files, *a, **k):
        outer, here.cur = getattr(here, "cur", None), []
        try:
            return real_read(files, *a, **k)
        finally:
            calls.append((list(files), here.cur))
            here.cur = outer

    monkeypatch.setattr(os, "stat", stat)
    monkeypatch.setattr(IO, "read_parquet_batch", read)
    return calls


@pytest.mark.parametrize("shape", ["point-lookup", "range-aggregate", "join-q12", "join-q3", "scan-aggregate-q6"])
@pytest.mark.parametrize("cache", ["cold", "hot"])
def test_plans_over_source_files_stat_each_file_once_a_read(lake, stats_in_reads, shape, cache):
    sess, _hs, tables, root = lake
    hst.set_session(sess)
    sql = {"point-lookup": LOOKUP, "range-aggregate": RANGE_AGG, "join-q12": Q12,
           "join-q3": Q3, "scan-aggregate-q6": Q6}[shape]
    sess.disable_hyperspace()
    try:
        _clear_caches()
        if cache == "hot":
            sess.sql(sql).collect()
        del stats_in_reads[:]
        before = _identity_counts()
        got = sess.sql(sql).collect()
        log, stat = (a - b for a, b in zip(_identity_counts(), before))
    finally:
        sess.enable_hyperspace()
    _same(got, _oracle(sql, tables), ordered=shape != "point-lookup")
    assert stats_in_reads  # source files were read
    for files, stat_paths in stats_in_reads:
        assert sorted(p for p in stat_paths if p in files) == sorted(files)
    assert log == 0 and stat >= sum(len(files) for files, _ in stats_in_reads)


@pytest.mark.parametrize("reader", ["native-rg-scan", "per-file"])
@pytest.mark.parametrize("predicate", ["whole", "pruned"])
def test_a_read_asks_for_each_key_once_whoever_supplies_it(tmp_path, monkeypatch, reader, predicate):
    """Concat key, cached list and cache puts share one identity a file: a
    cold and a hot read of three files make three stats each, and none when
    the caller hands over what a log recorded."""
    from hyperspace_tpu.plan.expr import col, lit

    if reader == "per-file":
        monkeypatch.setenv("HS_NATIVE_RG", "0")
    files = []
    for i in range(3):
        f = str(tmp_path / f"f{i}.parquet")
        pq.write_table(pa.table({"x": np.arange(i * 100, i * 100 + 100, dtype=np.int64)}), f, row_group_size=50)
        files.append(f)
    pred = (col("x") >= lit(120)) if predicate == "pruned" else None
    want = np.arange(300, dtype=np.int64)
    IO.clear_io_cache()
    for _ in ("cold", "hot"):
        with _stats_recorded(monkeypatch) as seen:
            got = IO.read_parquet_batch(files, ["x"], predicate=pred)
        assert sorted(p for p in seen if p in files) == files
        np.testing.assert_array_equal(got["x"], want[100:] if predicate == "pruned" else want)
    committed = {k[0]: k for k in file_identities(files)}
    IO.clear_io_cache()
    for _ in ("cold", "hot"):
        with _stats_recorded(monkeypatch) as seen:
            IO.read_parquet_batch(files, ["x"], predicate=pred, committed=committed)
        assert [p for p in seen if p in files] == []


# -- a rewritten source file misses every cache -------------------------------


@pytest.mark.parametrize("path", ["host-decode-cache", "device-column-cache", "served-build-side-cache"])
def test_in_place_rewrite_of_a_source_file_misses_every_cache(tmp_path, path):
    root = tmp_path
    tables = _tables(n_orders=60, seed=5)
    sess = hst.Session(conf={hst.keys.SYSTEM_PATH: str(root / "indexes"), hst.keys.TPU_QUERY_DEVICE_MIN_ROWS: 0})
    hst.set_session(sess)
    try:
        for name, frame in tables.items():
            _write(frame, str(root / name), parts=1)
            sess.read_parquet(str(root / name)).create_or_replace_temp_view(name)
        sql = {
            "host-decode-cache": "SELECT o_orderkey, o_custkey FROM orders",
            "device-column-cache": "SELECT o_orderkey FROM orders WHERE o_custkey >= 10 AND o_custkey < 30",
            "served-build-side-cache": (
                "select o_orderkey, c_segment from customer, orders where c_custkey = o_custkey "
                "order by o_orderkey"
            ),
        }[path]

        def oracle(t):
            o, c = t["orders"], t["customer"]
            if path == "host-decode-cache":
                return o[["o_orderkey", "o_custkey"]]
            if path == "device-column-cache":
                return o[(o.o_custkey >= 10) & (o.o_custkey < 30)][["o_orderkey"]]
            j = c.merge(o, left_on="c_custkey", right_on="o_custkey").sort_values("o_orderkey")
            return j[["o_orderkey", "c_segment"]]

        _clear_caches()
        with contextlib.ExitStack() as stack:
            ask = lambda: sess.sql(sql).collect()  # noqa: E731
            if path == "served-build-side-cache":
                srv = stack.enter_context(QueryServer(sess, workers=1))
                ask = lambda: srv.query(sql)  # noqa: E731
            _same(ask(), oracle(tables), ordered=path == "served-build-side-cache")
            _same(ask(), oracle(tables), ordered=path == "served-build-side-cache")  # from the caches
            # the same paths, other rows: customer's segments flip, orders get other customers
            changed = _tables(n_orders=60, seed=6)
            changed["customer"]["c_segment"] = SEGMENTS[(np.arange(40) + 1) % 2]
            for name in ("orders", "customer"):
                _write(changed[name], str(root / name), parts=1)
            assert not oracle(changed).reset_index(drop=True).equals(oracle(tables).reset_index(drop=True))
            _same(ask(), oracle(changed), ordered=path == "served-build-side-cache")
    finally:
        hst.set_session(None)


# -- commits: no cache answers from before one --------------------------------

# a join on two key columns rides the shared rank encodings (the rank cache)
TWO_KEYS = (
    "select count(*) as n, sum(l_price) as total from lineitem, orders "
    "where l_orderkey = o_orderkey and l_quantity = o_custkey"
)
TWO_KEY_INDEXES = {
    "li_2k": ("lineitem", ["l_orderkey", "l_quantity"], ["l_price"]),
    "o_2k": ("orders", ["o_orderkey", "o_custkey"], ["o_flag"]),
}


def _oracle_after(sql, t):
    if sql == TWO_KEYS:
        j = t["lineitem"].merge(
            t["orders"], left_on=["l_orderkey", "l_quantity"], right_on=["o_orderkey", "o_custkey"]
        )
        return pd.DataFrame({"n": [len(j)], "total": [j.l_price.sum()]})
    return _oracle(sql, t)


def _commit(hs, sess, root, how, tables, indexes):
    """Changes the lake and commits it the given way; returns the tables the
    indexes describe afterwards."""
    import shutil

    if how == "vacuum-and-rebuild":
        # other rows of the same shape: the same file names, sizes that may well be equal
        after = dict(tables, **{k: v for k, v in _tables(seed=78).items() if k != "customer"})
        for name in ("orders", "lineitem"):
            shutil.rmtree(str(root / name))
            _write(after[name], str(root / name))
        for name, (table, indexed, included) in indexes.items():
            hs.delete_index(name)
            hs.vacuum_index(name)
            hs.create_index(
                sess.read_parquet(str(root / table)), hst.CoveringIndexConfig(name, indexed, included)
            )
    else:
        more = _tables(n_orders=100, seed=77, key0=400)
        more["lineitem"]["l_price"] *= 4  # an answer from before the commit cannot equal the new one
        for name in ("orders", "lineitem"):
            _write(more[name], str(root / name), parts=1, first=7)
        for name in indexes:
            hs.refresh_index(name, "full" if how == "refresh-full" else "incremental")
            if how == "optimize":
                hs.optimize_index(name, "full")
        after = dict(
            tables,
            orders=pd.concat([tables["orders"], more["orders"]], ignore_index=True),
            lineitem=pd.concat([tables["lineitem"], more["lineitem"]], ignore_index=True),
        )
    for name in ("orders", "lineitem"):  # the views list the files as they are now
        sess.read_parquet(str(root / name)).create_or_replace_temp_view(name)
    return after


@pytest.mark.parametrize("how", ["refresh-incremental", "refresh-full", "optimize", "vacuum-and-rebuild"])
def test_no_cache_answers_from_before_a_commit(tmp_path, monkeypatch, how):
    """Warm before the commit, right after it: the host decode cache (every
    plan), the footer-rows memo and the device key matrices (q12), the rank
    cache (the two-key join), the served build-side cache (q3)."""
    tables = _tables()
    indexes = dict(INDEXES, **TWO_KEY_INDEXES)
    sess, hs = _lake(tmp_path, tables, indexes)
    plans = [(LOOKUP, False), (RANGE_AGG, True), (Q12, True), (Q6, True), (Q3, True), (TWO_KEYS, True)]
    try:
        _clear_caches()
        with QueryServer(sess, workers=2) as srv:
            for _ in range(2):  # the second round is answered from the caches
                for sql, ordered in plans:
                    _same(srv.query(sql), _oracle_after(sql, tables), ordered)
                    _same(sess.sql(sql).collect(), _oracle_after(sql, tables), ordered)
            assert len(D._FOOTER_ROWS_CACHE) > 0 and len(D._RANK_CACHE.keys()) > 0
            assert len(IO._io_cache.keys()) > 0 and srv.join_build_cache.hits > 0
            after = _commit(hs, sess, tmp_path, how, tables, indexes)
            before = _identity_counts()
            with _stats_recorded(monkeypatch) as seen:
                for sql, ordered in plans:
                    _same(srv.query(sql), _oracle_after(sql, after), ordered)
                    _same(sess.sql(sql).collect(), _oracle_after(sql, after), ordered)
            log, stat = (a - b for a, b in zip(_identity_counts(), before))
        assert _index_data_files(seen, tmp_path) == []
        sources = {p for p in seen if isinstance(p, str) and p.startswith(str(tmp_path / "customer"))}
        assert log > 0 and stat >= len(sources) > 0  # q3 reads customer's source files
    finally:
        hst.set_session(None)


def test_counter_says_log_for_index_files_and_stat_for_source_files(lake):
    sess, _hs, _tables_, root = lake
    hst.set_session(sess)
    plan = sess.sql(LOOKUP).optimized_plan()
    (scan,) = L.collect(plan, lambda p: isinstance(p, L.IndexScan))
    before = _identity_counts()
    assert len(scan_identity(scan)) == len(scan.files)
    assert tuple(a - b for a, b in zip(_identity_counts(), before)) == (len(scan.files), 0)
    source = L.FileScan([str(root / "orders" / "part-00000.parquet"), str(root / "nowhere.parquet")], "parquet", ["o_orderkey"])
    before = _identity_counts()
    assert scan_identity(source) is None  # a file that cannot be stat'ed: no identity, no caching
    assert tuple(a - b for a, b in zip(_identity_counts(), before)) == (0, 2)
