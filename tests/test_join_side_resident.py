"""A bucketed join side is merged once an index version and kept.

``exec/device._read_buckets`` hands the join tiers slices of ONE contiguous,
bucket-ordered, key-sorted array a column, held in the host cache and keyed on
the index files' identities, the sort keys and the file column. Held here, on
indexes whose buckets really hold several files (a small ``batchRows``, and an
incremental refresh in merge mode):

- every answer equals the pandas oracle, and equals array for array, dtype for
  dtype and row for row what the per-bucket reading gives (``_per_bucket_side``
  below: per bucket concat, stable sort, filter), through whichever tier;
- the second execution finds both sides resident and sorts nothing; other
  literals share the entries;
- no commit is answered from an earlier side, and ``purge_io_cache`` drops it;
- the streamed tier keeps no side;
- a side is held once, read-only.
"""

import os
import shutil

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.exec import batch as B
from hyperspace_tpu.exec import device as D
from hyperspace_tpu.exec import io as IO
from hyperspace_tpu.exec import trace
from hyperspace_tpu.exec.file_identity import committed_keys
from hyperspace_tpu.obs.metrics import REGISTRY
from hyperspace_tpu.ops.encode import sort_key_int64
from hyperspace_tpu.plan import logical as L
from hyperspace_tpu.plan.expr import as_bool_mask, col

pytestmark = pytest.mark.join

NUM_BUCKETS = 8
BATCH_ROWS = 500  # fact has 2,400 rows: five build chunks, five files a bucket
DAY0 = np.datetime64("1995-01-01")
MODES = np.array(["MAIL", "SHIP", "AIR", None], dtype=object)


def _tables(seed=35, n_fact=2400, n_dim=240, key0=0):
    rng = np.random.default_rng(seed)
    dk = np.arange(key0, key0 + n_dim, dtype=np.int64)
    dim = pd.DataFrame(
        {
            "dk": dk,
            "dk2": dk % 5,
            "dd": DAY0 + (dk % 80).astype("timedelta64[D]"),
            "ds": np.array([f"s{v % 60:03d}" for v in dk], dtype=object),
            "w": rng.integers(0, 1000, n_dim).astype(np.int64),
        }
    )
    fk = rng.integers(key0, key0 + n_dim + 60, n_fact).astype(np.int64)  # some match nothing
    n_null = np.where(fk < key0 + 2, np.nan, rng.integers(0, 9, n_fact))  # two keys: two buckets at most
    fact = pd.DataFrame(
        {
            "fk": fk,
            "fk2": rng.integers(0, 5, n_fact).astype(np.int64),
            "fd": DAY0 + rng.integers(0, 100, n_fact).astype("timedelta64[D]"),
            "fs": np.array([f"s{v:03d}" for v in rng.integers(0, 75, n_fact)], dtype=object),
            "v": rng.integers(0, 1000, n_fact).astype(np.int64),
            "m": MODES[rng.integers(0, 4, n_fact)],
            # an int column with NULLs in two buckets' files at most: those
            # buckets decode as float64, the others as int64
            "n": n_null,
        }
    )
    # three keys only: most buckets of these two indexes have no file
    few_f = pd.DataFrame({"ek": rng.integers(1, 4, 600).astype(np.int64), "ev": np.arange(600, dtype=np.int64)})
    few_d = pd.DataFrame({"gk": np.arange(1, 4, dtype=np.int64), "gw": np.arange(3, dtype=np.int64)})
    return {"fact": fact, "dim": dim, "few_f": few_f, "few_d": few_d}


def _arrow(frame):
    cols = {}
    for c in frame.columns:
        v = frame[c].to_numpy()
        if v.dtype.kind == "M":
            cols[c] = pa.array(v.astype("datetime64[D]"))  # date32
        elif v.dtype == object:
            cols[c] = pa.array(v, pa.string(), from_pandas=True)  # None or NaN -> NULL
        elif c == "n":
            cols[c] = pa.array(v, pa.int64(), from_pandas=True)  # NaN -> NULL
        else:
            cols[c] = pa.array(v)
    return pa.table(cols)


def _write(frame, root, name="part-00000.parquet"):
    os.makedirs(root, exist_ok=True)
    pq.write_table(_arrow(frame), os.path.join(root, name))


INDEXES = {
    "f_k": ("fact", ["fk"], ["v", "m", "n", "fd"]),
    "d_k": ("dim", ["dk"], ["w", "ds"]),
    "f_d": ("fact", ["fd"], ["v", "m"]),
    "d_d": ("dim", ["dd"], ["w"]),
    "f_s": ("fact", ["fs"], ["v", "m"]),
    "d_s": ("dim", ["ds"], ["w"]),
    "f_2k": ("fact", ["fk", "fk2"], ["v", "m"]),
    "d_2k": ("dim", ["dk", "dk2"], ["w"]),
    "e_k": ("few_f", ["ek"], ["ev"]),
    "g_k": ("few_d", ["gk"], ["gw"]),
}


def _lake(root, tables, indexes=INDEXES):
    sess = hst.Session(
        conf={
            hst.keys.SYSTEM_PATH: str(root / "indexes"),
            hst.keys.NUM_BUCKETS: NUM_BUCKETS,
            hst.keys.TPU_BUILD_BATCH_ROWS: BATCH_ROWS,
            hst.keys.TPU_QUERY_DEVICE_MIN_ROWS: 0,
        }
    )
    hst.set_session(sess)
    hs = hst.Hyperspace(sess)
    for name, frame in tables.items():
        _write(frame, str(root / name))
    for name, (table, indexed, included) in indexes.items():
        hs.create_index(
            sess.read_parquet(str(root / table)), hst.CoveringIndexConfig(name, indexed, included)
        )
    sess.enable_hyperspace()
    return sess, hs


def _clear_caches():
    IO.clear_io_cache()
    D.clear_device_cache()
    D._RANK_CACHE.clear()
    D._REBUCKET_CACHE.clear()
    D._FOOTER_ROWS_CACHE.clear()


def _side_counts():
    return tuple(
        REGISTRY.counter("hs_join_side_total", "", result=r).value for r in ("resident", "built")
    )


def _side_keys():
    return [k for k in IO._io_cache.keys() if k[0] == "join-side"]


# -- the reference: what the per-bucket reading did ---------------------------


def _per_bucket_side(session, node, columns, sort_keys):
    """``_side_buckets`` as it was before the side became resident, for the
    shapes of this file: per bucket, concat the files, stable sort on the
    keys where there are several, evaluate the Filter, mask."""
    while isinstance(node, L.Project):
        node = node.child
    if isinstance(node, L.Filter):
        inner = list(dict.fromkeys(list(columns) + list(node.condition.references())))
        out = {}
        for b, batch in _per_bucket_side(session, node.child, inner, sort_keys).items():
            kept = B.mask_rows(batch, as_bool_mask(node.condition.eval(batch)))
            out[b] = {c: kept[c] for c in columns}
        return out
    assert isinstance(node, L.IndexScan), type(node)
    from hyperspace_tpu.indexes.covering import bucket_of_file

    per_bucket = {}
    for f in node.files:
        per_bucket.setdefault(bucket_of_file(f), []).append(f)
    file_cols = [node.file_column_of(c) for c in columns]
    out = {}
    for b, files in per_bucket.items():
        got = IO.read_parquet_batch(files, file_cols, committed=committed_keys(node))
        batch = {c: got[fc] for c, fc in zip(columns, file_cols)}
        if len(files) > 1:
            order = np.lexsort([sort_key_int64(batch[k]) for k in sort_keys][::-1])  # stable
            batch = B.take(batch, order)
        out[b] = batch
    return out


def _assert_same_arrays(got, want):
    assert list(got) == list(want)
    for c in want:
        assert got[c].dtype == want[c].dtype, (c, got[c].dtype, want[c].dtype)
        if want[c].dtype == object:  # NULLs are None or NaN: the same kind in the same places
            null = pd.isna(want[c])
            assert [type(x) for x in got[c][null]] == [type(x) for x in want[c][null]], c
            np.testing.assert_array_equal(got[c][~null], want[c][~null], err_msg=c)
        else:
            np.testing.assert_array_equal(got[c], want[c], err_msg=c)


def _assert_same_buckets(got, want):
    assert list(got) == list(want)  # the same buckets, in the same order
    for b in want:
        _assert_same_arrays(got[b], want[b])


def _norm(df):
    return sorted(
        tuple("NULL" if x is None or x != x else str(x) for x in row)
        for row in df.itertuples(index=False)
    )


def _join_of(plan):
    (join,) = L.collect(plan, lambda p: isinstance(p, L.Join))
    return join


def _run(sess, q, monkeypatch):
    """Collect ``q`` through the resident sides and through the per-bucket
    reference; both went through a bucketed tier. Returns the answer."""
    _clear_caches()
    before = _side_counts()
    with trace.recording() as events:
        got = q.collect()
    assert any(e in (("join", "device-smj"), ("join", "host-span-smj")) for e in events), trace.summarize(events)
    assert sum(_side_counts()) - sum(before) == 2  # one count a side
    _clear_caches()
    with monkeypatch.context() as m:
        m.setattr(D, "_side_buckets", _per_bucket_side)
        want = q.collect()
    _assert_same_arrays(got, want)
    # and the sides themselves, bucket for bucket
    join = _join_of(q.optimized_plan())
    lside, rside, lkeys, rkeys = D.join_sides_compatible(join)
    for side, keys in ((lside, lkeys), (rside, rkeys)):
        cols = list(side.output_columns)
        _assert_same_buckets(
            D._side_buckets(sess, side, cols, keys), _per_bucket_side(sess, side, cols, keys)
        )
    return got


@pytest.fixture(scope="module")
def lake(tmp_path_factory):
    root = tmp_path_factory.mktemp("join_side")
    tables = _tables()
    sess, _hs = _lake(root, tables)
    # the premise of this file: the fact indexes' buckets hold several files
    fr = _frames(sess, root)
    join = _join_of(fr["fact"].join(fr["dim"], on=col("fk") == col("dk")).select("fk", "v", "w").optimized_plan())
    (scan,) = L.collect(join.left, lambda p: isinstance(p, L.IndexScan))
    assert min(len(fs) for fs in D._bucket_files(scan).values()) >= 4
    yield sess, tables, root
    hst.set_session(None)


def _frames(sess, root):
    return {t: sess.read_parquet(str(root / t)) for t in ("fact", "dim", "few_f", "few_d")}


KEYS = {
    # name: (join condition, pandas left_on, right_on, selected columns)
    "int": (col("fk") == col("dk"), ["fk"], ["dk"], ["fk", "v", "m", "n", "dk", "w"]),
    "date": (col("fd") == col("dd"), ["fd"], ["dd"], ["fd", "v", "dd", "w"]),
    "string": (col("fs") == col("ds"), ["fs"], ["ds"], ["fs", "v", "ds", "w"]),
    "composite": (
        (col("fk") == col("dk")) & (col("fk2") == col("dk2")),
        ["fk", "fk2"], ["dk", "dk2"], ["fk", "fk2", "v", "dk", "dk2", "w"],
    ),
}


@pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
@pytest.mark.parametrize("key", list(KEYS))
def test_join_equals_oracle_and_per_bucket_reference(lake, monkeypatch, key, how):
    sess, tables, root = lake
    hst.set_session(sess)
    on, left_on, right_on, cols = KEYS[key]
    fr = _frames(sess, root)
    q = fr["fact"].join(fr["dim"], on=on, how=how).select(*cols)
    got = _run(sess, q, monkeypatch)
    want = tables["fact"].merge(tables["dim"], left_on=left_on, right_on=right_on, how=how)[cols]
    assert _norm(pd.DataFrame(got)[cols]) == _norm(want)


FILTERS = {
    # name: (fact filter, the same in pandas, dim filter, the same in pandas)
    "in-list-with-null": (
        col("m").isin("MAIL", "SHIP", None), lambda f: f[f.m.isin(["MAIL", "SHIP"])], None, None),
    "empties-buckets": (col("fk") < 3, lambda f: f[f.fk < 3], None, None),
    "empties-every-bucket": (col("v") < 0, lambda f: f[f.v < 0], None, None),
    "filter-over-filter": (
        (col("v") >= 100, col("fd") < np.datetime64("1995-03-01")),
        lambda f: f[(f.v >= 100) & (f.fd < np.datetime64("1995-03-01"))], None, None),
    "both-sides": (
        col("m") == "AIR", lambda f: f[f.m == "AIR"], col("w") < 500, lambda d: d[d.w < 500]),
    "null-int-column": (col("n") >= 4, lambda f: f[f.n >= 4], None, None),
    "single-file-side-only": (None, None, col("w") >= 900, lambda d: d[d.w >= 900]),
}


@pytest.mark.parametrize("how", ["inner", "outer"])
@pytest.mark.parametrize("name", list(FILTERS))
def test_side_filter_evaluated_once_equals_per_bucket_reference(lake, monkeypatch, name, how):
    sess, tables, root = lake
    hst.set_session(sess)
    ff, ff_pd, df, df_pd = FILTERS[name]
    fr = _frames(sess, root)
    fact, dim = fr["fact"], fr["dim"]
    for cond in ff if isinstance(ff, tuple) else (ff,):
        if cond is not None:
            fact = fact.filter(cond)
    if df is not None:
        dim = dim.filter(df)
    cols = ["fk", "v", "m", "n", "dk", "w"]
    q = fact.join(dim, on=col("fk") == col("dk"), how=how).select(*cols)
    assert L.collect(_join_of(q.optimized_plan()), lambda p: isinstance(p, L.Filter))
    got = _run(sess, q, monkeypatch)
    f_pd = ff_pd(tables["fact"]) if ff_pd else tables["fact"]
    d_pd = df_pd(tables["dim"]) if df_pd else tables["dim"]
    want = f_pd.merge(d_pd, left_on="fk", right_on="dk", how=how)[cols]
    assert _norm(pd.DataFrame(got)[cols]) == _norm(want)


def test_filter_with_null_comparison_keeps_nothing_in_any_bucket(lake, monkeypatch):
    """A comparison with a scalar subquery over no rows is NULL for every
    row (NullableBool): the side keeps every bucket, each empty."""
    sess, tables, root = lake
    hst.set_session(sess)
    fr = _frames(sess, root)
    nothing = fr["dim"].filter(col("w") < 0).agg(top=("w", "max")).as_scalar()
    q = fr["fact"].filter(col("v") > nothing).join(fr["dim"], on=col("fk") == col("dk"), how="right")
    q = q.select("fk", "v", "dk", "w")
    got = _run(sess, q, monkeypatch)
    assert len(got["dk"]) == len(tables["dim"]) and np.isnan(got["v"].astype(float)).all()
    join = _join_of(q.optimized_plan())
    side = D._side_buckets(sess, join.left, ["fk", "v"], ["fk"])
    assert sorted(side) == list(range(NUM_BUCKETS)) and all(B.num_rows(b) == 0 for b in side.values())


@pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
def test_index_with_empty_buckets(lake, monkeypatch, how):
    sess, tables, root = lake
    hst.set_session(sess)
    fr = _frames(sess, root)
    q = fr["few_f"].filter(col("ev") % 2 == 0).join(fr["few_d"], on=col("ek") == col("gk"), how=how)
    got = _run(sess, q, monkeypatch)
    join = _join_of(q.optimized_plan())
    side = D._side_buckets(sess, join.left, ["ek", "ev"], ["ek"])
    assert 0 < len(side) < NUM_BUCKETS  # a bucket without a file is absent
    f = tables["few_f"]
    want = f[f.ev % 2 == 0].merge(tables["few_d"], left_on="ek", right_on="gk", how=how)
    assert _norm(pd.DataFrame(got)[list(want.columns)]) == _norm(want)


def test_per_bucket_dtypes_survive_the_contiguous_column(lake):
    """``n`` holds NULLs in the files of two buckets at most: the views keep
    int64 wherever the per-bucket read gives int64."""
    sess, _tables_, root = lake
    hst.set_session(sess)
    fr = _frames(sess, root)
    join = _join_of(fr["fact"].join(fr["dim"], on=col("fk") == col("dk")).select("fk", "n", "w").optimized_plan())
    _clear_caches()
    side = D._side_buckets(sess, join.left, ["fk", "n"], ["fk"])
    kinds = {b: batch["n"].dtype.kind for b, batch in side.items()}
    assert set(kinds.values()) == {"i", "f"}, kinds
    _assert_same_buckets(side, _per_bucket_side(sess, join.left, ["fk", "n"], ["fk"]))


# -- merged once, shared by every literal -------------------------------------


def test_second_execution_is_resident_and_sorts_nothing(lake, monkeypatch):
    sess, tables, root = lake
    hst.set_session(sess)
    fr = _frames(sess, root)

    def query(mode, top):
        return (
            fr["fact"].filter((col("m") == mode) & (col("v") < top))
            .join(fr["dim"], on=col("fk") == col("dk")).select("fk", "v", "m", "w")
        )

    def oracle(mode, top):
        f = tables["fact"]
        return f[(f.m == mode) & (f.v < top)].merge(tables["dim"], left_on="fk", right_on="dk")[
            ["fk", "v", "m", "w"]
        ]

    sorts = []
    real = D._sort_bucket
    monkeypatch.setattr(D, "_sort_bucket", lambda *a, **k: sorts.append(1) or real(*a, **k))
    _clear_caches()
    before = _side_counts()
    first = query("MAIL", 800).collect()
    assert tuple(a - b for a, b in zip(_side_counts(), before)) == (0, 2)
    assert len(sorts) == NUM_BUCKETS  # the fact side's buckets; dim's hold one file each
    entries = sorted(map(repr, _side_keys()))
    assert entries

    del sorts[:]
    before = _side_counts()
    again = query("MAIL", 800).collect()
    other = query("SHIP", 300).collect()  # other literals, the same entries
    assert tuple(a - b for a, b in zip(_side_counts(), before)) == (4, 0)
    assert sorts == []
    assert sorted(map(repr, _side_keys())) == entries
    _assert_same_arrays(again, first)
    assert _norm(pd.DataFrame(first)) == _norm(oracle("MAIL", 800))
    assert _norm(pd.DataFrame(other)) == _norm(oracle("SHIP", 300))

    # a column no earlier query read is merged now, beside the resident ones
    before = _side_counts()
    wider = fr["fact"].join(fr["dim"], on=col("fk") == col("dk")).select("fk", "fd", "w").collect()
    assert tuple(a - b for a, b in zip(_side_counts(), before)) == (1, 1)
    assert len(_side_keys()) == len(entries) + 1
    want = tables["fact"].merge(tables["dim"], left_on="fk", right_on="dk")[["fk", "fd", "w"]]
    assert _norm(pd.DataFrame(wider)) == _norm(want)


def test_no_cache_key_holds_a_predicate_or_a_literal(lake):
    sess, _tables_, root = lake
    hst.set_session(sess)
    fr = _frames(sess, root)
    _clear_caches()
    fr["fact"].filter((col("m") == "MAIL") & (col("v") < 777)).join(
        fr["dim"].filter(col("w") != 31337), on=col("fk") == col("dk")
    ).select("fk", "v", "w").collect()
    keys = _side_keys()
    assert keys
    for key in keys:
        tag, identity, sort_cols, column = key
        assert all(len(k) == 3 and os.path.isabs(k[0]) for k in identity)  # (path, size, mtime_ns)
        assert sort_cols in (("fk",), ("dk",)) and isinstance(column, str)
        assert "MAIL" not in repr(key) and "777" not in repr((sort_cols, column)) and "31337" not in repr(key)


# -- held once, read-only ------------------------------------------------------


def test_a_side_is_held_once_and_read_only(lake):
    sess, _tables_, root = lake
    hst.set_session(sess)
    fr = _frames(sess, root)
    q = fr["fact"].join(fr["dim"], on=col("fk") == col("dk")).select("fk", "v", "m", "w")
    join = _join_of(q.optimized_plan())
    lside, rside, lkeys, rkeys = D.join_sides_compatible(join)
    sides = [(lside, ["fk", "v", "m"], lkeys), (rside, ["dk", "w"], rkeys)]

    # what the per-bucket reading leaves behind: every file's batch, and a
    # concatenation for every bucket of several files
    _clear_caches()
    merged_bytes = 0
    for side, cols, keys in sides:
        for batch in _per_bucket_side(sess, side, cols, keys).values():
            merged_bytes += IO._batch_nbytes(batch)
    kinds = [k[0] == "concat" for k in IO._io_cache.keys()]
    assert any(kinds) and not all(kinds) and IO._io_cache.total_bytes > merged_bytes

    _clear_caches()
    q.collect()
    assert {k[0] for k in IO._io_cache.keys()} == {"join-side"}  # nothing of the build's reads beside it
    assert IO._io_cache.total_bytes <= 1.1 * merged_bytes  # one merged copy, and the offsets

    for side, cols, keys in sides:
        for batch in D._side_buckets(sess, side, cols, keys).values():
            assert not any(a.flags.writeable for a in batch.values())
    for key in _side_keys():
        assert not any(a.flags.writeable for a in IO._io_cache.get(key).values())


# -- the streamed tier keeps no side -------------------------------------------


def test_streamed_tier_builds_no_side(lake):
    sess, tables, root = lake
    hst.set_session(sess)
    fr = _frames(sess, root)
    q = fr["fact"].filter(col("v") < 500).join(fr["dim"], on=col("fk") == col("dk")).select("fk", "v", "w")
    sess.conf.set(hst.keys.EXEC_STREAM_JOIN_MIN_BYTES, 1)
    try:
        _clear_caches()
        before = _side_counts()
        with trace.recording() as events:
            got = q.collect()
    finally:
        sess.conf.set(
            hst.keys.EXEC_STREAM_JOIN_MIN_BYTES, hst.config.DEFAULTS[hst.keys.EXEC_STREAM_JOIN_MIN_BYTES]
        )
    assert ("join", "host-span-smj-stream") in events, trace.summarize(events)
    assert _side_keys() == [] and _side_counts() == before
    f = tables["fact"]
    want = f[f.v < 500].merge(tables["dim"], left_on="fk", right_on="dk")[["fk", "v", "w"]]
    assert _norm(pd.DataFrame(got)) == _norm(want)


# -- commits --------------------------------------------------------------------


def _commit(hs, sess, root, how, tables):
    """Changes the lake and commits it the given way; returns the tables the
    indexes describe afterwards."""
    if how == "vacuum-and-rebuild":
        after = _tables(seed=91)
        for name in ("fact", "dim"):
            shutil.rmtree(str(root / name))
            _write(after[name], str(root / name))
        for name in ("f_k", "d_k"):
            table, indexed, included = INDEXES[name]
            hs.delete_index(name)
            hs.vacuum_index(name)
            hs.create_index(
                sess.read_parquet(str(root / table)), hst.CoveringIndexConfig(name, indexed, included)
            )
        return after
    more = _tables(seed=92, n_fact=700, n_dim=60, key0=240)
    more["fact"]["v"] += 5000  # an answer from before the commit cannot equal the new one
    for name in ("fact", "dim"):
        _write(more[name], str(root / name), name="part-00007.parquet")
    for name in ("f_k", "d_k"):
        hs.refresh_index(name, "incremental")
        if how == "optimize":
            hs.optimize_index(name, "full")
    return {name: pd.concat([tables[name], more[name]], ignore_index=True) for name in ("fact", "dim")}


@pytest.mark.parametrize("how", ["refresh-incremental", "optimize", "vacuum-and-rebuild"])
def test_no_side_answers_from_before_a_commit(tmp_path, monkeypatch, how):
    tables = _tables()
    indexes = {name: INDEXES[name] for name in ("f_k", "d_k")}
    sess, hs = _lake(tmp_path, tables, indexes)
    cols = ["fk", "v", "m", "n", "dk", "w"]

    def query():
        fact = sess.read_parquet(str(tmp_path / "fact"))
        dim = sess.read_parquet(str(tmp_path / "dim"))
        return fact.filter(col("v") >= 50).join(dim, on=col("fk") == col("dk"), how="left").select(*cols)

    def oracle(t):
        f = t["fact"]
        return f[f.v >= 50].merge(t["dim"], left_on="fk", right_on="dk", how="left")[cols]

    try:
        _clear_caches()
        for _ in range(2):  # the second answer comes from the resident sides
            assert _norm(pd.DataFrame(query().collect())[cols]) == _norm(oracle(tables))
        stale = _side_keys()
        old_files = {k[0] for key in stale for k in key[1]}
        assert stale and old_files

        after = _commit(hs, sess, tmp_path, how, tables)
        q = query()
        scans = L.collect(q.optimized_plan(), lambda p: isinstance(p, L.IndexScan))
        assert len(scans) == 2
        if how == "refresh-incremental":  # merge mode: delta files join the old ones in their buckets
            assert all(old_files & set(s.files) and set(s.files) - old_files for s in scans)
        else:
            assert not any(old_files & set(s.files) for s in scans)
        got = _run(sess, q, monkeypatch)  # clears the caches, compares with the reference
        assert _norm(pd.DataFrame(got)[cols]) == _norm(oracle(after))
        # and with the earlier sides still in the cache: none of them answers
        _clear_caches()
        for key in stale:
            IO._io_cache.put(key, {"values": np.empty(0), "offsets": np.zeros(NUM_BUCKETS + 1, np.int64),
                                   "buckets": np.empty(0, np.int64)}, 8)
        before = _side_counts()
        assert _norm(pd.DataFrame(q.collect())[cols]) == _norm(oracle(after))
        assert tuple(a - b for a, b in zip(_side_counts(), before)) == (0, 2)

        assert IO.purge_io_cache(old_files) >= len(stale)
        assert not any(k[0] in old_files for key in _side_keys() for k in key[1])
    finally:
        hst.set_session(None)
