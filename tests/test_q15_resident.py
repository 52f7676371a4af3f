"""TPC-H Q15 whole, answered from device-resident index columns.

The deployment of the benchmark's ``sf10-rollup`` cell at toy size: one
covering index on ``lineitem(l_shipdate)`` that includes ``l_suppkey``,
``l_extendedprice`` and ``l_discount``, and ``supplier`` as source Parquet.
The text is ``tests/tpch_queries.py``'s ``q15`` as it stands. Held here:

- through ``Session.sql`` and through ``QueryServer`` the answer equals the
  plain pandas reference (``revenue0`` computed once, ``== max``) and the host
  path's (device execution off), and holds at least one row;
- ``revenue0`` is answered by ``grouped-agg-keyed`` over the resident columns
  with no fallback of any kind and no host fold of ``lineitem``; the CTE's second
  reading (the scalar ``max``) is the request's memo, so the float equality
  compares one evaluation with itself: one dispatch of the program a request;
- the memo keeps apart what only the output names tell apart: two equal
  aggregates of one request under other aliases, or under swapped ones, each
  come back under their own names with their own values;
- the second ask opens no ``lineitem`` file and uploads nothing;
- under tracing the nested executors' spans hang on the request's tree: the
  tier's span says which program answered and what it found;
- after a refresh the answer is the reference's over the new data.
"""

import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.exec import device as D
from hyperspace_tpu.exec import io as IO
from hyperspace_tpu.exec import trace
from hyperspace_tpu.obs.metrics import REGISTRY
from hyperspace_tpu.serving import QueryServer

from tpch_queries import TPCH_QUERIES

INDEX = "li_sd_sup"
Q15 = TPCH_QUERIES["q15"]
SUPPLIERS = 400


def _lineitem(rows: int, seed: int, price: float = 1.0) -> pd.DataFrame:
    rng = np.random.default_rng(seed)
    return pd.DataFrame({
        "l_orderkey": np.arange(rows, dtype=np.int64),
        "l_suppkey": rng.integers(1, SUPPLIERS + 1, rows).astype(np.int64),
        "l_extendedprice": np.round(rng.uniform(900.0, 105000.0, rows), 2) * price,
        "l_discount": rng.integers(0, 11, rows) / 100.0,
        "l_shipdate": np.datetime64("1995-06-01") + rng.integers(0, 400, rows).astype("timedelta64[D]"),
    })


def _supplier() -> pd.DataFrame:
    keys = np.arange(1, SUPPLIERS + 1, dtype=np.int64)
    return pd.DataFrame({
        "s_suppkey": keys,
        "s_name": [f"Supplier#{k:09d}" for k in keys],
        "s_address": [f"{k} Dock Rd" for k in keys],
        "s_phone": [f"{10 + k % 25}-{k:03d}-55" for k in keys],
    })


def _write(frame: pd.DataFrame, directory: str, parts: int, first: int = 0) -> None:
    os.makedirs(directory, exist_ok=True)
    per = -(-len(frame) // parts)
    for i in range(parts):
        pq.write_table(pa.Table.from_pandas(frame.iloc[i * per:(i + 1) * per], preserve_index=False),
                       os.path.join(directory, f"part-{first + i:05d}.parquet"))


def _reference(lineitem: pd.DataFrame, supplier: pd.DataFrame) -> dict:
    ship = lineitem.l_shipdate.to_numpy()
    m = (ship >= np.datetime64("1996-01-01")) & (ship < np.datetime64("1996-04-01"))
    f = lineitem[m]
    revenue0 = pd.DataFrame({"supplier_no": f.l_suppkey, "total_revenue": f.l_extendedprice * (1 - f.l_discount)}).groupby(
        "supplier_no", as_index=False).total_revenue.sum()
    top = revenue0[revenue0.total_revenue == revenue0.total_revenue.max()]
    out = supplier.merge(top, left_on="s_suppkey", right_on="supplier_no").sort_values("s_suppkey")
    return {c: out[c].to_numpy() for c in ["s_suppkey", "s_name", "s_address", "s_phone", "total_revenue"]}


def _same(got: dict, want: dict) -> None:
    assert list(got) == list(want)
    assert len(got["s_suppkey"]) == len(want["s_suppkey"]) >= 1
    for c in want:
        if c == "total_revenue":
            np.testing.assert_allclose(got[c], want[c], rtol=1e-12, atol=0)
        else:
            assert list(got[c]) == list(want[c]), c


def _counter(name: str, **labels) -> float:
    return REGISTRY.counter(name, "", **labels).value


def _total(name: str) -> float:
    entry = REGISTRY.snapshot().get(name, {"series": []})
    return sum(float(s.get("value", 0.0)) for s in entry["series"])


def _fallbacks() -> float:
    """``hs_device_fallback_total`` over every op and reason (the merge with
    ``supplier`` is a host join by choice: a decision, not a fallback)."""
    return _total("hs_device_fallback_total")


def _clear_caches() -> None:
    IO.clear_io_cache()
    D.clear_device_cache()
    D._FOOTER_ROWS_CACHE.clear()


def _lake(root, lineitem: pd.DataFrame):
    sess = hst.Session(conf={
        hst.keys.SYSTEM_PATH: str(root / "indexes"),
        hst.keys.NUM_BUCKETS: 4,
        hst.keys.TPU_QUERY_DEVICE_MIN_ROWS: 0,
    })
    hst.set_session(sess)
    hs = hst.Hyperspace(sess)
    _write(lineitem, str(root / "lineitem"), parts=3)
    _write(_supplier(), str(root / "supplier"), parts=1)
    for table in ("lineitem", "supplier"):
        sess.read_parquet(str(root / table)).create_or_replace_temp_view(table)
    hs.create_index(sess.read_parquet(str(root / "lineitem")),
                    hst.CoveringIndexConfig(INDEX, ["l_shipdate"], ["l_suppkey", "l_extendedprice", "l_discount"]))
    sess.enable_hyperspace()
    return sess, hs


@pytest.fixture(scope="module")
def lake(tmp_path_factory):
    root = tmp_path_factory.mktemp("q15")
    frame = _lineitem(30000, seed=41)
    sess, hs = _lake(root, frame)
    yield sess, hs, frame, root
    hst.set_session(None)


@pytest.mark.parametrize("through", ["session", "server"])
def test_q15_equals_the_reference_with_revenue0_on_the_device(lake, through):
    sess, _hs, frame, _root = lake
    hst.set_session(sess)
    _clear_caches()
    assert "IndexScan" in sess.sql(Q15).optimized_plan().pretty()
    before = (_counter("hs_agg_rows_total", path="device"), _fallbacks(),
              _counter("hs_device_dispatches_total", program="grouped-agg-keyed"),
              _counter("hs_agg_groups_total", program="grouped-agg-keyed"))
    if through == "session":
        sess.sql(Q15).collect()  # the first ask climbs the capacity ladder (several runs of the program)
        ran = _counter("hs_device_dispatches_total", program="grouped-agg-keyed")
        with trace.recording() as events:
            got = sess.sql(Q15).collect()
        assert events.count(("agg", "device-grouped-scan")) == 1, trace.summarize(events)
        assert events.count(("agg", "request-memo")) == 1, "the CTE's second reading is the first one's table"
        assert ("join", "generic-merge") in events, "a group table is merged on the host"
        assert not [e for e in events if e == ("filter", "device")], "the predicate runs inside the aggregate program"
        assert _counter("hs_device_dispatches_total", program="grouped-agg-keyed") == ran + 1
    else:
        with QueryServer(sess, workers=2) as srv:
            got = srv.query(Q15)
    _same(got, _reference(frame, _supplier()))
    assert _fallbacks() == before[1], "nothing fell back: not the aggregate, not the join"
    asks = 2 if through == "session" else 1
    assert _counter("hs_agg_rows_total", path="device") - before[0] == asks * len(frame)
    assert _counter("hs_agg_groups_total", program="grouped-agg-keyed") - before[3] == asks * SUPPLIERS


def test_the_host_path_gives_the_same_answer(lake):
    sess, _hs, frame, _root = lake
    hst.set_session(sess)
    device = sess.sql(Q15).collect()
    sess.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
    try:
        with trace.recording() as events:
            host = sess.sql(Q15).collect()
        assert ("agg", "device-grouped-scan") not in events
        assert ("agg", "request-memo") in events, "the host path's two readings are one evaluation too"
    finally:
        sess.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
    _same(host, _reference(frame, _supplier()))
    _same(device, {c: np.asarray(v) for c, v in host.items()})


_QUARTER = "where l_shipdate >= date '1996-01-01' and l_shipdate < date '1996-04-01' group by l_suppkey"
ALIASES = {
    # two grouped aggregates of one request that the plan fingerprint cannot tell apart
    "another-alias": (f"select l_suppkey as k, sum(l_extendedprice) as s1 from lineitem {_QUARTER}",
                      f"select l_suppkey as k, sum(l_extendedprice) as s2 from lineitem {_QUARTER}"),
    "swapped-aliases": (f"select l_suppkey as k, sum(l_extendedprice) as p, sum(l_discount) as q from lineitem {_QUARTER}",
                        f"select l_suppkey as k, sum(l_extendedprice) as q, sum(l_discount) as p from lineitem {_QUARTER}"),
    "the-same-twice": (f"select l_suppkey as k, sum(l_extendedprice) as s1 from lineitem {_QUARTER}",
                       f"select l_suppkey as k, sum(l_extendedprice) as s1 from lineitem {_QUARTER}"),
}


@pytest.mark.parametrize("case", list(ALIASES))
def test_the_memo_keeps_apart_what_only_the_output_names_tell_apart(lake, case):
    from hyperspace_tpu.plan.expr import subquery_scope

    sess, _hs, _frame, _root = lake
    hst.set_session(sess)
    alone = [sess.sql(q).collect() for q in ALIASES[case]]  # a request each: nothing shared
    with subquery_scope():  # one request's scope round both, as round a plan that holds both
        with trace.recording() as events:
            together = [sess.sql(q).collect() for q in ALIASES[case]]
    assert events.count(("agg", "request-memo")) == (1 if case == "the-same-twice" else 0), trace.summarize(events)
    for got, want in zip(together, alone):
        assert list(got) == list(want)
        for c in want:
            assert np.array_equal(np.asarray(got[c]), np.asarray(want[c])), (case, c)
    if case == "swapped-aliases":
        first, second = together
        assert np.array_equal(first["p"], second["q"]) and not np.array_equal(first["p"], second["p"])


def test_two_sides_of_a_join_that_differ_in_aliases_alone_are_both_answered(lake):
    """One SQL text, so one request through the server: both derived tables
    are the same aggregate but for the names (the parent of the memo answered
    this; a memo keyed by the fingerprint alone hands ``b`` the batch of ``a``)."""
    sess, _hs, frame, _root = lake
    hst.set_session(sess)
    text = (f"select a.k, a.s1, b.s2 from (select l_suppkey as k, sum(l_extendedprice) as s1 from lineitem {_QUARTER}) a "
            f"join (select l_suppkey as k2, sum(l_extendedprice) as s2 from lineitem {_QUARTER}) b on a.k = b.k2 order by a.k")
    with QueryServer(sess, workers=1) as srv:
        got = srv.query(text)
    ship = frame.l_shipdate.to_numpy()
    f = frame[(ship >= np.datetime64("1996-01-01")) & (ship < np.datetime64("1996-04-01"))]
    want = f.groupby("l_suppkey").l_extendedprice.sum().sort_index()
    assert list(got) == ["k", "s1", "s2"] and list(got["k"]) == list(want.index)
    np.testing.assert_allclose(got["s1"], want.to_numpy(), rtol=1e-12)
    assert np.array_equal(got["s1"], got["s2"])


def test_the_second_ask_reads_no_lineitem_file_and_uploads_nothing(lake):
    sess, _hs, frame, _root = lake
    hst.set_session(sess)
    _clear_caches()
    first = sess.sql(Q15).collect()
    before = (_total("hs_h2d_bytes_total"), _total("hs_native_decode_bytes_total"),
              _counter("hs_device_cache_lookups_total", result="miss"), _counter("hs_agg_rows_total", path="host"))
    again = sess.sql(Q15).collect()
    assert _total("hs_h2d_bytes_total") == before[0]
    assert _total("hs_native_decode_bytes_total") == before[1]
    assert _counter("hs_device_cache_lookups_total", result="miss") == before[2]
    # the outer max over the group table is the only host fold
    assert _counter("hs_agg_rows_total", path="host") - before[3] == SUPPLIERS
    for c in first:
        assert np.array_equal(np.asarray(first[c]), np.asarray(again[c])), c


def test_the_nested_executors_spans_hang_on_the_requests_tree(lake):
    sess, _hs, frame, _root = lake
    hst.set_session(sess)
    sess.sql(Q15).collect()  # capacities known
    sess.conf.set("hyperspace.obs.tracing.enabled", True)
    try:
        with QueryServer(sess, workers=1) as srv:
            fut = srv.submit(Q15)
            fut.result(timeout=120)
            root = fut.request_root
    finally:
        sess.conf.set("hyperspace.obs.tracing.enabled", False)
    spans = list(root.walk())
    tiers = [s for s in spans if s.name == "agg-device-grouped-scan"]
    assert len(tiers) == 1, [s.name for s in spans]
    attrs = tiers[0].attrs
    assert attrs["program"] == "grouped-agg-keyed" and attrs["groups"] == SUPPLIERS
    assert attrs["capacity"] >= SUPPLIERS and 0 < attrs["selected_rows"] < len(frame)
    assert [s for s in spans if s.name == "device-launch" and s.attrs.get("program") == "grouped-agg-keyed"]
    events = [e for s in spans for e in s.events]
    assert ("agg", "device-grouped-scan") in events and ("agg", "request-memo") in events
    assert ("join", "generic-merge") in events
    (merge,) = [s for s in spans if s.name == "join-generic-merge"]  # the supplier merge is a span of the request
    assert merge.attrs.get("chosen") == "group-table" and "fallback" not in merge.attrs
    assert not [s for s in spans if s.name == "join-broadcast-hash-stream"]


def test_after_a_refresh_the_answer_is_of_the_new_version(tmp_path):
    frame = _lineitem(12000, seed=7)
    sess, hs = _lake(tmp_path, frame)
    try:
        _clear_caches()
        _same(sess.sql(Q15).collect(), _reference(frame, _supplier()))
        more = _lineitem(4000, seed=8, price=50.0)  # another supplier wins, by far
        _write(more, str(tmp_path / "lineitem"), parts=1, first=7)
        hs.refresh_index(INDEX, "incremental")
        sess.read_parquet(str(tmp_path / "lineitem")).create_or_replace_temp_view("lineitem")
        after = pd.concat([frame, more], ignore_index=True)
        want = _reference(after, _supplier())
        assert list(want["s_suppkey"]) != list(_reference(frame, _supplier())["s_suppkey"])
        with trace.recording() as events:
            got = sess.sql(Q15).collect()
        _same(got, want)
        assert ("agg", "device-grouped-scan") in events, trace.summarize(events)
    finally:
        hst.set_session(None)
