"""TPC-H Q12 answered by a join of two resident covering indexes.

The deployment of the benchmark's ``sf10-join`` cell at SF 0.01: ``li_ok_ship``
on ``lineitem(l_orderkey)`` and ``o_ok_pri`` on ``orders(o_orderkey)``, equal
``numBuckets`` (JoinIndexRule's shape). The text is ``tests/tpch_queries.py``'s
``q12`` as it stands. Held here:

- through ``Session.sql`` and through ``QueryServer`` the answer equals the
  plain reference (``tests/reference_join.py``) and the parent's path (device
  execution off), exactly: strings and integer counts;
- the plan holds two ``IndexScan``s, and the tier that answers is
  ``agg-device-join-scan``: one launch of ``join-agg-resident`` a request, no
  fallback, no host fold, the join's own tiers never reached;
- the second ask opens no file, uploads nothing and finds the build table;
- the tier's span says what it found, and both counters count it;
- after a refresh of either index the answer is the reference's over the new
  data, through a table made from the new files;
- what the tier turns away goes on to the tiers there are with a right
  answer: a budget too small (``over-cap``), too few rows at the default gate
  (``min-rows``), a session that shards its queries, a build key that repeats.
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

import reference_join as R
from tpch_queries import TPCH_QUERIES

Q12 = TPCH_QUERIES["q12"]
MODES = ["AIR", "AIR REG", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
ORDERS, LINES = 15000, 60000


def _orders(rows: int = ORDERS, seed: int = 43, first: int = 0, urgent: float = 0.2) -> pd.DataFrame:
    rng = np.random.default_rng(seed)
    rest = (1.0 - urgent) / 4
    return pd.DataFrame({
        "o_orderkey": np.arange(first, first + rows, dtype=np.int64),
        "o_orderpriority": rng.choice(np.array(PRIORITIES, dtype=object), rows, p=[urgent] + [rest] * 4),
    })


def _lineitem(rows: int = LINES, seed: int = 44, orders: int = ORDERS) -> pd.DataFrame:
    rng = np.random.default_rng(seed)
    ship = np.datetime64("1993-01-01") + rng.integers(0, 1800, rows).astype("timedelta64[D]")
    commit = ship + rng.integers(-10, 30, rows).astype("timedelta64[D]")
    receipt = commit + rng.integers(-5, 6, rows).astype("timedelta64[D]")
    return pd.DataFrame({
        "l_orderkey": rng.integers(0, orders, rows).astype(np.int64),
        "l_shipmode": rng.choice(np.array(MODES, dtype=object), rows),
        "l_shipdate": ship, "l_commitdate": commit, "l_receiptdate": receipt,
    })


def _write(frame: pd.DataFrame, directory: str, parts: int, first: int = 0) -> None:
    os.makedirs(directory, exist_ok=True)
    per = -(-len(frame) // parts)
    for i in range(parts):
        pq.write_table(pa.Table.from_pandas(frame.iloc[i * per:(i + 1) * per], preserve_index=False),
                       os.path.join(directory, f"part-{first + i:05d}.parquet"))


def _reference(lineitem: pd.DataFrame, orders: pd.DataFrame) -> dict:
    lo, hi = np.datetime64("1994-01-01"), np.datetime64("1995-01-01")
    return R.join_aggregate(
        lineitem, orders, ("l_orderkey", "o_orderkey"),
        left_filter=lambda f: (f.l_shipmode.isin(["MAIL", "SHIP"]) & (f.l_commitdate < f.l_receiptdate) & (f.l_shipdate < f.l_commitdate)
                               & (f.l_receiptdate >= lo) & (f.l_receiptdate < hi)).to_numpy(),
        computes={
            "h": lambda j: R.case(len(j), [(R.eq(j.o_orderpriority, "1-URGENT") | R.eq(j.o_orderpriority, "2-HIGH"), 1)], 0),
            "l": lambda j: R.case(len(j), [(R.ne(j.o_orderpriority, "1-URGENT") & R.ne(j.o_orderpriority, "2-HIGH"), 1)], 0),
        },
        keys=["l_shipmode"], aggs=[("high_line_count", "sum", "h"), ("low_line_count", "sum", "l")])


def _same(got: dict, want: dict) -> None:
    assert list(got) == ["l_shipmode", "high_line_count", "low_line_count"]
    assert list(got["l_shipmode"]) == list(want["l_shipmode"]) == ["MAIL", "SHIP"]  # the ORDER BY's order
    for c in ("high_line_count", "low_line_count"):
        assert np.asarray(got[c]).dtype.kind == "i" and list(got[c]) == list(want[c]), c


def _counter(name: str, **labels) -> float:
    return REGISTRY.counter(name, "", **labels).value


def _total(name: str) -> float:
    entry = REGISTRY.snapshot().get(name, {"series": []})
    return sum(float(s.get("value", 0.0)) for s in entry["series"])


def _fallbacks(reason=None) -> float:
    if reason is not None:
        return _counter("hs_device_fallback_total", op="agg", reason=reason)
    return _total("hs_device_fallback_total")


def _clear_caches() -> None:
    IO.clear_io_cache()
    D.clear_device_cache()
    D._FOOTER_ROWS_CACHE.clear()


def _lake(root, lineitem: pd.DataFrame, orders: pd.DataFrame, **conf):
    sess = hst.Session(conf={
        hst.keys.SYSTEM_PATH: str(root / "indexes"),
        hst.keys.NUM_BUCKETS: 4,
        hst.keys.TPU_QUERY_DEVICE_MIN_ROWS: 0,
        **conf,
    })
    hst.set_session(sess)
    hs = hst.Hyperspace(sess)
    _write(lineitem, str(root / "lineitem"), parts=3)
    _write(orders, str(root / "orders"), parts=2)
    for table in ("lineitem", "orders"):
        sess.read_parquet(str(root / table)).create_or_replace_temp_view(table)
    hs.create_index(sess.read_parquet(str(root / "lineitem")), hst.CoveringIndexConfig(
        "li_ok_ship", ["l_orderkey"], ["l_shipmode", "l_shipdate", "l_commitdate", "l_receiptdate"]))
    hs.create_index(sess.read_parquet(str(root / "orders")), hst.CoveringIndexConfig("o_ok_pri", ["o_orderkey"], ["o_orderpriority"]))
    sess.enable_hyperspace()
    return sess, hs


@pytest.fixture(scope="module")
def lake(tmp_path_factory):
    root = tmp_path_factory.mktemp("q12")
    lineitem, orders = _lineitem(), _orders()
    sess, hs = _lake(root, lineitem, orders)
    yield sess, hs, lineitem, orders, root
    hst.set_session(None)
    D.set_device_cache_bytes(int(D._CONF_DEFAULTS[hst.keys.TPU_QUERY_DEVICE_CACHE_BYTES]))


@pytest.mark.parametrize("through", ["session", "server"])
def test_q12_equals_the_reference_by_one_program_over_two_resident_scans(lake, through):
    sess, _hs, lineitem, orders, _root = lake
    hst.set_session(sess)
    _clear_caches()
    plan = sess.sql(Q12).optimized_plan().pretty()
    assert plan.count("IndexScan(") == 2 and "li_ok_ship" in plan and "o_ok_pri" in plan
    before = (_counter("hs_agg_rows_total", path="device"), _fallbacks(), _counter("hs_agg_rows_total", path="host"),
              _counter("hs_device_dispatches_total", program="join-agg-resident"))
    if through == "session":
        with trace.recording() as events:
            got = sess.sql(Q12).collect()
        assert events.count(("agg", "device-join-scan")) == 1, trace.summarize(events)
        assert not [e for e in events if e[0] == "join"], "no join tier is reached: not the stream gate, not the span program"
        assert not [e for e in events if e == ("filter", "device")], "the predicate runs inside the program"
    else:
        with QueryServer(sess, workers=2) as srv:
            got = srv.query(Q12)
    _same(got, _reference(lineitem, orders))
    assert _fallbacks() == before[1], "nothing fell back"
    assert _counter("hs_agg_rows_total", path="device") - before[0] == len(lineitem), "the probe side's rows"
    assert _counter("hs_agg_rows_total", path="host") == before[2], "no host fold"
    assert _counter("hs_device_dispatches_total", program="join-agg-resident") - before[3] == 1


def test_the_parents_path_gives_the_same_answer(lake):
    sess, _hs, lineitem, orders, _root = lake
    hst.set_session(sess)
    device = sess.sql(Q12).collect()
    sess.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
    try:
        with trace.recording() as events:
            host = sess.sql(Q12).collect()
        assert ("agg", "device-join-scan") not in events
    finally:
        sess.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
    _same(host, _reference(lineitem, orders))
    _same(device, {c: np.asarray(v) for c, v in host.items()})


def test_the_second_ask_reads_no_file_uploads_nothing_and_finds_the_table(lake):
    sess, _hs, _lineitem, _orders, _root = lake
    hst.set_session(sess)
    _clear_caches()
    built, hit = _counter("hs_join_build_table_total", result="built"), _counter("hs_join_build_table_total", result="hit")
    first = sess.sql(Q12).collect()
    assert _counter("hs_join_build_table_total", result="built") - built == 1
    before = (_total("hs_h2d_bytes_total"), _total("hs_native_decode_bytes_total"),
              _counter("hs_device_cache_lookups_total", result="miss"), _counter("hs_device_cache_lookups_total", result="hit"),
              _total("hs_device_dispatches_total"), _counter("hs_d2h_bytes_total", site="agg-table"))
    again = sess.sql(Q12).collect()
    assert _total("hs_h2d_bytes_total") == before[0]
    assert _total("hs_native_decode_bytes_total") == before[1]
    assert _counter("hs_device_cache_lookups_total", result="miss") == before[2]
    assert _counter("hs_device_cache_lookups_total", result="hit") - before[3] == 7, "five columns of lineitem, two of orders"
    assert _total("hs_device_dispatches_total") - before[4] == 1, "the selected rows' count is remembered: one program"
    assert 0 < _counter("hs_d2h_bytes_total", site="agg-table") - before[5] < 4096
    assert (_counter("hs_join_build_table_total", result="built") - built, _counter("hs_join_build_table_total", result="hit") - hit) == (1, 1)
    for c in first:
        assert list(first[c]) == list(again[c]), c


def test_the_tiers_span_and_both_counters(lake):
    sess, _hs, lineitem, orders, _root = lake
    hst.set_session(sess)
    want = _reference(lineitem, orders)
    selected, matched = _counter("hs_join_probe_rows_total", kind="selected"), _counter("hs_join_probe_rows_total", kind="matched")
    sess.conf.set("hyperspace.obs.tracing.enabled", True)
    try:
        with QueryServer(sess, workers=1) as srv:
            fut = srv.submit(Q12)
            fut.result(timeout=120)
            root = fut.request_root
    finally:
        sess.conf.set("hyperspace.obs.tracing.enabled", False)
    spans = list(root.walk())
    (tier,) = [s for s in spans if s.name == "agg-device-join-scan"]
    attrs = tier.attrs
    rows = int(sum(want["high_line_count"]) + sum(want["low_line_count"]))
    assert attrs["program"] == "join-agg-resident" and attrs["table"] == "direct" and attrs["groups"] == 2
    assert attrs["probe_rows"] == len(lineitem) and attrs["build_rows"] == len(orders)
    assert attrs["matched"] == attrs["selected"] == rows, "every lineitem has its order; every priority is in one count"
    assert attrs["resident"] in ("hit", "miss") and "fallback" not in attrs
    assert [s for s in spans if s.name == "device-launch" and s.attrs.get("program") == "join-agg-resident"]
    assert [s for s in spans if s.name == "device-wait" and s.attrs.get("program") == "join-agg-resident"]
    assert ("agg", "device-join-scan") in [e for s in spans for e in s.events]
    assert not [s for s in spans if s.name.startswith("join-") or s.name in ("agg-host", "agg-fused-bucketed-join")]
    assert _counter("hs_join_probe_rows_total", kind="selected") - selected == rows
    assert _counter("hs_join_probe_rows_total", kind="matched") - matched == rows


@pytest.mark.parametrize("which", ["orders", "lineitem"])
def test_after_a_refresh_the_answer_is_of_the_new_version(tmp_path, which):
    lineitem, orders = _lineitem(12000, seed=7, orders=3000), _orders(3000, seed=8)
    sess, hs = _lake(tmp_path, lineitem, orders)
    try:
        _clear_caches()
        _same(sess.sql(Q12).collect(), _reference(lineitem, orders))
        built = _counter("hs_join_build_table_total", result="built")
        if which == "orders":  # new orders, all urgent, and line items of theirs
            more_o = _orders(1000, seed=9, first=3000, urgent=1.0)
            more_l = _lineitem(4000, seed=10, orders=1000).assign(l_orderkey=lambda f: f.l_orderkey + 3000)
            _write(more_o, str(tmp_path / "orders"), parts=1, first=7)
            _write(more_l, str(tmp_path / "lineitem"), parts=1, first=7)
            hs.refresh_index("o_ok_pri", "incremental")
            hs.refresh_index("li_ok_ship", "incremental")
            orders, lineitem = pd.concat([orders, more_o], ignore_index=True), pd.concat([lineitem, more_l], ignore_index=True)
        else:
            more_l = _lineitem(5000, seed=11, orders=3000)
            _write(more_l, str(tmp_path / "lineitem"), parts=1, first=7)
            hs.refresh_index("li_ok_ship", "incremental")
            lineitem = pd.concat([lineitem, more_l], ignore_index=True)
        for table in ("lineitem", "orders"):
            sess.read_parquet(str(tmp_path / table)).create_or_replace_temp_view(table)
        with trace.recording() as events:
            got = sess.sql(Q12).collect()
        _same(got, _reference(lineitem, orders))
        assert ("agg", "device-join-scan") in events, trace.summarize(events)
        # the orders side's table follows ITS files: another only when they changed
        assert _counter("hs_join_build_table_total", result="built") - built == (1 if which == "orders" else 0)
    finally:
        hst.set_session(None)


@pytest.mark.parametrize("reason", ["over-cap", "min-rows", "sharded-session", "repeated-build-key"])
def test_what_the_tier_turns_away_is_answered_by_the_tiers_there_are(tmp_path, reason):
    lineitem, orders = _lineitem(9000, seed=21, orders=2000), _orders(2000, seed=22)
    if reason == "repeated-build-key":
        orders = pd.concat([orders, orders.iloc[:5]], ignore_index=True)  # five orders twice: their lines count twice
    conf = {
        "over-cap": {hst.keys.TPU_QUERY_DEVICE_CACHE_BYTES: 100_000},
        "min-rows": {hst.keys.TPU_QUERY_DEVICE_MIN_ROWS: 1_000_000},
        "sharded-session": {"hyperspace.parallel.enabled": True},
    }.get(reason, {})
    sess, _hs = _lake(tmp_path, lineitem, orders, **conf)
    counted = {"sharded-session": "unsupported", "repeated-build-key": "unsupported"}.get(reason, reason)
    try:
        _clear_caches()
        before, launches = _fallbacks(counted), _counter("hs_device_dispatches_total", program="join-agg-resident")
        with trace.recording() as events:
            got = sess.sql(Q12).collect()
        _same(got, _reference(lineitem, orders))
        assert ("agg", "device-join-scan") not in events, trace.summarize(events)
        assert _fallbacks(counted) - before == 1, "a counted refusal"
        assert _counter("hs_device_dispatches_total", program="join-agg-resident") == launches
        if reason in ("over-cap", "min-rows", "sharded-session"):  # decided before anything is uploaded
            assert not [k for k in D._device_cache.keys() if k[1] in ("l_shipmode", "o_orderpriority")]
    finally:
        hst.set_session(None)
        D.set_device_cache_bytes(int(D._CONF_DEFAULTS[hst.keys.TPU_QUERY_DEVICE_CACHE_BYTES]))
