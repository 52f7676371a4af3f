"""TPC-H Q1 and Q6 answered from device-resident index columns.

The deployment of the benchmark's ``sf10-report`` cell at toy size: one
covering index on ``lineitem(l_shipdate)`` that includes every column the two
templates read. Held here, against ``tests/reference_report.py`` (plain pandas):

- both templates, through ``Session.sql`` and through ``QueryServer``, equal
  the reference (Q1's row order too), folded by the device tier with no fallback;
- with the tier's gate closed (columns over the device cache's budget, or
  device execution off) the tiers behind it give the same answers, and the
  fallback says why;
- the second ask of each opens no file, decodes nothing and uploads nothing;
- a budget smaller than the working set streams and answers the same, and a
  stream-sized scan whose columns fit stays on the device;
- after a refresh, an optimize or a vacuum and rebuild the answer is the
  reference's over the new data and no column of a replaced file is resident;
- the budget is the session's ``hyperspace.tpu.query.deviceCacheBytes``,
  eviction frees nothing a query still holds, and a float32 fold fails the
  comparison;
- Q1's ``interval '90' day (3)`` parses to the value of ``interval '90' day``.
"""

import os
import shutil

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
from hyperspace_tpu.plan import logical as L
from hyperspace_tpu.serving import QueryServer
from hyperspace_tpu.utils.lru import BytesLRU

import reference_report as ref

INDEX = "li_sd_rep"
INCLUDED = ["l_quantity", "l_extendedprice", "l_discount", "l_tax", "l_returnflag", "l_linestatus"]
TIER = {"q1": "device-grouped-scan", "q6": "device-fused-scan"}
QUERIES = ["q1", "q6"]


def _write(frame: pd.DataFrame, directory: str, parts: int = 3, first: int = 0) -> None:
    os.makedirs(directory, exist_ok=True)
    per = -(-len(frame) // parts)
    for i in range(parts):
        pq.write_table(pa.Table.from_pandas(frame.iloc[i * per:(i + 1) * per], preserve_index=False),
                       os.path.join(directory, f"part-{first + i:05d}.parquet"))


def _lake(root, frame, conf=None):
    settings = {
        hst.keys.SYSTEM_PATH: str(root / "indexes"),
        hst.keys.NUM_BUCKETS: 4,
        hst.keys.TPU_QUERY_DEVICE_MIN_ROWS: 0,
    }
    settings.update(conf or {})
    sess = hst.Session(conf=settings)
    hst.set_session(sess)
    hs = hst.Hyperspace(sess)
    _write(frame, str(root / "lineitem"))
    df = sess.read_parquet(str(root / "lineitem"))
    df.create_or_replace_temp_view("lineitem")
    hs.create_index(df, hst.CoveringIndexConfig(INDEX, ["l_shipdate"], INCLUDED))
    sess.enable_hyperspace()
    return sess, hs


def _sql(name: str) -> str:
    return ref.SQL[name].format(**ref.PARAMS[name])


def _clear_caches() -> None:
    IO.clear_io_cache()
    D.clear_device_cache()
    D._FOOTER_ROWS_CACHE.clear()


def _counter(name: str, **labels) -> float:
    return REGISTRY.counter(name, "", **labels).value


def _total(name: str) -> float:
    entry = REGISTRY.snapshot().get(name, {"series": []})
    return sum(float(s.get("value", 0.0)) for s in entry["series"])


def _index_files(sess, name: str) -> set:
    scans = L.collect(sess.sql(_sql(name)).optimized_plan(), lambda p: isinstance(p, L.IndexScan))
    assert scans, "the plan holds no IndexScan"
    return set(scans[0].files)


@pytest.fixture(scope="module")
def lake(tmp_path_factory):
    root = tmp_path_factory.mktemp("report")
    frame = ref.lineitem(6000, seed=34)
    sess, hs = _lake(root, frame)
    yield sess, hs, frame, root
    hst.set_session(None)


@pytest.mark.parametrize("through", ["session", "server"])
@pytest.mark.parametrize("name", QUERIES)
def test_answers_equal_the_reference_on_the_device_tier(lake, name, through):
    sess, _hs, frame, _root = lake
    hst.set_session(sess)
    _clear_caches()
    before = (_counter("hs_agg_rows_total", path="device"), _counter("hs_agg_rows_total", path="host"),
              _total("hs_device_fallback_total"))
    if through == "session":
        with trace.recording() as events:
            got = sess.sql(_sql(name)).collect()
        assert ("agg", TIER[name]) in events, trace.summarize(events)
        assert not [e for e in events if e[0] == "filter"], "the predicate runs inside the aggregate program"
    else:
        with QueryServer(sess, workers=2) as srv:
            got = srv.query(_sql(name))
    ref.compare(got, ref.answer(name, frame), ref.ORDERED[name])
    assert _counter("hs_agg_rows_total", path="device") - before[0] == len(frame)
    assert _counter("hs_agg_rows_total", path="host") == before[1]
    assert _total("hs_device_fallback_total") == before[2]


def _plain(batch: dict) -> dict:
    return {c: np.asarray(v) for c, v in batch.items()}


@pytest.mark.parametrize("gate", ["over-cap", "device-off"])
@pytest.mark.parametrize("name", QUERIES)
def test_with_the_gate_closed_the_other_tiers_give_the_same_answer(lake, monkeypatch, name, gate):
    sess, _hs, frame, _root = lake
    hst.set_session(sess)
    _clear_caches()
    with trace.recording() as events:
        resident = _plain(sess.sql(_sql(name)).collect())
    assert ("agg", TIER[name]) in events, trace.summarize(events)
    _clear_caches()
    over_cap = _counter("hs_device_fallback_total", op="agg", reason="over-cap")
    if gate == "over-cap":
        monkeypatch.setattr(D, "_device_cache", BytesLRU(1024))
    else:
        sess.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
    try:
        with trace.recording() as events:
            closed = _plain(sess.sql(_sql(name)).collect())
    finally:
        sess.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, True)
    assert not [e for e in events if e[0] == "agg"], trace.summarize(events)  # the host folded
    assert _counter("hs_device_fallback_total", op="agg", reason="over-cap") - over_cap == (gate == "over-cap")
    assert len(D._device_cache) == 0 or gate == "device-off"
    ref.compare(closed, ref.answer(name, frame), ref.ORDERED[name])
    # the same rows in the same order, the floats within the reference's tolerance of each other
    ref.compare(closed, pd.DataFrame(resident), ordered=True)


@pytest.mark.parametrize("name", QUERIES)
def test_second_ask_opens_no_file_and_moves_no_bytes(lake, monkeypatch, name):
    sess, _hs, frame, _root = lake
    hst.set_session(sess)
    _clear_caches()
    sess.sql(_sql(name)).collect()
    reads = []
    real = IO.read_parquet_batch
    monkeypatch.setattr(IO, "read_parquet_batch", lambda files, *a, **k: reads.append(files) or real(files, *a, **k))
    before = {c: _total(c) for c in ("hs_native_decode_bytes_total", "hs_h2d_bytes_total")}
    hits, misses = (_counter("hs_device_cache_lookups_total", result=r) for r in ("hit", "miss"))
    # other literals, the same columns: the residency is the scan's, not the query's
    other = dict(ref.PARAMS[name], **({"delta": 61} if name == "q1" else {"date": "1995-01-01"}))
    from hyperspace_tpu.obs import spans

    with spans.trace("query") as root:
        got = sess.sql(ref.SQL[name].format(**other)).collect()
    want = {"q1": ref.q1, "q6": ref.q6}[name](frame, **other)
    ref.compare(got, want, ref.ORDERED[name])
    assert reads == []
    assert {c: _total(c) for c in before} == before
    assert _counter("hs_device_cache_lookups_total", result="miss") == misses
    assert _counter("hs_device_cache_lookups_total", result="hit") > hits
    tiers = [s for s in root.walk() if s.name.startswith("agg-device-")]
    assert tiers and all(s.attrs.get("resident") == "hit" and s.attrs.get("rows") == len(frame) for s in tiers)


@pytest.mark.parametrize("budget", ["fits", "too-small"])
@pytest.mark.parametrize("name", QUERIES)
def test_a_stream_sized_scan_stays_on_the_device_only_if_its_columns_fit(tmp_path, monkeypatch, name, budget):
    """``exec.stream.aggMinBytes`` makes every scan here stream-sized; what
    decides is whether the columns fit the device cache's budget."""
    frame = ref.lineitem(3000, seed=5)
    sess, _hs = _lake(tmp_path, frame, conf={
        "hyperspace.exec.stream.aggMinBytes": 1, "hyperspace.exec.stream.chunkBytes": 1})
    try:
        cache = BytesLRU(1 << 30 if budget == "fits" else 1024)
        monkeypatch.setattr(D, "_device_cache", cache)
        IO.clear_io_cache()
        said = _counter("hs_device_fallback_total", op="agg", reason="over-cap")
        with trace.recording() as events:
            got = sess.sql(_sql(name)).collect()
        ref.compare(got, ref.answer(name, frame), ref.ORDERED[name])
        if budget == "fits":
            assert ("agg", TIER[name]) in events, trace.summarize(events)
            assert len(cache) == (7 if name == "q1" else 4)
            assert _counter("hs_device_fallback_total", op="agg", reason="over-cap") == said
        else:
            assert ("agg", "streamed-partial") in events, trace.summarize(events)
            assert len(cache) == 0
            assert _counter("hs_device_fallback_total", op="agg", reason="over-cap") == said + 1
    finally:
        hst.set_session(None)


def _commit(hs, sess, root, how, frame):
    """Changes the lake and commits it the given way; returns the rows the
    index describes afterwards."""
    directory = str(root / "lineitem")
    if how == "vacuum-and-rebuild":
        after = ref.lineitem(len(frame), seed=78)  # the same file names, other rows
        shutil.rmtree(directory)
        _write(after, directory)
        hs.delete_index(INDEX)
        hs.vacuum_index(INDEX)
        hs.create_index(sess.read_parquet(directory), hst.CoveringIndexConfig(INDEX, ["l_shipdate"], INCLUDED))
    else:
        more = ref.lineitem(1500, seed=77)
        more["l_extendedprice"] *= 4  # an answer from before the commit cannot equal the new one
        _write(more, directory, parts=1, first=7)
        hs.refresh_index(INDEX, "full" if how == "refresh-full" else "incremental")
        if how == "optimize":
            hs.optimize_index(INDEX, "full")
        after = pd.concat([frame, more], ignore_index=True)
    sess.read_parquet(directory).create_or_replace_temp_view("lineitem")
    return after


@pytest.mark.parametrize("how", ["refresh-incremental", "refresh-full", "optimize", "vacuum-and-rebuild"])
def test_after_a_commit_the_answer_is_the_new_data_and_nothing_replaced_is_resident(tmp_path, how):
    frame = ref.lineitem(3000, seed=9)
    sess, hs = _lake(tmp_path, frame)
    try:
        _clear_caches()
        for name in QUERIES:
            ref.compare(sess.sql(_sql(name)).collect(), ref.answer(name, frame), ref.ORDERED[name])
        old = _index_files(sess, "q1")
        assert any(k[0][0][0] in old for k in D._device_cache.keys()), "nothing was resident before the commit"
        after = _commit(hs, sess, tmp_path, how, frame)
        new = _index_files(sess, "q1")
        replaced = old - new
        if how != "refresh-incremental":
            assert replaced
        for name in QUERIES:
            ref.compare(sess.sql(_sql(name)).collect(), ref.answer(name, after), ref.ORDERED[name])
        for key in D._device_cache.keys():
            paths = {part[0] for part in key[0] if isinstance(part, tuple) and part and isinstance(part[0], str)}
            assert not paths & replaced, f"a column of a replaced file is resident: {sorted(paths & replaced)[:2]}"
    finally:
        hst.set_session(None)


@pytest.mark.parametrize("stated", [None, 3 << 30, 4096], ids=["default", "3GiB", "4KiB"])
def test_the_budget_is_the_sessions_key(tmp_path, stated):
    from hyperspace_tpu.config import DEFAULTS

    key = hst.keys.TPU_QUERY_DEVICE_CACHE_BYTES
    was = D.device_cache_cap()
    conf = {hst.keys.SYSTEM_PATH: str(tmp_path / "indexes")}
    if stated is not None:
        conf[key] = stated
    try:
        sess = hst.Session(conf=conf)
        want = DEFAULTS[key] if stated is None else stated
        assert sess.conf.device_cache_bytes == want and D.device_cache_cap() == want
        if stated == 4096:
            with pytest.raises(D.ResidentOverCap):
                D.check_fits_device_cache(1000, 1)
        else:
            D.check_fits_device_cache(1000, 1)
    finally:
        D.set_device_cache_bytes(was)


def test_eviction_counts_and_frees_nothing_a_query_holds(monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setattr(D, "_device_cache", BytesLRU(100))
    before = _counter("hs_device_cache_evictions_total")
    held = jnp.arange(8)
    D._device_cache_put(("a",), (held, None, 8), 64)
    mine = D._device_cache_get(("a",))[0]  # a running query's reference
    D._device_cache_put(("b",), (jnp.arange(8), None, 8), 64)
    assert D._device_cache_get(("a",)) is None
    assert _counter("hs_device_cache_evictions_total") - before == 1
    assert REGISTRY.gauge("hs_device_cache_bytes").value == 64
    assert int(mine.sum()) == 28  # still there for the query that looked it up


@pytest.mark.parametrize("name", QUERIES)
def test_a_float32_fold_fails_the_comparison(lake, name):
    _sess, _hs, frame, _root = lake
    want = ref.answer(name, frame)
    low = ref.answer(name, frame, dtype=np.float32)
    with pytest.raises(AssertionError, match="float gap"):
        ref.compare({c: low[c].to_numpy() for c in low.columns}, want, ref.ORDERED[name])


@pytest.mark.parametrize("text, days", [
    ("interval '90' day (3)", 90), ("interval '90' day(3)", 90), ("interval '61' day", 61), ("interval 7 days (2)", 7),
])
def test_an_interval_takes_a_leading_field_precision(text, days):
    from hyperspace_tpu.plan.expr import Lit
    from hyperspace_tpu.plan.sql import SqlError, parse

    q = parse(f"select a from t where d <= date '1998-12-01' - {text} group by a")
    lits = []

    def walk(e):
        if isinstance(e, Lit):
            lits.append(e.value)
        for c in e.children():
            walk(c)

    walk(q.where)
    assert np.timedelta64(days, "D") in lits
    with pytest.raises(SqlError):
        parse("select a from t where d <= date '1998-12-01' - interval '90' day (x)")
