"""A point lookup reads one bucket.

An equality (or ``IN``) conjunct on the single bucket column of a covering
index whose files were hashed under the current ``bucketHashVersion`` reads
only the bucket(s) its literals hash to and is answered there on the host —
with no conf key, for every literal a plan-cache template is bound with.
Every case: the answer equals the unpruned answer and pandas.
"""

import datetime
import glob
import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.exec import trace
from hyperspace_tpu.indexes.covering import bucket_of_file
from hyperspace_tpu.obs.metrics import REGISTRY
from hyperspace_tpu.plan import logical as L
from hyperspace_tpu.serving import QueryServer

NUM_BUCKETS = 8
BASE_DAY = np.datetime64("1994-01-01")


def _prune_counts():
    return tuple(
        REGISTRY.counter("hs_index_bucket_prune_total", "", result=r).value
        for r in ("pruned", "full")
    )


def _frame(n=6000, seed=28):
    """One table with an int, a date and a string key of ~300 values each;
    the values 7 / day 7 / 'name7' are present, 100007 / day 9000 / 'nobody'
    absent."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, 300, n)
    return pd.DataFrame(
        {
            "ki": ids.astype(np.int64),
            "kd": (BASE_DAY + ids.astype("timedelta64[D]")).astype("datetime64[s]"),
            "ks": np.array([f"name{i}" for i in ids], dtype=object),
            "o": rng.integers(0, 5, n).astype(np.int64),
            "v": np.arange(n, dtype=np.int64),
        }
    )


def _write(frame, root, parts=3):
    os.makedirs(root, exist_ok=True)
    table = pa.table(
        {
            "ki": pa.array(frame["ki"]),
            "kd": pa.array(frame["kd"].to_numpy().astype("datetime64[D]")),  # date32
            "ks": pa.array(frame["ks"], pa.string()),
            "o": pa.array(frame["o"]),
            "v": pa.array(frame["v"]),
        }
    )
    step = -(-len(frame) // parts)
    for i in range(parts):
        pq.write_table(table.slice(i * step, step), os.path.join(root, f"part-{i:05d}.parquet"))


@pytest.fixture(scope="module")
def lake(tmp_path_factory):
    """Three copies of one table, each indexed on one key kind (one index a
    table, so the filter rule has one candidate), plus a table whose index
    has two bucket columns."""
    root = tmp_path_factory.mktemp("bucket_pruning")
    frame = _frame()
    sess = hst.Session(
        conf={
            hst.keys.SYSTEM_PATH: str(root / "indexes"),
            hst.keys.NUM_BUCKETS: NUM_BUCKETS,
            hst.keys.TPU_QUERY_DEVICE_MIN_ROWS: 0,
        }
    )
    hst.set_session(sess)
    hs = hst.Hyperspace(sess)
    frames = {}
    for kind, key in (("int", ["ki"]), ("date", ["kd"]), ("str", ["ks"]), ("two", ["ki", "o"])):
        d = str(root / f"t_{kind}")
        _write(frame, d)
        frames[kind] = sess.read_parquet(d)
        included = [c for c in ("ki", "kd", "ks", "o", "v") if c not in key]
        hs.create_index(frames[kind], hst.CoveringIndexConfig(f"ix_{kind}", key, included))
        frames[kind].create_or_replace_temp_view(f"t_{kind}")
    sess.enable_hyperspace()
    yield sess, hs, frames, frame, root
    hst.set_session(None)


KEY = {"int": "ki", "date": "kd", "str": "ks"}
PRESENT = {"int": 7, "date": np.datetime64("1994-01-08"), "str": "name7"}
OTHERS = {
    "int": [11, 205],
    "date": [np.datetime64("1994-01-12"), np.datetime64("1994-07-25")],
    "str": ["name11", "name205"],
}
LOWEST = {"int": 0, "date": BASE_DAY, "str": "name"}
ABSENT = {"int": 100007, "date": np.datetime64("2018-08-23"), "str": "nobody"}


def _cases():
    out = []
    for kind in ("int", "date", "str"):
        k = KEY[kind]
        v = PRESENT[kind]
        out += [
            (f"{kind}-eq", kind, lambda c, v=v, k=k: c(k) == v, lambda f, v=v, k=k: f[k] == v, 1),
            (
                f"{kind}-in",
                kind,
                lambda c, kind=kind, k=k: c(k).isin([PRESENT[kind]] + OTHERS[kind]),
                lambda f, kind=kind, k=k: f[k].isin([PRESENT[kind]] + OTHERS[kind]),
                "in",
            ),
            (
                f"{kind}-eq-and-other",
                kind,
                lambda c, v=v, k=k: (c("o") < 3) & (c(k) == v),
                lambda f, v=v, k=k: (f["o"] < 3) & (f[k] == v),
                1,
            ),
            (
                f"{kind}-absent",
                kind,
                lambda c, kind=kind, k=k: c(k) == ABSENT[kind],
                lambda f, kind=kind, k=k: f[k] == ABSENT[kind],
                1,
            ),
            (f"{kind}-null", kind, lambda c, k=k: c(k) == hst.lit(None), lambda f: f["v"] < 0, 0),
            # an equality on another column (the range on the key keeps
            # the index applicable) decides no bucket
            (
                f"{kind}-non-bucket-column",
                kind,
                lambda c, kind=kind, k=k: (c(k) >= LOWEST[kind]) & (c("o") == 2),
                lambda f: f["o"] == 2,
                0,
            ),
        ]
    out += [
        # a literal of another numeric type hashes as the value it equals
        ("int-float-literal", "int", lambda c: c("ki") == 7.0, lambda f: f["ki"] == 7, 1),
        ("int-numpy-literal", "int", lambda c: c("ki") == np.int32(7), lambda f: f["ki"] == 7, 1),
        ("int-fraction-literal", "int", lambda c: c("ki") == 7.5, lambda f: f["v"] < 0, 1),
        # a date asked for as a string or a python date is compared as a
        # date, so it is hashed as one
        ("date-string-literal", "date", lambda c: c("kd") == "1994-01-08",
         lambda f: f["kd"] == np.datetime64("1994-01-08"), 1),
        ("date-python-literal", "date", lambda c: c("kd") == datetime.date(1994, 1, 8),
         lambda f: f["kd"] == np.datetime64("1994-01-08"), 1),
        ("date-finer-unit-literal", "date", lambda c: c("kd") == np.datetime64("1994-01-08T00:00:00"),
         lambda f: f["kd"] == np.datetime64("1994-01-08"), 1),
        # a literal of a kind the column is not compared in as-is: no guess
        ("str-number-literal", "str", lambda c: c("ks") == 7, lambda f: f["v"] < 0, 0),
        # a range, and an equality under OR, decide no bucket
        ("int-range", "int", lambda c: (c("ki") >= 7) & (c("ki") <= 7), lambda f: f["ki"] == 7, 0),
        ("int-eq-or-eq", "int", lambda c: (c("ki") == 7) | (c("ki") == 11),
         lambda f: f["ki"].isin([7, 11]), 0),
        # two bucket columns: one literal does not decide the bucket
        ("two-column-bucket-spec", "two", lambda c: (c("ki") == 7) & (c("o") == 2),
         lambda f: (f["ki"] == 7) & (f["o"] == 2), 0),
    ]
    return out


CASES = _cases()


def _files_holding(entry, key, mask_fn):
    """The index files that hold a row the predicate keeps, read from disk."""
    out = []
    for f in entry.content.files:
        t = pq.read_table(f).to_pandas()
        if key == "kd":
            t[key] = t[key].astype("datetime64[s]")
        if bool(mask_fn(t).any()):
            out.append(f)
    return out


def _run(sess, q, frame, mask_fn, buckets):
    """Answer of ``q`` pruned == unpruned == pandas; returns what was read."""
    before = _prune_counts()
    with trace.recording() as events:
        got = np.sort(q.collect()["v"])
    growth = tuple(a - b for a, b in zip(_prune_counts(), before))
    sess.disable_hyperspace()
    try:
        unpruned = np.sort(q.collect()["v"])
    finally:
        sess.enable_hyperspace()
    want = np.sort(frame["v"][mask_fn(frame)].to_numpy())
    np.testing.assert_array_equal(got, unpruned)
    np.testing.assert_array_equal(got, want)
    (scan,) = [p for p in L.collect(q.optimized_plan(), lambda x: isinstance(x, L.IndexScan))]
    scan_events = [v for k, v in events if k == "scan"]
    if buckets == 0:
        assert scan.pruned_buckets is None, scan.describe()
        assert list(scan.files) == list(scan.entry.content.files)
        assert growth == (0, 1) and scan_events == ["index"], (growth, scan_events)
    else:
        assert scan.pruned_buckets is not None, scan.describe()
        if buckets != "in":
            assert len(scan.pruned_buckets) == buckets
        assert {bucket_of_file(f) for f in scan.files} == set(scan.pruned_buckets)
        assert len(scan.files) < len(scan.entry.content.files)
        assert growth == (1, 0), growth
        assert scan_events == [f"index-bucket-pruned({len(scan.pruned_buckets)} buckets)"]
        # the host answered it: no device filter event, whatever deviceMinRows says
        assert [v for k, v in events if k == "filter"] == ["host"], trace.summarize(events)
    return scan, len(want)


@pytest.mark.parametrize("name,kind,cond,mask_fn,buckets", CASES, ids=[c[0] for c in CASES])
def test_pruned_answer_is_the_unpruned_answer(lake, name, kind, cond, mask_fn, buckets):
    sess, hs, frames, frame, _ = lake
    df = frames[kind]
    q = df.filter(cond(hst.col)).select("v")
    scan, n_rows = _run(sess, q, frame, mask_fn, buckets)
    if buckets and kind in KEY:
        # every index file that holds a matching row is among those read, and
        # a present key's rows are there to be found
        holding = _files_holding(scan.entry, KEY[kind], mask_fn)
        assert set(holding) <= set(scan.files)
        if "absent" not in name and "fraction" not in name:
            assert n_rows > 0 and holding


def _doctor_hash_version(system_path, index):
    for p in glob.glob(os.path.join(system_path, index, "_hyperspace_log", "*")):
        with open(p) as f:
            text = f.read()
        if "bucketHashVersion" in text:
            with open(p, "w") as f:
                f.write(text.replace('"bucketHashVersion": "2"', '"bucketHashVersion": "1"'))


@pytest.mark.parametrize("how", ["older-bucket-hash-version", "hybrid-scan-appended-files"])
def test_untrusted_or_hybrid_layout_does_not_prune(tmp_path, how):
    frame = _frame(n=3000, seed=3)
    d = str(tmp_path / "t")
    _write(frame, d)
    conf = {hst.keys.SYSTEM_PATH: str(tmp_path / "indexes"), hst.keys.NUM_BUCKETS: NUM_BUCKETS}
    sess = hst.Session(conf=conf)
    hst.set_session(sess)
    try:
        hs = hst.Hyperspace(sess)
        hs.create_index(sess.read_parquet(d), hst.CoveringIndexConfig("ix", ["ki"], ["v"]))
        if how == "older-bucket-hash-version":
            _doctor_hash_version(str(tmp_path / "indexes"), "ix")
            sess = hst.Session(conf=conf)  # a session that reads the doctored log
            hst.set_session(sess)
        else:
            extra = _frame(n=300, seed=4)
            extra["v"] += 1_000_000
            _write(extra, os.path.join(d, "appended"), parts=1)
            frame = pd.concat([frame, extra], ignore_index=True)
            sess.conf.set(hst.keys.HYBRID_SCAN_ENABLED, True)
            sess.conf.set(hst.keys.HYBRID_SCAN_MAX_APPENDED_RATIO, 0.9)
        sess.enable_hyperspace()
        q = sess.read_parquet(d).filter(hst.col("ki") == 7).select("v")
        before = _prune_counts()
        with trace.recording() as events:
            got = np.sort(q.collect()["v"])
        assert _prune_counts()[0] == before[0]
        assert not any("bucket-pruned" in v for _, v in events), trace.summarize(events)
        scans = [p for p in L.collect(q.optimized_plan(), lambda x: isinstance(x, L.IndexScan))]
        assert scans and all(s.pruned_buckets is None and s.bucket_key is None for s in scans)
        assert all(list(s.files) == list(s.entry.content.files) for s in scans)
        np.testing.assert_array_equal(got, np.sort(frame["v"][frame["ki"] == 7].to_numpy()))
        sess.disable_hyperspace()
        np.testing.assert_array_equal(got, np.sort(q.collect()["v"]))
    finally:
        hst.set_session(None)


def test_join_rule_sides_are_pruned_by_the_same_pass(lake):
    """JoinIndexRule no longer prunes in the rule: its sides carry a
    ``bucket_key`` and the one pass narrows them, key or no key."""
    sess, hs, frames, frame, root = lake
    d = str(root / "t_right")
    if not os.path.isdir(d):
        _write(frame, d)
        right = sess.read_parquet(d)
        hs.create_index(right, hst.CoveringIndexConfig("ix_right", ["ki"], ["v"]))
    right = sess.read_parquet(d)
    left = frames["int"].filter(hst.col("ki") == 7).select("ki", "o")
    q = left.join(right.select("ki", "v"), on="ki")
    scans = [p for p in L.collect(q.optimized_plan(), lambda x: isinstance(x, L.IndexScan))]
    by_name = {s.entry.name: s for s in scans}
    assert set(by_name) == {"ix_int", "ix_right"}, q.optimized_plan().pretty()
    assert by_name["ix_int"].pruned_buckets is not None and by_name["ix_right"].pruned_buckets is None
    assert by_name["ix_int"].bucket_spec is not None  # the join rule advertises the layout
    on = q.collect()
    sess.disable_hyperspace()
    try:
        off = q.collect()
    finally:
        sess.enable_hyperspace()
    assert len(on["v"]) == len(off["v"]) > 0
    np.testing.assert_array_equal(np.sort(on["v"]), np.sort(off["v"]))


def test_bucket_files_map_is_kept_on_the_content(lake):
    from hyperspace_tpu.rules.utils import index_files_for_buckets

    sess, hs, frames, _, _ = lake
    entry = hs._manager.get_index("ix_int")
    files = entry.content.files
    assert index_files_for_buckets(entry, None) == files
    by_bucket = {}
    for f in files:
        by_bucket.setdefault(bucket_of_file(f), []).append(f)
    for b, want in by_bucket.items():
        assert index_files_for_buckets(entry, [b]) == want
    some = sorted(by_bucket)[:3]
    assert index_files_for_buckets(entry, some) == [f for f in files if bucket_of_file(f) in some]
    assert index_files_for_buckets(entry, [NUM_BUCKETS + 5]) == []
    assert "_bucket_files" in entry.content.__dict__


# --- through the server: one template, every literal its own bucket --------


def _keys_by_bucket(entry):
    """{bucket: sorted int keys stored in it}, read from the index files."""
    out = {}
    for f in entry.content.files:
        keys = pq.read_table(f, columns=["ki"]).column("ki").to_numpy()
        out.setdefault(bucket_of_file(f), set()).update(int(k) for k in keys)
    return {b: sorted(ks) for b, ks in out.items()}


def test_fifty_literals_one_miss_and_each_reads_its_own_bucket(lake):
    sess, hs, frames, frame, _ = lake
    entry = hs._manager.get_index("ix_int")
    by_bucket = _keys_by_bucket(entry)
    bucket_of_key = {k: b for b, ks in by_bucket.items() for k in ks}
    keys = [int(k) for k in np.random.default_rng(5).permutation(300)[:50]]
    assert len({bucket_of_key[k] for k in keys}) > 4  # the literals span buckets
    with QueryServer(sess, workers=1, micro_batch_enabled=False) as srv:
        read = []
        real = srv.bucket_cache.read

        def spy(files, columns, **kw):
            read.append(list(files))
            return real(files, columns, **kw)

        srv.bucket_cache.read = spy
        for k in keys:
            del read[:]
            got = srv.query(f"SELECT v FROM t_int WHERE ki = {k}")
            np.testing.assert_array_equal(
                np.sort(got["v"]), np.sort(frame["v"][frame["ki"] == k].to_numpy())
            )
            # its own bucket: never the bucket of the key that compiled the template
            assert [{bucket_of_file(f) for f in fs} for fs in read] == [{bucket_of_key[k]}], (k, read)
        stats = srv.stats()["planCache"]
    assert (stats["misses"], stats["paramHits"], stats["exactHits"]) == (1, 49, 0), stats


def test_template_is_literal_free_and_binds_another_bucket(lake):
    """The hazard the design must not have: compile with key a, query key b
    in another bucket. The stored template holds no prune at all; the bound
    plan holds b's."""
    from hyperspace_tpu.serving.fingerprint import plan_fingerprint

    sess, hs, frames, frame, _ = lake
    by_bucket = _keys_by_bucket(hs._manager.get_index("ix_int"))
    (ba, a), (bb, b) = [(bk, ks[0]) for bk, ks in sorted(by_bucket.items())[:2]]
    assert ba != bb
    with QueryServer(sess, workers=1) as srv:
        srv.query(f"SELECT v FROM t_int WHERE ki = {a}")
        (entry,) = [e for e in srv.plan_cache._entries.values()]
        assert entry.parameterizable
        (tscan,) = [p for p in L.collect(entry.template, lambda x: isinstance(x, L.IndexScan))]
        assert tscan.pruned_buckets is None and list(tscan.files) == list(tscan.entry.content.files)
        assert entry.prefetch_leaves == []  # nothing is read whatever the literal
        fp_b = plan_fingerprint(sess.sql(f"SELECT v FROM t_int WHERE ki = {b}").plan)
        (bscan,) = [p for p in L.collect(entry.bind(fp_b), lambda x: isinstance(x, L.IndexScan))]
        assert bscan.pruned_buckets == [bb]
        assert {bucket_of_file(f) for f in bscan.files} == {bb}
        got = srv.query(f"SELECT v FROM t_int WHERE ki = {b}")
        np.testing.assert_array_equal(
            np.sort(got["v"]), np.sort(frame["v"][frame["ki"] == b].to_numpy())
        )
        # a range on the same column shares nothing with the point template
        # and is prefetched whole
        srv.query("SELECT v FROM t_int WHERE ki >= 7 AND ki < 9")
        ranged = [e for e in srv.plan_cache._entries.values() if e is not entry]
        assert [len(e.prefetch_leaves) for e in ranged] == [1]


def test_queued_lookups_share_one_scan_of_their_buckets(lake):
    """Requests of one template that queue up behind a busy worker are
    answered from ONE read of the union of their buckets — each from its own
    rows, none from the bucket of the key that compiled the template."""
    import threading

    sess, hs, frames, frame, _ = lake
    by_bucket = _keys_by_bucket(hs._manager.get_index("ix_int"))
    keys = [ks[0] for _, ks in sorted(by_bucket.items())[:4]]
    with QueryServer(sess, workers=1, micro_batch_enabled=True, micro_batch_max_requests=8) as srv:
        srv.query(f"SELECT v FROM t_int WHERE ki = {keys[0]}")  # compile the template
        gate = threading.Event()
        real_group = srv._process_group

        def held(group):
            gate.wait(5.0)
            real_group(group)

        srv._process_group = held
        read = []
        real_read = srv.bucket_cache.read
        srv.bucket_cache.read = lambda files, columns, **kw: (
            read.append(list(files)), real_read(files, columns, **kw))[1]
        # the worker takes the blocker and waits; the lookups queue up behind it
        blocker = srv.submit("SELECT v FROM t_str WHERE ks >= 'name299'")
        futures = [srv.submit(f"SELECT v FROM t_int WHERE ki = {k}") for k in keys[1:]]
        before = _prune_counts()
        gate.set()
        blocker.result(10.0)
        for k, fut in zip(keys[1:], futures):
            np.testing.assert_array_equal(
                np.sort(fut.result(10.0)["v"]), np.sort(frame["v"][frame["ki"] == k].to_numpy())
            )
        assert srv.stats()["batchedRequests"] == 3
    growth = tuple(a - b for a, b in zip(_prune_counts(), before))
    assert growth == (1, 1), growth  # the blocker's range read whole, the three lookups one pruned scan
    want = {b for b, ks in by_bucket.items() if ks[0] in keys[1:]}
    of_index = [fs for fs in read if os.sep + "ix_int" + os.sep in fs[0]]
    assert [{bucket_of_file(f) for f in fs} for fs in of_index] == [want], read


def test_no_program_compiles_across_pruned_lookups_of_many_buckets(tmp_path):
    """deviceMinRows=0 sends every filter it can to the device; a pruned
    bucket would be a new row count, so a new program shape, each time."""
    n, buckets = 20_000, 24
    rng = np.random.default_rng(11)
    # skewed keys: the buckets hold clearly different row counts
    ki = np.minimum(rng.zipf(1.3, n), 400).astype(np.int64)
    d = str(tmp_path / "t")
    os.makedirs(d)
    pq.write_table(pa.table({"ki": ki, "v": np.arange(n, dtype=np.int64)}), os.path.join(d, "p.parquet"))
    sess = hst.Session(
        conf={
            hst.keys.SYSTEM_PATH: str(tmp_path / "indexes"),
            hst.keys.NUM_BUCKETS: buckets,
            hst.keys.TPU_QUERY_DEVICE_EXECUTION: True,
            hst.keys.TPU_QUERY_DEVICE_MIN_ROWS: 0,
        }
    )
    hst.set_session(sess)
    try:
        hs = hst.Hyperspace(sess)
        df = sess.read_parquet(d)
        hs.create_index(df, hst.CoveringIndexConfig("ix", ["ki"], ["v"]))
        df.create_or_replace_temp_view("t_skew")
        sess.enable_hyperspace()
        entry = hs._manager.get_index("ix")
        by_bucket = _keys_by_bucket(entry)
        sizes = {
            b: sum(pq.read_metadata(f).num_rows for f in entry.content.files if bucket_of_file(f) == b)
            for b in by_bucket
        }
        picked = sorted(by_bucket)[:20]
        assert len(picked) == 20 and len({sizes[b] for b in picked}) >= 15, sizes
        compiles = REGISTRY.counter("hs_xla_compiles_total", "")
        with QueryServer(sess, workers=2) as srv:
            # a range compiles the whole-index filter program: the device path is live
            srv.query("SELECT v FROM t_skew WHERE ki >= 3 AND ki < 5")
            before = compiles.value
            for b in picked:
                k = by_bucket[b][0]
                got = srv.query(f"SELECT v FROM t_skew WHERE ki = {k}")
                np.testing.assert_array_equal(np.sort(got["v"]), np.flatnonzero(ki == k))
            assert compiles.value == before
    finally:
        hst.set_session(None)
