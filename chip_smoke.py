#!/usr/bin/env python3
"""chip_smoke.py — build -> index -> query -> serve, once, on the chip.

The quickest proof that the system still starts on a TPU: one process drives
the main path through the entry points a user calls (``Session`` ->
``Hyperspace.create_index`` -> ``sess.sql(...).collect()`` -> ``QueryServer``)
over TPC-H-shaped data at ``--sf`` (default 1: 6,000,000 lineitem rows), checks
every answer against a pandas computation over the same Parquet files, checks
every index file against the host hash, and proves the two Pallas kernels went
through Mosaic. Any failed check raises; nothing is downgraded to a warning.

It exits non-zero unless ``jax.devices()[0].platform == "tpu"``. The last line
of stdout is the verdict the driver reads, one JSON object with exactly
``{"ok": ..., "device": {"platform", "kind", "count"}}``. The line before it,
``report: {...}``, is the full record: versions, ``sf``, per-stage wall
seconds, both dispatch summaries, digests and the compile cache in use. Wall
times in it are smoke timings (one cold reading each, compile included), not
benchmark results.

The second query pass asserts the device programs it exists to reach
(``filter: device``, ``join: device-smj``, ``agg: device-grouped-scan``,
``agg: device-fused-scan``). They
are what the product dispatches while an index stays under its 1 GiB
streaming gates, as at the default ``--sf``; from about ``--sf 5`` it streams
the join and the aggregates instead and that assertion says so, after both
passes' answers have been checked.

``--rehearse-on-cpu`` runs the same stages on the CPU backend (Pallas in
interpret mode) to debug the script at a tiny ``--sf``; its output says it is
a rehearsal and ``ok`` stays false. ``--parallel`` sets
``hyperspace.parallel.enabled`` in the session conf (several chips on one
host) and runs what that changes: the distributed build and the sharded q6-
and q1-shape queries, checked like the single-device ones, with every device
holding a shard. The digests in the report line compare a run with another at
the same ``--sf`` and ``--seed``.
"""

from __future__ import annotations

import argparse
import hashlib
import importlib.metadata
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager

import numpy as np

FLOAT_RTOL = 1e-9  # float sums vs pandas: summation order differs, values don't

D_Q6_LO, D_Q6_HI = "1994-01-01", "1995-01-01"
D_Q3 = "1995-03-15"
D_Q1 = "1998-09-02"


def queries(point_key: int) -> dict:
    return {
        "q6": f"""
            SELECT SUM(l_extendedprice * l_discount) AS revenue FROM lineitem
            WHERE l_shipdate >= date '{D_Q6_LO}' AND l_shipdate < date '{D_Q6_HI}'
              AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24""",
        "join": """
            SELECT l_orderkey, l_extendedprice, o_totalprice FROM lineitem, orders
            WHERE l_orderkey = o_orderkey AND o_totalprice < 1000.0""",
        "q3": f"""
            SELECT l_orderkey, SUM(l_extendedprice * (1 - l_discount)) AS revenue,
                   o_orderdate, o_shippriority
            FROM orders, lineitem
            WHERE l_orderkey = o_orderkey AND o_orderdate < date '{D_Q3}'
              AND l_shipdate > date '{D_Q3}'
            GROUP BY l_orderkey, o_orderdate, o_shippriority
            ORDER BY revenue DESC, o_orderdate LIMIT 10""",
        "q1": f"""
            SELECT l_quantity, COUNT(*) AS n, SUM(l_extendedprice) AS sum_price,
                   AVG(l_discount) AS avg_disc
            FROM lineitem WHERE l_shipdate <= date '{D_Q1}' GROUP BY l_quantity""",
        "point": f"""
            SELECT l_extendedprice, l_quantity FROM lineitem
            WHERE l_orderkey = {point_key}""",
        # a float64 GROUP BY key: the device grouping codes stay floats
        "groupf": f"""
            SELECT l_discount, COUNT(*) AS n, SUM(l_quantity) AS sum_qty
            FROM lineitem WHERE l_shipdate <= date '{D_Q1}' GROUP BY l_discount""",
        # rows, not an aggregate: the device filter's mask comes back to the host
        "year": f"""
            SELECT l_extendedprice, l_quantity FROM lineitem
            WHERE l_shipdate >= date '{D_Q6_LO}' AND l_shipdate < date '{D_Q6_HI}'""",
    }


def references(li, o, point_key: int) -> dict:
    """The same answers from pandas over the source Parquet files."""
    d = np.datetime64
    m = (
        (li.l_shipdate >= d(D_Q6_LO)) & (li.l_shipdate < d(D_Q6_HI))
        & (li.l_discount >= 0.05) & (li.l_discount <= 0.07) & (li.l_quantity < 24)
    )
    q6 = {"revenue": np.array([(li.l_extendedprice[m] * li.l_discount[m]).sum()])}

    j = li.merge(o[o.o_totalprice < 1000.0], left_on="l_orderkey", right_on="o_orderkey")
    join = {c: j[c].to_numpy() for c in ("l_orderkey", "l_extendedprice", "o_totalprice")}

    j3 = li[li.l_shipdate > d(D_Q3)].merge(
        o[o.o_orderdate < d(D_Q3)], left_on="l_orderkey", right_on="o_orderkey"
    )
    j3 = j3.assign(revenue=j3.l_extendedprice * (1 - j3.l_discount))
    g3 = j3.groupby(["l_orderkey", "o_orderdate", "o_shippriority"], as_index=False).revenue.sum()
    g3 = g3.sort_values(["revenue", "o_orderdate"], ascending=[False, True], kind="stable").head(10)
    q3 = {c: g3[c].to_numpy() for c in ("l_orderkey", "revenue", "o_orderdate", "o_shippriority")}

    g1 = li[li.l_shipdate <= d(D_Q1)].groupby("l_quantity", as_index=False).agg(
        n=("l_quantity", "size"), sum_price=("l_extendedprice", "sum"), avg_disc=("l_discount", "mean")
    )
    q1 = {c: g1[c].to_numpy() for c in ("l_quantity", "n", "sum_price", "avg_disc")}

    p = li[li.l_orderkey == point_key]
    point = {c: p[c].to_numpy() for c in ("l_extendedprice", "l_quantity")}

    gf = li[li.l_shipdate <= d(D_Q1)].groupby("l_discount", as_index=False).agg(
        n=("l_discount", "size"), sum_qty=("l_quantity", "sum")
    )
    groupf = {c: gf[c].to_numpy() for c in ("l_discount", "n", "sum_qty")}

    y = li[(li.l_shipdate >= d(D_Q6_LO)) & (li.l_shipdate < d(D_Q6_HI))]
    year = {c: y[c].to_numpy() for c in ("l_extendedprice", "l_quantity")}
    return {"q6": q6, "join": join, "q3": q3, "q1": q1, "point": point, "groupf": groupf, "year": year}


# q3 keeps its ORDER BY ... LIMIT order; the others are row sets
ORDERED = {"q3"}


def canonical_rows(batch: dict) -> dict:
    """A row set in one fixed order: sorted by every column, names ascending."""
    batch = {c: np.asarray(v) for c, v in batch.items()}
    order = np.lexsort([batch[c] for c in sorted(batch, reverse=True)])
    return {c: v[order] for c, v in batch.items()}


def assert_answer(name: str, got: dict, want: dict) -> None:
    """Keys and counts exact, float aggregates to FLOAT_RTOL."""
    assert set(got) == set(want), f"{name}: columns {sorted(got)} != {sorted(want)}"
    n = len(next(iter(want.values())))
    assert all(len(v) == n for v in got.values()), f"{name}: {n} rows expected"
    if name not in ORDERED:
        got, want = canonical_rows(got), canonical_rows(want)
    for c, w in want.items():
        g, w = np.asarray(got[c]), np.asarray(w)
        if w.dtype.kind == "f":
            np.testing.assert_allclose(g, w, rtol=FLOAT_RTOL, atol=0, err_msg=f"{name}.{c}")
        elif w.dtype.kind == "M":
            np.testing.assert_array_equal(
                g.astype("datetime64[D]"), w.astype("datetime64[D]"), err_msg=f"{name}.{c}"
            )
        else:
            np.testing.assert_array_equal(g, w, err_msg=f"{name}.{c}")


def load_frame(path: str):
    import pandas as pd
    import pyarrow.parquet as pq

    t = pq.read_table(path)
    return pd.DataFrame({c: t.column(c).to_numpy() for c in t.column_names})


def check_covering_index(entry, key: str, num_buckets: int, source_rows: int) -> str:
    """Every index file holds exactly the rows the HOST hash sends to its
    bucket, sorted by the key; together they hold every source row. Returns a
    digest of the index content (bucket order, then row order)."""
    import pyarrow.parquet as pq

    from hyperspace_tpu.ops import encode, hashing

    rows = 0
    runs = {}  # bucket -> content digest of each of its files (one per build chunk)
    for f in entry.content.files:
        bucket = int(os.path.basename(f).split("-")[1])
        t = pq.read_table(f)
        col = t.column(key).to_numpy(zero_copy_only=False)
        want = hashing.bucket_ids_np([encode.hash_input_uint32(col)], num_buckets)
        assert (want == bucket).all(), f"{entry.name}: bucket {bucket} holds rows of other buckets"
        order = encode.sort_key_int64(col)  # the order the build program sorts by
        assert (order[1:] >= order[:-1]).all(), f"{entry.name}: bucket {bucket} run not sorted"
        rows += t.num_rows
        h = hashlib.sha256()
        for name in sorted(t.column_names):
            arr = t.column(name).to_numpy(zero_copy_only=False)
            h.update(arr.astype("U").tobytes() if arr.dtype.kind == "O" else arr.tobytes())
        runs.setdefault(bucket, []).append(h.digest())
    assert rows == source_rows, f"{entry.name}: {rows} index rows for {source_rows} source rows"
    # bucket-major, a bucket's runs in content order: the digest does not
    # depend on the random part of the file names
    digest = hashlib.sha256()
    for bucket in sorted(runs):
        for r in sorted(runs[bucket]):
            digest.update(r)
    return digest.hexdigest()[:16]


def check_minmax_index(entry, sketches, li_dir: str) -> None:
    """The sketch table's per-file bounds equal numpy min/max per source file."""
    import pyarrow.parquet as pq

    sk = pq.read_table(entry.content.files[0])
    files = sorted(os.path.join(li_dir, f) for f in os.listdir(li_dir) if f.endswith(".parquet"))
    assert sk.num_rows == len(files), f"{sk.num_rows} sketch rows for {len(files)} files"
    for s in sketches:
        lo, hi = (sk.column(n).to_numpy() for n in s.output_names())
        cols = [pq.read_table(f, columns=[s.expr]).column(0).to_numpy() for f in files]
        want = sorted((c.min(), c.max()) for c in cols)
        assert sorted(zip(lo, hi)) == want, f"MinMaxSketch({s.expr}) differs from numpy"


def digest_columns(batch: dict, key: str) -> dict:
    """Per-column digest of a grouped result's bytes, rows ordered by ``key``."""
    order = np.argsort(np.asarray(batch[key]), kind="stable")
    return {
        c: hashlib.sha256(np.ascontiguousarray(np.asarray(batch[c])[order]).tobytes()).hexdigest()[:16]
        for c in sorted(batch)
    }


def metric_samples(name: str) -> dict:
    """One registry metric as {label values, in label-name order: value}."""
    from hyperspace_tpu.obs.metrics import REGISTRY

    series = REGISTRY.snapshot().get(name, {}).get("series", [])
    return {tuple(v for _, v in sorted(s["labels"].items())): s["value"] for s in series}


# Four chips are charged fourfold, so --parallel runs what
# hyperspace.parallel.enabled changes: the distributed build (one all_to_all)
# and the sharded filter and grouped aggregate, both over li_shipdate.
PARALLEL_BUILDS = ("li_shipdate",)
PARALLEL_QUERIES = ("q6", "q1", "year")


def device_gate(args) -> tuple:
    """The script itself is the first thing in the process to touch JAX."""
    import jax
    import jaxlib

    dev0 = jax.devices()[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind, "count": len(jax.devices())}
    versions = {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
    }
    print(f"device: {device}  versions: {versions}", flush=True)
    if args.rehearse_on_cpu:
        if device["platform"] != "cpu":
            raise SystemExit("--rehearse-on-cpu needs JAX_PLATFORMS=cpu")
        print("REHEARSAL on the CPU backend: debugs this script, proves nothing about the chip",
              flush=True)
    elif device["platform"] != "tpu":
        raise SystemExit(f"no TPU: jax.devices()[0].platform == {device['platform']!r}")
    if args.parallel and device["count"] < 2:
        raise SystemExit("--parallel needs more than one device")
    return device, versions


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--sf", type=float, default=1.0, help="TPC-H scale factor (default 1)")
    ap.add_argument("--seed", type=int, default=0, help="data generation seed")
    ap.add_argument("--parallel", action="store_true",
                    help="set hyperspace.parallel.enabled (needs more than one device)")
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="debug run on the CPU backend; never a pass")
    args = ap.parse_args(argv)
    device, versions = device_gate(args)
    rehearsal = args.rehearse_on_cpu

    import jax

    import hyperspace_tpu as hst
    from benchmarks import datagen
    from hyperspace_tpu import native
    from hyperspace_tpu.exec import trace
    from hyperspace_tpu.ops import kernels, sort
    from hyperspace_tpu.serving import QueryServer

    cache = {"hits": 0, "misses": 0}

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache["misses"] += 1

    jax.monitoring.register_event_listener(on_event)

    seconds: dict = {}

    @contextmanager
    def stage(name: str):
        t0 = time.perf_counter()
        print(f"[{name}] ...", flush=True)
        yield
        seconds[name] = round(time.perf_counter() - t0, 3)
        print(f"[{name}] {seconds[name]} s", flush=True)

    native._load()  # a native build failure on this host fails the smoke
    root = tempfile.mkdtemp(prefix="hs_chip_smoke_")
    try:
        with stage("datagen"):
            li_dir = datagen.gen_lineitem(root, args.sf, seed=args.seed)
            o_dir = datagen.gen_orders(root, args.sf, seed=args.seed + 1)
            c_dir = datagen.gen_customer(root, args.sf, seed=args.seed + 2)
        with stage("reference_load"):
            li_pd, o_pd = load_frame(li_dir), load_frame(o_dir)
            c_rows = len(load_frame(c_dir))
        point_key = int(li_pd.l_orderkey.iloc[len(li_pd) // 2])

        conf = {hst.keys.SYSTEM_PATH: os.path.join(root, "_indexes")}
        if args.parallel:
            conf[hst.keys.PARALLEL_ENABLED] = True
        sess = hst.Session(conf=conf)
        hst.set_session(sess)
        hs = hst.Hyperspace(sess)
        nb = sess.conf.num_buckets
        li, o, c = (sess.read_parquet(d) for d in (li_dir, o_dir, c_dir))
        li.create_or_replace_temp_view("lineitem")
        o.create_or_replace_temp_view("orders")
        c.create_or_replace_temp_view("customer")

        # -- kernel proof: the lowered build program and min/max call hold the
        # Mosaic custom call, i.e. neither Pallas kernel is interpreted
        interpret = kernels._use_interpret()
        assert interpret == (device["platform"] == "cpu")
        n_class = sort.padded_size(min(len(li_pd), sess.conf.build_batch_rows))
        build_text = sort._build_sorted.lower(
            (jax.ShapeDtypeStruct((n_class,), np.int32),), (), np.int32(n_class),
            nb, ("i",), interpret,
        ).as_text()
        mm = jax.ShapeDtypeStruct((16, 4096), np.int32)
        minmax_text = kernels._minmax_call.lower(mm, mm, mm, interpret).as_text()
        mosaic = {"build": "tpu_custom_call" in build_text, "minmax": "tpu_custom_call" in minmax_text}
        if not rehearsal:
            assert all(mosaic.values()), f"Pallas kernel not lowered through Mosaic: {mosaic}"

        # -- build, default conf ------------------------------------------------
        digests = {}
        CI = hst.CoveringIndexConfig
        builds = [
            ("li_orderkey", li, CI("li_orderkey", ["l_orderkey"],
                                   ["l_extendedprice", "l_discount", "l_shipdate", "l_quantity"]),
             "l_orderkey", len(li_pd)),
            ("o_orderkey", o, CI("o_orderkey", ["o_orderkey"],
                                 ["o_totalprice", "o_orderdate", "o_shippriority"]),
             "o_orderkey", len(o_pd)),
            ("li_shipdate", li, CI("li_shipdate", ["l_shipdate"],
                                   ["l_extendedprice", "l_discount", "l_quantity"]),
             "l_shipdate", len(li_pd)),
            # string key: the host-hash plane of the build program
            ("c_mktsegment", c, CI("c_mktsegment", ["c_mktsegment"], ["c_custkey", "c_acctbal"]),
             "c_mktsegment", c_rows),
            # float64 key: the "f" branch of the device hash
            ("o_totalprice", o, CI("o_totalprice", ["o_totalprice"], ["o_orderkey"]),
             "o_totalprice", len(o_pd)),
        ]
        sql = queries(point_key)
        if args.parallel:
            builds = [b for b in builds if b[0] in PARALLEL_BUILDS]
            sql = {name: sql[name] for name in PARALLEL_QUERIES}
        for name, df, cfg, key, rows in builds:
            with stage(f"build_{name}"):
                entry = hs.create_index(df, cfg)
            with stage(f"verify_{name}"):
                digests[name] = check_covering_index(entry, key, nb, rows)

        if args.parallel:
            peak = [d.memory_stats()["peak_bytes_in_use"] for d in jax.devices()
                    if d.memory_stats() is not None]
            assert all(p > 0 for p in peak), f"a device held nothing during the build: {peak}"
        else:
            # a second build in li_orderkey's size class must compile nothing
            compiles0 = metric_samples("hs_xla_compiles_total")
            programs0 = sort._build_sorted._cache_size()
            with stage("build_li_partkey"):
                entry = hs.create_index(li, CI("li_partkey", ["l_partkey"], ["l_quantity"]))
            assert metric_samples("hs_xla_compiles_total") == compiles0, "second build counted a compile"
            assert sort._build_sorted._cache_size() == programs0, "second build compiled a program"
            with stage("verify_li_partkey"):
                digests["li_partkey"] = check_covering_index(entry, "l_partkey", nb, len(li_pd))

            sketches = [hst.MinMaxSketch("l_partkey"), hst.MinMaxSketch("l_extendedprice")]
            with stage("build_li_minmax"):
                entry = hs.create_index(li, hst.DataSkippingIndexConfig("li_minmax", *sketches))
            check_minmax_index(entry, sketches, li_dir)

        # -- query: default conf, then the co-located-host settings -----------
        sess.enable_hyperspace()
        want = references(li_pd, o_pd, point_key)
        for name, text in sql.items():
            plan = sess.sql(text).optimized_plan().pretty()
            assert "IndexScan" in plan, f"{name}: no IndexScan in\n{plan}"

        def run_queries(label: str):
            answers = {}
            with trace.recording() as events:
                for name, text in sql.items():
                    with stage(f"query_{label}_{name}"):
                        answers[name] = sess.sql(text).collect()
            for name in sql:
                assert_answer(name, answers[name], want[name])
            summary = trace.summarize(events)
            print(f"dispatch summary ({label}):\n{summary}", flush=True)
            return answers, summary, events

        _, summary_default, _ = run_queries("default")

        sess.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        sess.conf.set(hst.keys.TPU_JOIN_DEVICE_SPAN_MAX_BYTES, 4 << 30)
        sess.conf.set(hst.keys.TPU_JOIN_DEVICE_MATERIALIZE_MAX_BYTES, 4 << 30)
        before = metric_samples("hs_device_fallback_total")  # keyed (op, reason)
        answers, summary_device, events = run_queries("device")
        tags = (
            ("filter: device-sharded", "agg: device-grouped-scan") if args.parallel
            else ("filter: device", "join: device-smj", "agg: device-grouped-scan", "agg: device-fused-scan")
        )
        for tag in tags:
            assert tag in summary_device, f"{tag!r} missing from the device pass"
        assert not [e for e in events if e[1].endswith("-fallback")], summary_device
        fell = {k: v - before.get(k, 0) for k, v in metric_samples("hs_device_fallback_total").items()}
        fell = {k: v for k, v in fell.items() if v and k[1] != "min-rows"}
        assert not fell, f"device fallbacks in the device pass: {fell}"
        digests["q1"] = digest_columns(answers["q1"], "l_quantity")

        if args.parallel:
            held = set().union(*(a.sharding.device_set for a in jax.live_arrays()))
            assert held == set(jax.devices()), f"only {len(held)} devices hold a shard"
        else:
            # each request is a whole-table analytic query: the queue deadline
            # is sized for that, not for the interactive 30 s default
            with stage("serve"), QueryServer(sess, workers=2) as srv:
                names = list(sql)
                futs = [
                    (names[i % len(names)],
                     srv.submit(sql[names[i % len(names)]], timeout=900,
                                tenant=("web", "batch")[i % 2]))
                    for i in range(20)
                ]
                for name, f in futs:
                    assert_answer(f"serve.{name}", f.result(timeout=900), answers[name])
                stats = srv.stats()
            print(f"serve latency seconds: {stats['latencySeconds']}", flush=True)
            assert stats["completed"] == len(futs), stats
            assert stats["errors"] == 0 and stats["queue"]["rejected"] == 0, stats
    finally:
        hst.set_session(None)
        shutil.rmtree(root, ignore_errors=True)

    report = {
        "ok": not rehearsal,
        **({"rehearsal": "CPU backend; not a chip pass"} if rehearsal else {}),
        "device": device,
        "versions": versions,
        "sf": args.sf,
        "seed": args.seed,
        "parallel": args.parallel,
        "native": "live",
        "mosaic": mosaic,
        "smoke_seconds": seconds,
        # wall of first-seen (compiling) device program calls, by family
        "compile_seconds_by_program": {
            k[0]: round(v, 3) for k, v in metric_samples("hs_device_compile_seconds_total").items()
        },
        "dispatch_default": summary_default.splitlines(),
        "dispatch_device": summary_device.splitlines(),
        "digests": digests,
        "compile_cache": {"dir": jax.config.jax_compilation_cache_dir, **cache},
    }
    if rehearsal:
        print("rehearsal complete: every stage passed on the CPU backend; "
              "the exit code stays non-zero because no chip ran", flush=True)
    print("report: " + json.dumps(report), flush=True)
    # the verdict: exactly these keys, last on stdout
    print(json.dumps({"ok": report["ok"], "device": device}), flush=True)
    return report


if __name__ == "__main__":
    sys.exit(0 if main()["ok"] else 1)
