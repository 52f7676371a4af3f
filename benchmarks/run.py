"""Benchmark runner for the five BASELINE.md configs.

Usage:
    python benchmarks/run.py [config1|config2|config3|config4|config5|all] [--sf 0.1]

Each config prints one JSON line:
    {"config": N, "metric": ..., "value": ..., "unit": ..., "speedup_vs_noindex": ...}

Methodology: every query is executed once to warm jit compiles and OS caches,
then timed over ``--reps`` repetitions (median). The no-index baseline is the
same query with hyperspace disabled in the same process (the Spark-CPU
baseline of BASELINE.md must be measured on a Spark cluster; the speedups
reported here are vs this framework's own non-indexed execution path).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import datagen  # noqa: E402


def _session(root, num_buckets=64):
    import hyperspace_tpu as hst

    sysd = os.path.join(root, "_indexes")
    os.makedirs(sysd, exist_ok=True)
    sess = hst.Session(
        conf={
            hst.keys.SYSTEM_PATH: sysd,
            hst.keys.NUM_BUCKETS: num_buckets,
            # filter-rule scans advertise the index's bucket spec (equality/IN
            # filters on the indexed column read only their hash bucket's
            # files with or without it)
            hst.keys.FILTER_RULE_USE_BUCKET_SPEC: True,
        }
    )
    hst.set_session(sess)
    return sess, hst.Hyperspace(sess), hst


def _time_query(q, reps: int):
    """(median, IQR) seconds over ``reps`` timed runs after one warm run.
    IQR (p75-p25) is reported alongside the median so run-to-run ambient
    variance on shared machines is visible in every published number."""
    q.collect()  # warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        q.collect()
        times.append(time.perf_counter() - t0)
    med = statistics.median(times)
    if len(times) >= 4:
        qs = statistics.quantiles(times, n=4)
        iqr = qs[2] - qs[0]
    else:
        iqr = max(times) - min(times)
    return med, iqr


def _ab(sess, q, reps: int):
    """((indexed_median, iqr), (plain_median, iqr)) in the same process."""
    sess.enable_hyperspace()
    ti = _time_query(q, reps)
    sess.disable_hyperspace()
    tp = _time_query(q, reps)
    sess.enable_hyperspace()
    return ti, tp


def _emit(config: int, metric: str, ti, tp, extra=None):
    """One JSON line per config: indexed median (ms) ± IQR, plain median,
    speedup, and the 1-minute loadavg for cross-run comparability."""
    (med_i, iqr_i), (med_p, iqr_p) = ti, tp
    row = {
        "config": config,
        "metric": metric,
        "value": round(med_i * 1000, 4),
        "unit": "ms",
        "speedup_vs_noindex": round(med_p / med_i, 3),
        "iqr_ms": round(iqr_i * 1000, 4),
        "noindex_ms": round(med_p * 1000, 4),
        "noindex_iqr_ms": round(iqr_p * 1000, 4),
        "loadavg_1m": round(os.getloadavg()[0], 2),
    }
    if extra:
        row.update(extra)
    print(json.dumps(row), flush=True)


def config1(root, args):
    """Covering index on sample data; single filter query (BASELINE config 1)."""
    data = datagen.gen_sample(root)
    sess, hs, hst = _session(root, num_buckets=16)
    df = sess.read_parquet(data)
    hs.create_index(df, hst.CoveringIndexConfig("sample_idx", ["dept"], ["value", "name"]))
    q = df.filter(hst.col("dept") == 7).select("value", "name")
    ti, tp = _ab(sess, q, args.reps)
    _emit(1, "sample_filter_query_latency", ti, tp)


def config2(root, args):
    """TPC-H lineitem covering index on l_shipdate; FilterIndexRule (config 2)."""
    data = datagen.gen_lineitem(root, args.sf)
    sess, hs, hst = _session(root)
    df = sess.read_parquet(data)
    t0 = time.perf_counter()
    hs.create_index(
        df,
        hst.CoveringIndexConfig(
            "li_shipdate", ["l_shipdate"], ["l_orderkey", "l_extendedprice", "l_discount"]
        ),
    )
    build_s = time.perf_counter() - t0
    day = np.datetime64("1995-06-15")
    q = df.filter(hst.col("l_shipdate") == day).select("l_orderkey", "l_extendedprice")
    ti, tp = _ab(sess, q, args.reps)
    n = int(datagen.LINEITEM_ROWS_SF1 * args.sf)
    _emit(2, "tpch_shipdate_filter_latency", ti, tp,
          {"sf": args.sf, "build_rows_per_s": round(n / build_s, 1)})


def config3(root, args):
    """lineitem JOIN orders shuffle-free bucketed SMJ via JoinIndexRule (config 3)."""
    li_d = datagen.gen_lineitem(root, args.sf)
    o_d = datagen.gen_orders(root, args.sf)
    sess, hs, hst = _session(root)
    li = sess.read_parquet(li_d)
    o = sess.read_parquet(o_d)
    hs.create_index(
        li, hst.CoveringIndexConfig("li_ok", ["l_orderkey"], ["l_extendedprice", "l_discount"])
    )
    hs.create_index(o, hst.CoveringIndexConfig("o_ok", ["o_orderkey"], ["o_totalprice"]))
    q = li.join(o, on=hst.col("l_orderkey") == hst.col("o_orderkey")).select(
        "l_extendedprice", "o_totalprice"
    )
    ti, tp = _ab(sess, q, args.reps)
    _emit(3, "tpch_indexed_join_latency", ti, tp, {"sf": args.sf})


def config4(root, args):
    """Multi-way join + hybrid scan over appended files (config 4)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    li_d = datagen.gen_lineitem(root, args.sf)
    o_d = datagen.gen_orders(root, args.sf)
    sess, hs, hst = _session(root)
    li = sess.read_parquet(li_d)
    o = sess.read_parquet(o_d)
    hs.create_index(
        li, hst.CoveringIndexConfig("li_ok4", ["l_orderkey"], ["l_extendedprice"])
    )
    hs.create_index(o, hst.CoveringIndexConfig("o_ok4", ["o_orderkey"], ["o_totalprice"]))
    # append ~5% new lineitem rows AFTER indexing -> hybrid scan path
    rng = np.random.default_rng(99)
    n_app = max(1000, int(datagen.LINEITEM_ROWS_SF1 * args.sf * 0.05))
    base = np.datetime64("1992-01-01")
    t = pa.table(
        {
            "l_orderkey": rng.integers(0, int(datagen.ORDERS_ROWS_SF1 * args.sf), n_app).astype(np.int64),
            "l_partkey": rng.integers(0, 200_000, n_app).astype(np.int64),
            "l_quantity": rng.integers(1, 51, n_app).astype(np.int64),
            "l_extendedprice": np.round(rng.uniform(900.0, 105000.0, n_app), 2),
            "l_discount": np.round(rng.uniform(0.0, 0.1, n_app), 2),
            "l_tax": np.round(rng.uniform(0.0, 0.08, n_app), 2),
            "l_shipdate": base + rng.integers(0, 2526, n_app).astype("timedelta64[D]"),
        }
    )
    pq.write_table(t, os.path.join(li_d, "part-appended.parquet"))
    sess.conf.set(hst.keys.HYBRID_SCAN_ENABLED, True)
    li2 = sess.read_parquet(li_d)
    q = li2.join(o, on=hst.col("l_orderkey") == hst.col("o_orderkey")).select(
        "l_extendedprice", "o_totalprice"
    )
    ti, tp = _ab(sess, q, args.reps)
    _emit(4, "hybrid_scan_join_latency", ti, tp, {"sf": args.sf, "appended_rows": n_app})


def config5(root, args):
    """Delta source + incremental refresh + data-skipping index (config 5)."""
    import pyarrow as pa

    from hyperspace_tpu.sources.delta import write_delta_table

    sess, hs, hst = _session(root)
    rng = np.random.default_rng(5)
    n = max(10_000, int(1_000_000 * args.sf))
    d = os.path.join(root, "delta_li")

    def batch(seed):
        r = np.random.default_rng(seed)
        return pa.table(
            {
                "k": r.integers(0, 1_000_000, n // 2).astype(np.int64),
                "price": np.round(r.uniform(0, 1000, n // 2), 2),
            }
        )

    write_delta_table(batch(0), d)
    df = sess.read_delta(d)
    hs.create_index(df, hst.CoveringIndexConfig("delta_ci", ["k"], ["price"]))
    hs.create_index(
        df,
        hst.DataSkippingIndexConfig(
            "delta_ds", hst.MinMaxSketch("k"), hst.BloomFilterSketch("k", expected_items=n)
        ),
    )
    # new delta version, then incremental refresh
    write_delta_table(batch(1), d)
    t0 = time.perf_counter()
    hs.refresh_index("delta_ci", "incremental")
    hs.refresh_index("delta_ds", "incremental")
    refresh_s = time.perf_counter() - t0
    df2 = sess.read_delta(d)
    probe = int(np.asarray(batch(1)["k"])[0])
    q = df2.filter(hst.col("k") == probe).select("price")
    ti, tp = _ab(sess, q, args.reps)
    _emit(5, "delta_incremental_plus_skipping_latency", ti, tp,
          {"sf": args.sf, "incremental_refresh_s": round(refresh_s, 3)})


def config6(root, args):
    """String-payload-heavy indexed join (round-3 VERDICT item 7): orders
    joined to customer carrying c_name/c_address/c_mktsegment as included
    columns. Device materialization gathers numeric columns on device but
    string columns host-side by downloaded index arrays (exec/device.py);
    this config measures that cost so the decision to (not) dictionary-code
    device string gathers is recorded with a number."""
    o_d = datagen.gen_orders(root, args.sf)
    c_d = datagen.gen_customer(root, args.sf)
    sess, hs, hst = _session(root)
    o = sess.read_parquet(o_d)
    c = sess.read_parquet(c_d)
    hs.create_index(
        o, hst.CoveringIndexConfig("o_ck6", ["o_custkey"], ["o_totalprice"])
    )
    hs.create_index(
        c,
        hst.CoveringIndexConfig(
            "c_ck6", ["c_custkey"], ["c_name", "c_address", "c_mktsegment", "c_acctbal"]
        ),
    )
    q = o.join(c, on=hst.col("o_custkey") == hst.col("c_custkey")).select(
        "o_totalprice", "c_name", "c_address", "c_mktsegment"
    )
    ti, tp = _ab(sess, q, args.reps)
    # numeric-only variant of the same join sizes the string-gather delta
    qn = o.join(c, on=hst.col("o_custkey") == hst.col("c_custkey")).select(
        "o_totalprice", "c_acctbal"
    )
    tin, _ = _ab(sess, qn, args.reps)
    _emit(6, "string_payload_join_latency", ti, tp,
          {"sf": args.sf, "numeric_only_ms": round(tin[0] * 1000, 4),
           "string_gather_overhead_x": round(ti[0] / max(tin[0], 1e-9), 3)})


def config7(root, args):
    """Real TPC-H q3 text through the SQL front-end with covering indexes on
    the join keys — the end-to-end SQL+optimizer+engine latency on the
    benchmark family's own query, not a synthetic shape."""
    li_d = datagen.gen_lineitem(root, args.sf)
    o_d = datagen.gen_orders(root, args.sf)
    c_d = datagen.gen_customer(root, args.sf)
    sess, hs, hst = _session(root)
    li = sess.read_parquet(li_d)
    o = sess.read_parquet(o_d)
    c = sess.read_parquet(c_d)
    hs.create_index(
        li, hst.CoveringIndexConfig("li_ok7", ["l_orderkey"], ["l_extendedprice", "l_discount", "l_shipdate"])
    )
    # the selective l_shipdate filter leg must be covered by a filter index
    # that also carries the downstream join key, else the lineitem leg stays
    # a raw scan
    hs.create_index(
        li, hst.CoveringIndexConfig("li_sd7", ["l_shipdate"], ["l_orderkey", "l_extendedprice", "l_discount"])
    )
    hs.create_index(
        o, hst.CoveringIndexConfig("o_ok7", ["o_orderkey"], ["o_custkey", "o_orderdate", "o_shippriority"])
    )
    # the customer join needs orders bucketed by o_custkey (JoinIndexRule
    # requires indexed cols == join cols on both sides)
    hs.create_index(
        o, hst.CoveringIndexConfig("o_ck7", ["o_custkey"], ["o_orderkey", "o_orderdate", "o_shippriority"])
    )
    hs.create_index(c, hst.CoveringIndexConfig("c_ck7", ["c_custkey"], ["c_mktsegment"]))
    li.create_or_replace_temp_view("lineitem")
    o.create_or_replace_temp_view("orders")
    c.create_or_replace_temp_view("customer")
    q = sess.sql("""
      select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
             o_orderdate, o_shippriority
      from customer, orders, lineitem
      where c_mktsegment = 'AUTOMOBILE'
        and c_custkey = o_custkey
        and l_orderkey = o_orderkey
        and o_orderdate < date '1995-03-15'
        and l_shipdate > date '1995-03-15'
      group by l_orderkey, o_orderdate, o_shippriority
      order by revenue desc, o_orderdate
      limit 10
    """)
    ti, tp = _ab(sess, q, args.reps)
    _emit(7, "tpch_q3_sql_latency", ti, tp, {"sf": args.sf})


CONFIGS = {"config1": config1, "config2": config2, "config3": config3,
           "config4": config4, "config5": config5, "config6": config6,
           "config7": config7}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("which", nargs="?", default="all", choices=[*CONFIGS, "all"])
    ap.add_argument("--sf", type=float, default=float(os.environ.get("BENCH_SF", 0.1)))
    ap.add_argument("--reps", type=int, default=int(os.environ.get("BENCH_REPS", 10)))
    ap.add_argument("--keep", action="store_true", help="keep generated data dir")
    args = ap.parse_args()

    root = tempfile.mkdtemp(prefix="hs_bench_suite_")
    try:
        for name in ([args.which] if args.which != "all" else list(CONFIGS)):
            CONFIGS[name](os.path.join(root, name), args)
    finally:
        if not args.keep:
            shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
