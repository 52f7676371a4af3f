"""Benchmark every TPC-H query text (q1-q22) indexed vs non-indexed.

Round-4 VERDICT item 4: the q3-only config 7 left 21 of 22 texts never
benchmarked. This runs the full family over the scaled full-schema generator
(benchmarks/tpch_full.py), with the same covering-index roster the
correctness suite proves rewrites fire for (tests/test_tpch_queries.py), and
attaches whyNot output for every query where no rewrite fired.

Usage:
    python benchmarks/tpch22.py [--sf 0.05] [--reps 3] [--queries q3,q12]

One JSON line per query:
    {"query": "q3", "indexed_ms": ..., "plain_ms": ..., "speedup": ...,
     "rows": N, "indexes_used": [...]}
plus a final markdown table on stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tests"))

from benchmarks import tpch_full  # noqa: E402

# the roster the correctness suite uses (wide vertical slices; dispatch
# goldens prove which queries rewrite under it)
INDEXES = [
    ("lineitem", "li_ok", ["l_orderkey"],
     ["l_extendedprice", "l_discount", "l_quantity", "l_tax", "l_shipdate",
      "l_commitdate", "l_receiptdate", "l_shipmode", "l_returnflag",
      "l_linestatus", "l_suppkey", "l_partkey"]),
    ("lineitem", "li_sd", ["l_shipdate"],
     ["l_extendedprice", "l_discount", "l_quantity", "l_orderkey"]),
    ("lineitem", "li_pk", ["l_partkey"],
     ["l_extendedprice", "l_discount", "l_quantity", "l_shipdate",
      "l_shipmode", "l_shipinstruct"]),
    ("orders", "o_ok", ["o_orderkey"],
     ["o_custkey", "o_orderdate", "o_totalprice", "o_orderpriority",
      "o_orderstatus", "o_shippriority"]),
    ("orders", "o_ck", ["o_custkey"],
     ["o_orderkey", "o_orderdate", "o_totalprice", "o_shippriority",
      "o_comment"]),
    ("customer", "c_ck", ["c_custkey"],
     ["c_name", "c_acctbal", "c_mktsegment", "c_nationkey", "c_phone",
      "c_address", "c_comment"]),
    ("part", "p_pk", ["p_partkey"],
     ["p_name", "p_mfgr", "p_brand", "p_type", "p_size", "p_container",
      "p_retailprice"]),
    ("supplier", "s_sk", ["s_suppkey"],
     ["s_name", "s_address", "s_nationkey", "s_phone", "s_acctbal",
      "s_comment"]),
    ("partsupp", "ps_pk", ["ps_partkey"],
     ["ps_suppkey", "ps_availqty", "ps_supplycost"]),
]


def _median_iqr(times):
    med = statistics.median(times)
    if len(times) >= 4:
        qs = statistics.quantiles(times, n=4)
        return med, qs[2] - qs[0]
    return med, max(times) - min(times)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=float(os.environ.get("BENCH_SF", 0.05)))
    ap.add_argument("--reps", type=int, default=int(os.environ.get("BENCH_REPS", 3)))
    ap.add_argument("--queries", default="")
    ap.add_argument("--keep", action="store_true")
    args = ap.parse_args()

    from tpch_queries import TPCH_QUERIES  # noqa: E402 (tests/ on path)

    import hyperspace_tpu as hst

    want = [q.strip() for q in args.queries.split(",") if q.strip()] or sorted(
        TPCH_QUERIES, key=lambda s: int(s[1:])
    )

    root = tempfile.mkdtemp(prefix="hs_tpch22_")
    table_rows = []
    try:
        t0 = time.time()
        dirs = tpch_full.gen_all(root, args.sf)
        print(json.dumps({"event": "datagen_done", "sf": args.sf,
                          "seconds": round(time.time() - t0, 1)}), flush=True)
        sysd = os.path.join(root, "_indexes")
        os.makedirs(sysd, exist_ok=True)
        sess = hst.Session(conf={
            hst.keys.SYSTEM_PATH: sysd,
            hst.keys.NUM_BUCKETS: 16,
            hst.keys.FILTER_RULE_USE_BUCKET_SPEC: True,
        })
        hst.set_session(sess)
        hs = hst.Hyperspace(sess)
        for name, d in dirs.items():
            sess.read_parquet(d).create_or_replace_temp_view(name)
        t0 = time.time()
        for table, idx_name, indexed, included in INDEXES:
            hs.create_index(
                sess._temp_views[table], hst.CoveringIndexConfig(idx_name, indexed, included)
            )
        print(json.dumps({"event": "index_build_done",
                          "seconds": round(time.time() - t0, 1)}), flush=True)
        sess.enable_hyperspace()

        for qname in want:
            text = TPCH_QUERIES[qname]
            try:
                q = sess.sql(text)
                plan = q.optimized_plan().pretty()
                used = sorted(set(
                    part.split("Name: ")[1].split(",")[0]
                    for part in plan.split("Hyperspace(")[1:]
                ))
                # timed runs: one warm + reps, indexed then plain
                q.collect()
                ts = []
                for _ in range(args.reps):
                    s = time.perf_counter()
                    got = q.collect()
                    ts.append(time.perf_counter() - s)
                rows = len(next(iter(got.values()))) if got else 0
                ti, ti_iqr = _median_iqr(ts)
                sess.disable_hyperspace()
                try:
                    qp = sess.sql(text)
                    qp.collect()
                    ts = []
                    for _ in range(args.reps):
                        s = time.perf_counter()
                        qp.collect()
                        ts.append(time.perf_counter() - s)
                finally:
                    # a mid-query failure must not leave every later query
                    # running its "indexed" measurement unindexed
                    sess.enable_hyperspace()
                tp, tp_iqr = _median_iqr(ts)
                row = {
                    "query": qname,
                    "indexed_ms": round(ti * 1000, 2),
                    "indexed_iqr_ms": round(ti_iqr * 1000, 2),
                    "plain_ms": round(tp * 1000, 2),
                    "plain_iqr_ms": round(tp_iqr * 1000, 2),
                    "speedup": round(tp / ti, 3) if ti > 0 else None,
                    "rows": rows,
                    "indexes_used": used,
                }
                if not used:
                    why = hs.why_not(q)
                    # the summary sections only: keep the JSON line readable
                    row["why_not"] = " | ".join(
                        ln for ln in why.splitlines()
                        if ln.startswith("- ") or ln.endswith(":")
                    )[:500]
                print(json.dumps(row), flush=True)
                table_rows.append(row)
            except Exception as e:  # noqa: BLE001 — record and continue
                print(json.dumps({"query": qname, "error": f"{type(e).__name__}: {e}"[:300]}),
                      flush=True)
    finally:
        if not args.keep:
            import shutil

            shutil.rmtree(root, ignore_errors=True)

    if table_rows:
        print("\n| query | indexed ms | plain ms | speedup | rows | indexes |",
              file=sys.stderr)
        print("|---|---|---|---|---|---|", file=sys.stderr)
        for r in table_rows:
            print(
                f"| {r['query']} | {r['indexed_ms']}±{r['indexed_iqr_ms']} | "
                f"{r['plain_ms']}±{r['plain_iqr_ms']} | {r['speedup']}x | "
                f"{r['rows']} | {','.join(r['indexes_used']) or '-'} |",
                file=sys.stderr,
            )


if __name__ == "__main__":
    main()
