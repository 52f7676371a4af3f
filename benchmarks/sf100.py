"""TPC-H SF100 out-of-core proof (BASELINE.md:24's own scale class).

Runs the BASELINE config3 shape (lineitem JOIN orders on l_orderkey) at a
scale where nothing may materialize a full table: SF100 lineitem is 600M
rows (~34 GB raw). The round-5 streaming layer carries it end to end:

- the covering-index BUILD streams source files in ~batchRows groups
  (indexes/covering.py write) — peak RAM is O(2 chunks);
- the indexed JOIN streams bucket-by-bucket above
  ``hyperspace.exec.stream.joinMinBytes`` (exec/device.py
  stream_bucketed_join) — peak RAM is O(bucket pair + output);
- the non-indexed baseline runs the partitioned (grace) merge above
  ``hyperspace.exec.join.spillMinRows`` and streams its scans.

The reference inherits all three properties from Spark's streaming
executors (HS/index/covering/JoinIndexRule.scala:604-705 is valid at any
SF); this framework owns them explicitly, and this benchmark proves them
with numbers: peak RSS is recorded for every phase, and an optional
--rss-budget makes exceeding it a hard failure.

Usage:
    python benchmarks/sf100.py --sf 100 [--reps 1] [--rss-budget-gb 48]
        [--skip-baseline] [--agg-probe]

Prints one JSON line per phase (datagen / build / indexed query /
baseline query), each with elapsed seconds and peak RSS.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import datagen  # noqa: E402


def peak_rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / (1024**2)


def emit(phase: str, seconds: float, extra=None) -> None:
    row = {
        "phase": phase,
        "seconds": round(seconds, 2),
        "peak_rss_gb": round(peak_rss_gb(), 2),
        "loadavg_1m": round(os.getloadavg()[0], 2),
    }
    if extra:
        row.update(extra)
    print(json.dumps(row), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=float(os.environ.get("BENCH_SF", 100)))
    ap.add_argument("--reps", type=int, default=1)
    ap.add_argument("--root", default=None, help="data dir (default: temp; reused if it exists)")
    ap.add_argument("--keep", action="store_true")
    ap.add_argument("--skip-baseline", action="store_true")
    ap.add_argument("--skip-datagen", action="store_true", help="reuse --root's existing data")
    ap.add_argument(
        "--rss-budget-gb", type=float, default=None,
        help="fail the run if peak RSS exceeds this (the bounded-memory proof)",
    )
    ap.add_argument(
        "--agg-probe", action="store_true",
        help="also run a streamed full-scan aggregate (partial-agg merge proof)",
    )
    args = ap.parse_args()

    root = args.root or tempfile.mkdtemp(prefix="hs_sf100_")
    os.makedirs(root, exist_ok=True)
    n_li = int(datagen.LINEITEM_ROWS_SF1 * args.sf)

    # --- datagen (file count scales so each file stays ~8M rows: the
    # streaming build's decode bound is one file group) ---------------------
    t0 = time.perf_counter()
    li_files = max(16, int(np.ceil(n_li / 8_000_000)))
    o_files = max(8, li_files // 4)
    if args.skip_datagen and os.path.isdir(os.path.join(root, "lineitem")):
        li_d = os.path.join(root, "lineitem")
        o_d = os.path.join(root, "orders")
        emit("datagen", 0.0, {"sf": args.sf, "rows": n_li, "reused": True})
    else:
        li_d = datagen.gen_lineitem(root, args.sf, num_files=li_files)
        o_d = datagen.gen_orders(root, args.sf, num_files=o_files)
        emit("datagen", time.perf_counter() - t0, {"sf": args.sf, "rows": n_li,
                                                   "files": li_files + o_files})

    import hyperspace_tpu as hst

    sysd = os.path.join(root, "_indexes")
    os.makedirs(sysd, exist_ok=True)
    sess = hst.Session(conf={
        hst.keys.SYSTEM_PATH: sysd,
        hst.keys.NUM_BUCKETS: 64,
    })
    hst.set_session(sess)
    hs = hst.Hyperspace(sess)
    li = sess.read_parquet(li_d)
    o = sess.read_parquet(o_d)

    # --- streaming index builds -------------------------------------------
    ix_df = hs.indexes()
    existing = set(ix_df["name"]) if len(ix_df) else set()
    t0 = time.perf_counter()
    if "li_ok_sf" not in existing:
        hs.create_index(
            li, hst.CoveringIndexConfig("li_ok_sf", ["l_orderkey"],
                                        ["l_extendedprice", "l_discount"])
        )
    li_build_s = time.perf_counter() - t0
    li_skipped = "li_ok_sf" in existing
    emit("build_lineitem", li_build_s,
         {"rows": n_li,
          "rows_per_s": None if li_skipped else round(n_li / max(li_build_s, 1e-9), 1),
          "skipped": li_skipped})
    t0 = time.perf_counter()
    n_o = int(datagen.ORDERS_ROWS_SF1 * args.sf)
    if "o_ok_sf" not in existing:
        hs.create_index(
            o, hst.CoveringIndexConfig("o_ok_sf", ["o_orderkey"], ["o_totalprice"])
        )
    o_build_s = time.perf_counter() - t0
    o_skipped = "o_ok_sf" in existing
    emit("build_orders", o_build_s,
         {"rows": n_o,
          "rows_per_s": None if o_skipped else round(n_o / max(o_build_s, 1e-9), 1),
          "skipped": o_skipped})

    # --- the config3 query, indexed (streaming bucketed SMJ) ---------------
    sess.enable_hyperspace()
    q = li.join(o, on=hst.col("l_orderkey") == hst.col("o_orderkey")).select(
        "l_extendedprice", "o_totalprice"
    )
    from hyperspace_tpu.exec import trace

    times = []
    out_rows = 0
    for _ in range(args.reps):
        t0 = time.perf_counter()
        with trace.recording() as rec:
            # drain through the local iterator: the full output never has to
            # sit in one allocation (sum as we go to prove the rows moved)
            out_rows = 0
            checksum = 0.0
            for chunk in q.to_local_iterator():
                out_rows += len(chunk["l_extendedprice"])
                checksum += float(np.sum(chunk["o_totalprice"][:100]))
        times.append(time.perf_counter() - t0)
    emit("indexed_join", min(times),
         {"reps": args.reps, "out_rows": out_rows,
          "dispatch": sorted({f"{k}:{v}" for k, v in rec}),
          "checksum": round(checksum, 2)})

    # --- streamed full-scan aggregate probe --------------------------------
    if args.agg_probe:
        qa = li.agg(s=("l_extendedprice", "sum"), n=("*", "count"),
                    mx=("l_extendedprice", "max"))
        t0 = time.perf_counter()
        with trace.recording() as rec:
            got = qa.collect()
        emit("streamed_aggregate", time.perf_counter() - t0,
             {"n": int(got["n"][0]), "dispatch": sorted({f"{k}:{v}" for k, v in rec})})

    # --- the non-indexed baseline (largest SF it can run) ------------------
    if not args.skip_baseline:
        sess.disable_hyperspace()
        times_b = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            rows_b = 0
            for chunk in q.to_local_iterator():
                rows_b += len(chunk["l_extendedprice"])
            times_b.append(time.perf_counter() - t0)
        emit("baseline_join", min(times_b), {"reps": args.reps, "out_rows": rows_b,
                                             "speedup_indexed": round(min(times_b) / min(times), 3)})

    if args.rss_budget_gb is not None and peak_rss_gb() > args.rss_budget_gb:
        print(json.dumps({"phase": "rss_budget", "error":
                          f"peak RSS {peak_rss_gb():.1f} GB exceeded budget {args.rss_budget_gb} GB"}),
              flush=True)
        sys.exit(3)

    if not args.keep and args.root is None:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
