"""Absolute-performance framing: measure the bounds, then place the
framework's headline numbers against them.

Ratios against a host baseline say nothing about whether the chip is busy
or starved; this probe measures the bounds that govern every build and
device-join number on the host it runs on:

- host<->device link bandwidth (device_put up / np.asarray down, 64 MiB
  int64 arrays, best of N) — the ceiling for build key upload + perm
  download and for any device-join transfer;
- device sort throughput on the build kernel's own shapes (keys already
  resident: the pure-compute bound of the build's device stage);
- host parquet decode throughput (pyarrow + native path on index-dialect
  files) — the build pipeline's host-side bound.

Prints ONE JSON line with the measured bounds plus derived
fraction-of-bound figures for a given build rate (BENCH_BUILD_RATE env,
rows/s, e.g. the latest bench.py headline).

Run on the chip host (it fails where no TPU is found; one process per chip):
    python benchmarks/roofline.py
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    import bench

    bench._require_chip()
    import jax

    dev = jax.devices()[0]
    out = {
        "device": str(dev),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }

    # --- link bandwidth, 64 MiB payloads, best of 5 ------------------------
    nbytes = 64 << 20
    arr = np.random.default_rng(0).integers(0, 1 << 62, nbytes // 8, dtype=np.int64)
    ups, downs = [], []
    d = jax.device_put(arr)  # warm path + allocator
    d.block_until_ready()
    for _ in range(5):
        t0 = time.perf_counter()
        d = jax.device_put(arr)
        d.block_until_ready()
        ups.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        _ = np.asarray(d)
        downs.append(time.perf_counter() - t0)
    out["h2d_gbps"] = round(nbytes / min(ups) / 1e9, 3)
    out["d2h_gbps"] = round(nbytes / min(downs) / 1e9, 3)

    # --- device build-kernel compute bound (keys resident, no transfers) ---
    from hyperspace_tpu.ops.sort import bucket_sort_build, padded_size

    n = 2_000_000  # one default build chunk
    rng = np.random.default_rng(1)
    np2 = padded_size(n)
    keys = [jax.device_put(np.pad(rng.integers(0, 10**9, n), (0, np2 - n)))]
    # int builds reconstruct their hash plane ON device (_device_hash32) —
    # host_hashes is only consumed for string columns
    perm, counts = bucket_sort_build(keys, (), ("i",), 64, n)  # compile
    perm.block_until_ready()
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        perm, counts = bucket_sort_build(keys, (), ("i",), 64, n)
        perm.block_until_ready()
        times.append(time.perf_counter() - t0)
    out["device_sort_rows_per_s"] = round(n / min(times), 1)

    # --- host parquet decode bound (the build's other pipeline stage) ------
    import tempfile

    import pyarrow as pa
    import pyarrow.parquet as pq

    from hyperspace_tpu.exec.io import read_parquet_batch

    with tempfile.TemporaryDirectory(prefix="hs_roofline_") as td:
        path = os.path.join(td, "f.parquet")
        t = pa.table({
            "k": rng.integers(0, 10**9, 4_000_000).astype(np.int64),
            "a": rng.uniform(0, 1, 4_000_000),
            "b": rng.uniform(0, 1, 4_000_000),
            "c": rng.uniform(0, 1, 4_000_000),
        })
        pq.write_table(t, path, use_dictionary=False, compression="NONE")
        file_bytes = os.stat(path).st_size
        read_parquet_batch([path], None)  # warm (native mmap path)
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            read_parquet_batch([path], None)
            times.append(time.perf_counter() - t0)
        out["host_decode_gbps"] = round(file_bytes / min(times) / 1e9, 3)

    # --- place a build rate against the bounds -----------------------------
    # per-row traffic of the default build (single int64 key index):
    #   up: 8 B sort key + 4 B hash plane (uint32) per row (padded ~+6%)
    #   down: 4 B perm + negligible counts
    rate = float(os.environ.get("BENCH_BUILD_RATE", 0) or 0)
    if rate > 0:
        up_bps = rate * 12 * 1.06
        down_bps = rate * 4
        out["build_rate_rows_per_s"] = rate
        out["link_utilization_up"] = round(up_bps / (out["h2d_gbps"] * 1e9), 4)
        out["link_utilization_down"] = round(down_bps / (out["d2h_gbps"] * 1e9), 4)
        out["device_sort_utilization"] = round(rate / out["device_sort_rows_per_s"], 4)
        # end-to-end build moves ~32 B/row of parquet on each side of the
        # device stage (decode in, bucket write out)
        out["host_decode_utilization"] = round(
            (rate * 32) / (out["host_decode_gbps"] * 1e9), 4
        )
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
