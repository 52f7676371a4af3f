"""The comparison that decides ``correct``.

Nothing here imports the program. Answers are held against pandas oracles
over the source Parquet files; index files are held against the source rows
and against this file's own copy of the bucket hash (the HOST hash of the
program's ``ops/hashing.py`` at the commit the benchmark was defined on: if a
later PR changes the hash, indexes stop being readable by older readers, and
this check says so).
"""

from __future__ import annotations

import glob
import hashlib
import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

# -- the bucket hash, copied -------------------------------------------------

_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)
_SEED = np.uint32(0x9747B28C)


def _mix32(h):
    h = h ^ (h >> np.uint32(16))
    h = h * _C1
    h = h ^ (h >> np.uint32(13))
    h = h * _C2
    return h ^ (h >> np.uint32(16))


def order_key(arr: np.ndarray) -> np.ndarray:
    """Order-preserving int64 of an integer or date key column."""
    if arr.dtype.kind == "M":
        return arr.astype("datetime64[D]").view(np.int64)
    if arr.dtype.kind in "iub":
        return arr.astype(np.int64)
    raise TypeError(f"the benchmark's index check knows integer and date keys, not {arr.dtype}")


def host_bucket(key: np.ndarray, num_buckets: int) -> np.ndarray:
    """Bucket of every row of a single integer or date (days) key column."""
    bits = order_key(key).view(np.uint64)
    with np.errstate(over="ignore"):
        h32 = ((bits ^ (bits >> np.uint64(32))) & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        h = _mix32(np.full(h32.shape, _SEED, dtype=np.uint32) ^ _mix32(h32))
    return (h % np.uint32(num_buckets)).astype(np.int32)


# -- answers -----------------------------------------------------------------

def _plain(v) -> np.ndarray:
    """One column in a form that compares: dates as days, strings as ``str``."""
    a = v.to_numpy() if hasattr(v, "to_numpy") else np.asarray(v)
    if a.dtype.kind == "M":
        return a.astype("datetime64[D]")
    if a.dtype.kind in "OUST":
        return np.asarray(a, dtype=object).astype(str)
    return a


def _canonical(batch: dict) -> dict:
    """A row set in one fixed order: by every exact column, then the floats."""
    names = sorted(batch)
    exact = [c for c in names if batch[c].dtype.kind != "f"]
    floats = [c for c in names if batch[c].dtype.kind == "f"]
    keys = [batch[c] for c in reversed(exact + floats)]
    order = np.lexsort(keys) if keys and len(keys[0]) else np.arange(0)
    return {c: v[order] for c, v in batch.items()}


def compare_answer(got: dict, want: dict, ordered: bool) -> tuple:
    """``(exact mismatches, widest relative gap of a float)``: columns, row
    count, keys, counts, dates and strings exactly; floats by their gap from
    the oracle's value. ``ordered`` answers (ORDER BY ... LIMIT) compare row by
    row as returned, others as row sets."""
    got = {c: _plain(v) for c, v in got.items()}
    want = {c: _plain(v) for c, v in want.items()}
    if set(got) != set(want):
        return 1, 0.0
    n = len(next(iter(want.values()))) if want else 0
    if any(len(v) != n for v in got.values()):
        return 1, 0.0
    if not ordered:
        got, want = _canonical(got), _canonical(want)
    wrong, gap = 0, 0.0
    for c, w in want.items():
        g = got[c]
        if w.dtype.kind == "f" or g.dtype.kind == "f":
            g, w = g.astype(np.float64), w.astype(np.float64)
            if n:
                with np.errstate(divide="ignore", invalid="ignore"):
                    rel = np.abs(g - w) / np.maximum(np.abs(w), np.finfo(np.float64).tiny)
                rel = np.where(g == w, 0.0, rel)
                gap = max(gap, float(np.nan_to_num(rel, nan=np.inf).max()))
        elif g.dtype.kind != w.dtype.kind or not np.array_equal(g, w):
            wrong += 1
    return wrong, gap


# -- covering indexes ---------------------------------------------------------

def _row_digest(table: pa.Table, columns) -> np.ndarray:
    """One uint64 per row over ``columns`` (names sorted): swapping two rows'
    payloads, or a payload under another row's key, changes the sum."""
    with np.errstate(over="ignore"):
        acc = np.zeros(table.num_rows, dtype=np.uint64)
        for i, name in enumerate(sorted(columns)):
            col = table.column(name).combine_chunks()
            if pa.types.is_string(col.type) or pa.types.is_large_string(col.type):
                d = col.dictionary_encode()
                words = np.array(
                    [int.from_bytes(hashlib.blake2b(str(v).encode(), digest_size=8).digest(), "little")
                     for v in d.dictionary.to_pylist()], dtype=np.uint64)
                bits = words[d.indices.to_numpy(zero_copy_only=False)]
            else:
                a = col.to_numpy(zero_copy_only=False)
                if a.dtype.kind == "M":
                    a = a.astype("datetime64[D]").view(np.int64)
                a = a.astype(np.float64 if a.dtype.kind == "f" else np.int64)
                bits = np.ascontiguousarray(a).view(np.uint64)
            salt = np.uint64((0x9E3779B97F4A7C15 * (i + 1)) & 0xFFFFFFFFFFFFFFFF)
            x = (bits + salt) * np.uint64(0xBF58476D1CE4E5B9)
            x ^= x >> np.uint64(31)
            acc = (acc ^ x) * np.uint64(0x94D049BB133111EB)
        acc ^= acc >> np.uint64(29)
        return acc


def _pool():
    from concurrent.futures import ThreadPoolExecutor

    return ThreadPoolExecutor(max_workers=8)


def source_facts(files, key: str, columns, num_buckets: int, sample_buckets) -> dict:
    """What an index over ``files`` has to hold: rows per bucket by the host
    hash, and for the sampled buckets the sum of the row digests."""
    sampled = sorted(int(b) for b in sample_buckets)
    sample = np.zeros(num_buckets, dtype=bool)
    sample[sampled] = True

    def one(f):
        pf = pq.ParquetFile(f)
        rows = np.zeros(num_buckets, dtype=np.int64)
        sums = np.zeros(num_buckets, dtype=np.uint64)
        for g in range(pf.num_row_groups):
            t = pf.read_row_group(g, columns=sorted(set(columns) | {key}))
            bucket = host_bucket(t.column(key).to_numpy(zero_copy_only=False), num_buckets)
            rows += np.bincount(bucket, minlength=num_buckets)
            hit = sample[bucket]
            if hit.any():
                digest = _row_digest(t.filter(pa.array(hit)), columns)
                with np.errstate(over="ignore"):
                    np.add.at(sums, bucket[hit], digest)
        return rows, sums

    rows = np.zeros(num_buckets, dtype=np.int64)
    sums = np.zeros(num_buckets, dtype=np.uint64)
    with _pool() as pool, np.errstate(over="ignore"):
        for r, s in pool.map(one, files):
            rows += r
            sums += s
    return {"rows": rows, "sums": {b: sums[b] for b in sampled}}


def active_in_log(index_dir: str) -> bool:
    """The newest entry of the index's operation log says ACTIVE."""
    logs = [p for p in glob.glob(os.path.join(index_dir, "_hyperspace_log", "*"))
            if os.path.basename(p).isdigit()]
    if not logs:
        return False
    with open(max(logs, key=lambda p: int(os.path.basename(p)))) as f:
        return json.load(f).get("state") == "ACTIVE"


def index_numbers(files, index_dir: str, key: str, columns, num_buckets: int, facts: dict) -> dict:
    """Every number the build cell compares, for one built index, from a fresh
    read of its files. Each has the limit 0."""

    def one(f):
        """(bucket, rows, rows in a wrong bucket, rows out of order, digest sum) or a fault's name."""
        try:
            bucket = int(os.path.basename(f).split("-")[1])
        except (IndexError, ValueError):
            return "index.files_off"
        pf = pq.ParquetFile(f)
        if not set(columns) <= set(pf.schema_arrow.names):
            return "index.columns_missing"
        full = bucket in facts["sums"]
        t = pf.read(columns=sorted(set(columns)) if full else [key])
        k = t.column(key).to_numpy(zero_copy_only=False)
        order = order_key(k)
        with np.errstate(over="ignore"):
            digest = _row_digest(t, columns).sum(dtype=np.uint64) if full else np.uint64(0)
        return (bucket, t.num_rows, int((host_bucket(k, num_buckets) != bucket).sum()),
                int((order[1:] < order[:-1]).sum()), digest)

    rows = np.zeros(num_buckets, dtype=np.int64)
    sums = {b: np.uint64(0) for b in facts["sums"]}
    out = {"index.files_off": 0, "index.columns_missing": 0,
           "index.rows_in_wrong_bucket": 0, "index.rows_out_of_order": 0}
    with _pool() as pool, np.errstate(over="ignore"):
        for got in pool.map(one, files):
            if isinstance(got, str):
                out[got] += 1
                continue
            bucket, n, wrong, disorder, digest = got
            rows[bucket] += n
            out["index.rows_in_wrong_bucket"] += wrong
            out["index.rows_out_of_order"] += disorder
            if bucket in sums:
                sums[bucket] = sums[bucket] + digest
    out["index.not_active"] = 0 if active_in_log(index_dir) else 1
    out["index.rows_off"] = int(np.abs(rows - facts["rows"]).sum())
    out["index.checksum_differs"] = sum(1 for b in sums if sums[b] != facts["sums"][b])
    return out


def verdict(numbers: dict, limits: dict) -> bool:
    """Print each number compared beside its limit; true when all hold."""
    ok = True
    for name in sorted(numbers):
        value, limit = numbers[name], limits[name]
        good = value <= limit
        ok &= bool(good)
        print(f"check {name}: {value!r} (limit {limit!r}) {'ok' if good else 'FAILED'}", flush=True)
    return ok
