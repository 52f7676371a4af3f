"""A plain reference of ``createIndex`` for a covering index: what the build
has to put on disk, written out in NumPy and pyarrow with no JAX and nothing
imported from the package under test.

Semantics (upstream Hyperspace ``CoveringIndex.scala:54-69``:
``repartition(numBuckets, indexedColumns)`` then a bucketed, sorted write):

- the bucket of a row is the host hash of its key columns modulo
  ``numBuckets`` (the hash is written out again below; it is the index's
  on-disk contract, shared with readers that prune by bucket);
- the source files are taken in name order and cut into chunks: files are
  gathered until they reach ``batchRows`` rows (a file that would cross the cap
  starts the next group), and a group larger than the cap is cut into equal
  slices;
- every chunk writes one run (one file) per bucket it has rows for; inside a
  run rows are sorted by the key columns, equal keys in source order.

How the work is spread over chips is no part of this: one chip, four or eight
have to write the same runs.
"""

from __future__ import annotations

import hashlib

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)
_SEED = np.uint32(0x9747B28C)
_GOLDEN = 0x9E3779B9


def _mix32(h):
    h = h ^ (h >> np.uint32(16))
    h = h * _C1
    h = h ^ (h >> np.uint32(13))
    h = h * _C2
    return h ^ (h >> np.uint32(16))


def hash_input(col: np.ndarray) -> np.ndarray:
    """One uint32 per row: the 64-bit value of an integer, date or boolean
    folded to 32 bits; the first four bytes of a string's md5."""
    kind = col.dtype.kind
    if kind in "OUS":
        words = {v: np.uint32(int.from_bytes(hashlib.md5(str(v).encode("utf-8")).digest()[:4], "little"))
                 for v in set(col.tolist())}
        return np.array([words[v] for v in col.tolist()], dtype=np.uint32)
    if kind == "M":
        bits = col.astype("datetime64[D]").astype(np.int64).view(np.uint64)
    elif kind in "iub":
        bits = col.astype(np.int64).view(np.uint64)
    else:
        raise TypeError(f"the reference knows integer, date, boolean and string keys, not {col.dtype}")
    return ((bits ^ (bits >> np.uint64(32))) & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def bucket_of(key_columns, num_buckets: int) -> np.ndarray:
    """The bucket of every row, from its key columns in index order."""
    with np.errstate(over="ignore"):
        h = np.full(len(key_columns[0]), _SEED, dtype=np.uint32)
        for i, col in enumerate(key_columns):
            salted = hash_input(col) + np.uint32((i * _GOLDEN) & 0xFFFFFFFF)
            h = _mix32(h ^ _mix32(salted))
    return (h % np.uint32(num_buckets)).astype(np.int32)


def order_key(col: np.ndarray) -> np.ndarray:
    """A column in a form whose plain sort is the index's sort."""
    if col.dtype.kind == "M":
        return col.astype("datetime64[D]").astype(np.int64)
    if col.dtype.kind in "OUS":
        return col.astype(str)
    return col


def chunk_ranges(file_rows, batch_rows) -> list:
    """``[(first row, end row)]`` of the chunks, in rows of the files laid end
    to end in name order."""
    groups, start, rows = [], 0, 0
    at = 0
    for n in file_rows:
        if batch_rows and rows and rows + n > batch_rows:
            groups.append((start, at))
            start, rows = at, 0
        at += n
        rows += n
        if batch_rows and rows >= batch_rows:
            groups.append((start, at))
            start, rows = at, 0
    if rows:
        groups.append((start, at))
    out = []
    for lo, hi in groups:
        n = hi - lo
        if batch_rows and 0 < batch_rows < n:
            pieces = -(-n // batch_rows)
            size = -(-n // pieces)
            out += [(o, min(o + size, hi)) for o in range(lo, hi, size)]
        else:
            out.append((lo, hi))
    return out


def _numpy(table: pa.Table, name: str) -> np.ndarray:
    return table.column(name).to_numpy(zero_copy_only=False)


def reference_index(files, indexed, included, num_buckets: int, batch_rows=None) -> dict:
    """``{bucket: [run, ...]}``: the runs (``pa.Table`` with the index's
    columns, indexed first) that the build has to write, in chunk order."""
    files = sorted(files)
    columns = list(indexed) + list(included)
    source = pa.concat_tables([pq.read_table(f, columns=columns) for f in files]).combine_chunks()
    file_rows = [pq.read_metadata(f).num_rows for f in files]
    runs = {}
    for lo, hi in chunk_ranges(file_rows, batch_rows):
        chunk = source.slice(lo, hi - lo)
        keys = [_numpy(chunk, c) for c in indexed]
        bucket = bucket_of(keys, num_buckets)
        order = np.arange(hi - lo)
        # a stable sort by the last key first, the bucket last: equal keys stay in source order
        for col in [order_key(k) for k in reversed(keys)] + [bucket]:
            order = order[np.argsort(col[order], kind="stable")]
        sorted_bucket = bucket[order]
        bounds = np.searchsorted(sorted_bucket, np.arange(num_buckets + 1))
        for b in range(num_buckets):
            if bounds[b + 1] > bounds[b]:
                runs.setdefault(b, []).append(chunk.take(pa.array(order[bounds[b]:bounds[b + 1]])))
    return runs


def run_content(table: pa.Table, columns) -> tuple:
    """A run as a value that compares: every column's values, in row order."""
    return tuple(tuple(table.column(c).to_pylist()) for c in columns)
