"""Scan-side IO: parquet files -> columnar batches.

Index files (written uncompressed PLAIN/dictionary by the bucketed writer —
indexes/covering.py) decode through the native C++ path
(hyperspace_tpu.native): mmap -> column-chunk decode straight into numpy
buffers, no JVM and no pyarrow table materialization in the hot loop
(SURVEY.md §7 design stance (c)). Files outside the native dialect
(compressed, nested, unsupported encodings) fall back to pyarrow per file.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional

import numpy as np
import pyarrow as pa
import pyarrow.dataset as pads
import pyarrow.parquet as pq

from hyperspace_tpu.exec import batch as B
from hyperspace_tpu.exec import trace
from hyperspace_tpu.exec.file_identity import file_identities
from hyperspace_tpu.obs import spans
from hyperspace_tpu.reliability import errors as rerr
from hyperspace_tpu.reliability.degrade import QUARANTINE
from hyperspace_tpu.reliability.faults import FAULTS
from hyperspace_tpu.reliability.retry import with_retry

# ---------------------------------------------------------------------------
# Per-file decoded-batch cache (the framework's buffer pool). Spark gets this
# from the OS page cache + executor columnar caching; here repeated scans of
# the same immutable index/bucket files skip decode entirely. Entries key on
# the file's identity (path, size, mtime_ns: exec/file_identity.py) plus the
# columns, so any rewrite invalidates naturally.
# ---------------------------------------------------------------------------

from hyperspace_tpu.utils.lru import BytesLRU

_io_cache = BytesLRU(int(os.environ.get("HS_IO_CACHE_BYTES", 1 << 31)))


def _batch_nbytes(batch: B.Batch) -> int:
    total = 0
    for a in batch.values():
        if a.dtype == object and len(a):
            # strings: numpy reports pointer size only; estimate payload by
            # scaling a bounded sample to the full length
            k = min(len(a), 64)
            sample = sum(len(str(v)) for v in a[:k])
            total += int(a.nbytes) + int(sample * len(a) / k)
        else:
            total += int(a.nbytes)
    return total


def _io_cache_key(identity, columns: Optional[List[str]]):
    """Cache key of one file's decoded ``columns`` from the file's identity
    (file_identity.file_identities); None (= don't cache) where it has none."""
    if identity is None:
        return None
    return identity + (tuple(columns) if columns is not None else None,)


def _io_cache_get(key) -> Optional[B.Batch]:
    if key is None:
        return None
    got = _io_cache.get(key)
    if got is not None:
        return dict(got)  # callers may add/remove dict keys
    return None


def _io_cache_put(key, batch: B.Batch, cold: bool = False) -> None:
    """``cold``: the entry is the first to go under pressure (BytesLRU.put).
    The per-file pieces of a multi-file read whose concatenation is cached
    too are: the concatenation answers the next read, and the pieces beside
    it count the same rows against the cap a second time — left warm, one
    pass of whole-scan reads pushed live entries (join sides, other
    concatenations) out to keep them."""
    if key is None:
        return
    # cached buffers are shared with every future reader of this file —
    # freeze them so an in-place mutation of a collected result raises
    # instead of silently corrupting the cache (collect() results can be
    # read-only views; copy before mutating)
    for a in batch.values():
        a.setflags(write=False)
    _io_cache.put(key, dict(batch), _batch_nbytes(batch), cold=cold)


def _concat_key(file_keys):
    """Cache key of a multi-file read's concatenated batch; None when one
    of the per-file keys is (a stat failed): embedding a None in the tuple
    would collide unrelated scans."""
    return None if None in file_keys else ("concat", tuple(file_keys))


def discard_reads(identities, columns: List[str]) -> None:
    """Drop what a read of ``columns`` over the files with ``identities``
    left in the cache, the per-file entries and their concatenation: a
    reader that keeps its own merged copy of those rows (a join side,
    exec/device._read_buckets) holds them once, not beside these."""
    keys = [_io_cache_key(k, columns) for k in identities]
    for key in keys + [_concat_key(keys)]:
        if key is not None:
            _io_cache.discard(key)


def clear_io_cache() -> None:
    _io_cache.clear()


def _key_mentions_path(key, paths) -> bool:
    # cache keys are nested tuples whose leaves include the source path
    # string: file keys are (path, mtime, size, cols), concat keys wrap a
    # tuple of per-file keys, row-group keys append a suffix tuple, a join
    # side's column holds its scan's file identities — a recursive scan
    # covers every shape without coupling to each layout
    if isinstance(key, str):
        return key in paths
    if isinstance(key, tuple):
        return any(_key_mentions_path(part, paths) for part in key)
    return False


def purge_io_cache(paths) -> int:
    """Drop every cached batch derived from any of ``paths`` (data-version
    commit invalidation); returns the number of entries removed."""
    wanted = set(paths)
    if not wanted:
        return 0
    removed = 0
    for key in _io_cache.keys():
        if _key_mentions_path(key, wanted) and _io_cache.discard(key):
            removed += 1
    return removed


_DECODE_POOL = None
_DECODE_POOL_LOCK = threading.Lock()
_DECODE_POOL_SIZE = None  # width the live pool was created with
_CONFIGURED_THREADS: Optional[int] = None  # from conf, via set_decode_threads


def decode_threads() -> int:
    """Effective decode-pool width: HS_DECODE_THREADS env > session conf
    (``hyperspace.exec.io.decodeThreads``) > default 8."""
    env = os.environ.get("HS_DECODE_THREADS")
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    return max(1, _CONFIGURED_THREADS or 8)


def set_decode_threads(n: Optional[int]) -> None:
    """Record the conf-requested pool width (called on Session construction).
    An already-built pool of a different width is retired — its in-flight
    decodes finish on the old threads — and the next scan builds the new one."""
    global _CONFIGURED_THREADS, _DECODE_POOL, _DECODE_POOL_SIZE
    with _DECODE_POOL_LOCK:
        _CONFIGURED_THREADS = int(n) if n else None
        if _DECODE_POOL is not None and _DECODE_POOL_SIZE != decode_threads():
            _DECODE_POOL.shutdown(wait=False)
            _DECODE_POOL = None
            _DECODE_POOL_SIZE = None


def _decode_pool():
    """Shared decode thread pool — per-call pools would pay thread spin-up on
    every scan. Init is locked: serving workers scan concurrently, and a
    double-create here leaked a whole thread pool."""
    global _DECODE_POOL, _DECODE_POOL_SIZE
    if _DECODE_POOL is None:
        with _DECODE_POOL_LOCK:
            if _DECODE_POOL is None:
                from concurrent.futures import ThreadPoolExecutor

                _DECODE_POOL_SIZE = decode_threads()
                _DECODE_POOL = ThreadPoolExecutor(
                    max_workers=_DECODE_POOL_SIZE, thread_name_prefix="hs-decode"
                )
    return _DECODE_POOL


def _dtype_hints(schema: pa.Schema, columns: List[str]) -> Optional[Dict[str, np.dtype]]:
    """Numpy dtypes for native INT64-backed logical types (timestamps/dates).

    Returns None when any requested column's arrow type has no faithful
    numpy/native mapping (decimal, nested, ...) — the caller then uses pyarrow.
    """
    hints: Dict[str, np.dtype] = {}
    for c in columns:
        t = schema.field(c).type
        if pa.types.is_timestamp(t):
            hints[c] = np.dtype(f"datetime64[{t.unit}]")
        elif pa.types.is_date32(t):
            # INT32 days since epoch; pyarrow surfaces datetime64[D] — the
            # native wrapper widens int32 -> datetime64[D] by astype
            hints[c] = np.dtype("datetime64[D]")
        elif pa.types.is_date64(t):
            hints[c] = np.dtype("datetime64[ms]")
        elif (
            pa.types.is_time(t)       # time32/time64 surface as datetime.time objects
            or pa.types.is_duration(t)
            or pa.types.is_decimal(t)
            or pa.types.is_nested(t)
            or pa.types.is_dictionary(t)
        ):
            return None
    return hints


# ---------------------------------------------------------------------------
# Row-group pruning: a scan's pushed-down predicate is evaluated against the
# parquet footers' per-row-group min/max statistics BEFORE any decode, through
# the data-skipping rule's three-valued _SketchEvaluator (reused, not
# duplicated): "definitely no matching rows" skips the row group, anything
# uncertain decodes it. The Filter above re-applies the full predicate, so
# pruning is conservative by construction and never changes results.
# ---------------------------------------------------------------------------


def _rg_counters():
    from hyperspace_tpu.obs.metrics import REGISTRY

    return (
        REGISTRY.counter(
            "hs_rowgroups_scanned_total",
            "Parquet row groups decoded by predicate-pushdown scans",
        ),
        REGISTRY.counter(
            "hs_rowgroups_skipped_total",
            "Parquet row groups skipped by min/max statistics pruning",
        ),
        REGISTRY.counter(
            "hs_rowgroup_bytes_skipped_total",
            "Bytes of parquet row groups skipped by min/max statistics pruning",
        ),
    )


def _stats_array(vals: List) -> np.ndarray:
    """Per-row-group min or max values as an array the sketch evaluator's
    comparisons understand. None entries (absent statistics) survive as
    object-array nulls, which the evaluator keeps unconditionally."""
    import datetime

    if not vals or any(v is None for v in vals):
        out = np.empty(len(vals), dtype=object)
        out[:] = vals
        return out
    v0 = vals[0]
    if isinstance(v0, datetime.datetime):
        return np.array(vals, dtype="datetime64[us]")
    if isinstance(v0, datetime.date):
        return np.array(vals, dtype="datetime64[D]")
    if isinstance(v0, bytes):
        vals = [v.decode("utf-8", "surrogateescape") for v in vals]
    out = np.asarray(vals)
    if out.dtype.kind in ("U", "S"):
        out = out.astype(object)
    return out


def prune_row_groups(path: str, predicate) -> Optional[List[int]]:
    """Row-group indices of ``path`` that *might* hold rows matching
    ``predicate``, judged by footer min/max statistics; None when nothing can
    be pruned (every group kept). Columns without statistics — or predicate
    shapes outside the evaluator's language — keep their groups."""
    from hyperspace_tpu.indexes.dataskipping import MinMaxSketch
    from hyperspace_tpu.rules.dataskipping_rule import _SketchEvaluator

    refs = sorted(set(predicate.references()))
    if not refs:
        return None
    try:
        if FAULTS.active:
            FAULTS.check("io.footer", path)
        md = pq.read_metadata(path)
    except (OSError, pa.ArrowInvalid) as exc:
        # pruning is an optimization: the full decode below still answers
        # (and will surface/classify a genuinely bad file) — but the footer
        # failure itself is counted, never silently ignored
        rerr.count_io_error("io.footer", exc, swallowed=True)
        return None
    n_rg = md.num_row_groups
    if n_rg == 0:
        return None
    rg0 = md.row_group(0)
    col_idx = {rg0.column(j).path_in_schema: j for j in range(rg0.num_columns)}
    lower_idx = {name.lower(): j for name, j in col_idx.items()}
    sketches, table = [], {}
    for c in refs:
        j = col_idx.get(c, lower_idx.get(c.lower()))
        if j is None:
            continue  # partition / computed column: no file statistics
        mins: List = []
        maxs: List = []
        for i in range(n_rg):
            st = md.row_group(i).column(j).statistics
            if st is not None and st.has_min_max:
                mins.append(st.min)
                maxs.append(st.max)
            else:
                mins.append(None)
                maxs.append(None)
        s = MinMaxSketch(c)
        mn_name, mx_name = s.output_names()
        table[mn_name] = _stats_array(mins)
        table[mx_name] = _stats_array(maxs)
        sketches.append(s)
    if not sketches:
        return None
    try:
        mask = _SketchEvaluator(sketches, table, n_rg).eval(predicate)
    except Exception:
        return None  # pruning must never break a read the full decode answers
    if mask is None or mask.all():
        return None
    return [int(i) for i in np.nonzero(mask)[0]]


def _read_row_groups(
    f: str, columns: Optional[List[str]], schema: pa.Schema, keep: List[int], dsp, ckey
) -> B.Batch:
    """Decode only the surviving row groups of one file (pyarrow path; the
    native decoder reads whole column chunks). Fully-pruned files return a
    typed empty batch from the file schema. ``ckey`` is the whole file's
    cache key (_io_cache_key)."""
    scanned_c, skipped_c, bytes_c = _rg_counters()
    md = pq.read_metadata(f)
    n_rg = md.num_row_groups
    kept = set(keep)
    sk_bytes = sum(
        md.row_group(i).total_byte_size for i in range(n_rg) if i not in kept
    )
    scanned_c.inc(len(keep))
    skipped_c.inc(n_rg - len(keep))
    bytes_c.inc(sk_bytes)
    dsp.set(rowgroups_skipped=n_rg - len(keep), rowgroup_bytes_skipped=int(sk_bytes))
    if not keep:
        trace.record("decode", "rowgroup-pruned")
        t = schema.empty_table()
        if columns is not None:
            t = t.select(columns)
        return B.table_to_batch(t)
    ckey = ckey + (("rg",) + tuple(keep),) if ckey is not None else None
    got = _io_cache_get(ckey)
    if got is not None:
        trace.record("decode", "cached")
        return got
    trace.record("decode", "pyarrow-rowgroups")
    t = pq.ParquetFile(f).read_row_groups(keep, columns=columns)
    got = B.table_to_batch(t)
    dsp.set(rows=B.num_rows(got))
    _io_cache_put(ckey, got)
    return got


# ---------------------------------------------------------------------------
# Native row-group fast path: one scan decodes every surviving (file × row
# group × column) chunk in parallel on the hs-decode pool, each chunk writing
# straight into its slot of ONE √2-shape-bucket-padded buffer per column.
# Assembly is concat-free — the batch's column arrays are prefix views of the
# padded buffers, and the H2D staging hook (exec/device.py) detects the padded
# base and hands jax.device_put the exact memory the C decoder wrote.
# ---------------------------------------------------------------------------

_NATIVE_ENABLED = True  # hyperspace.exec.io.native.enabled
_NATIVE_RG = True  # hyperspace.exec.io.native.rowGroupDecode
_MAX_DICT = 4096  # hyperspace.exec.io.native.maxDictEntries
_STAGING_PAD = 1  # device-count multiple for padded buffers (set lazily)


def set_native_options(
    enabled: Optional[bool] = None,
    rowgroup: Optional[bool] = None,
    max_dict_entries: Optional[int] = None,
) -> None:
    """Record the conf-requested native decode knobs (called on Session
    construction, most-recent-wins — same contract as set_decode_threads)."""
    global _NATIVE_ENABLED, _NATIVE_RG, _MAX_DICT
    if enabled is not None:
        _NATIVE_ENABLED = bool(enabled)
    if rowgroup is not None:
        _NATIVE_RG = bool(rowgroup)
    if max_dict_entries is not None:
        _MAX_DICT = int(max_dict_entries)


def set_staging_pad(m: int) -> None:
    """Device-count multiple the staging padder rounds to; wired when a
    session materializes its mesh. A stale value only costs the zero-copy
    handoff (device._pad_to_bucket falls back to a pad copy), never rows."""
    global _STAGING_PAD
    _STAGING_PAD = max(1, int(m))


def _native_decode_counter(codec: str):
    from hyperspace_tpu.obs.metrics import REGISTRY

    return REGISTRY.counter(
        "hs_native_decode_total",
        "Column chunks decoded by the native row-group fast path",
        codec=codec,
    )


def _native_bytes_counter():
    from hyperspace_tpu.obs.metrics import REGISTRY

    return REGISTRY.counter(
        "hs_native_decode_bytes_total",
        "Logical bytes written into decode buffers by the native fast path",
    )


def _native_fallback_counter(reason: str):
    from hyperspace_tpu.obs.metrics import REGISTRY

    return REGISTRY.counter(
        "hs_native_fallback_total",
        "Decode attempts that left the native path for pyarrow",
        reason=reason,
    )


def _padded_rows(n: int) -> int:
    """Rows to allocate for ``n`` decoded rows: the √2 shape bucket the device
    padder would pick, rounded up to the mesh's device-count multiple — so the
    staged array IS the decode buffer, no pad copy."""
    if n <= 0:
        return 0
    from hyperspace_tpu.exec.device import bucket_rows

    t = bucket_rows(n)
    m = max(1, _STAGING_PAD)
    return t + (-t) % m


def _native_rg_scan(
    files: List[str],
    columns: Optional[List[str]],
    schemas: List[pa.Schema],
    predicate,
    file_keys: list,
    concat_key,
    kept: Optional[list] = None,
) -> Optional[B.Batch]:
    """Decode a whole scan natively at row-group granularity; None when the
    scan can't be answered natively end to end (caller falls back to the
    per-file path, which keeps its own native-first discipline).

    Requirements checked here: every file opens in the native dialect, every
    requested column decodes to one consistent dtype, and nothing about the
    scan is already cached. Row-group pruning applies per file with the same
    counter accounting as _read_row_groups; a pruned scan skips all cache
    writes (a pruned batch under an unpruned key would poison later readers)
    and reports the pruned files' surviving groups in ``kept``, as
    read_parquet_batch documents.
    """
    from hyperspace_tpu import native

    env = os.environ.get("HS_NATIVE_RG")
    if env is not None and env.strip().lower() in ("0", "false", "off"):
        return None
    if not (_NATIVE_ENABLED and _NATIVE_RG) or not files:
        return None
    cols = list(columns) if columns is not None else list(schemas[0].names)
    if not cols:
        return None
    hints = _dtype_hints(schemas[0], cols)
    if hints is None:
        return None  # per-file path counts the dtype fallback
    # one shared buffer per column needs ONE dtype: identical arrow types
    # across files (same-name/new-type evolution goes through the per-file path)
    t0 = {c: schemas[0].field(c).type for c in cols}
    for s in schemas[1:]:
        if any(not s.field(c).type.equals(t0[c]) for c in cols):
            return None

    handles: List[native.NativeParquetFile] = []
    try:
        try:
            for f in files:
                handles.append(native.NativeParquetFile(f))
        except native.NativeUnsupported:
            return None  # per-file path retries native and counts the fallback
        except OSError as exc:
            rerr.count_io_error("io.decode", exc, swallowed=True)
            _native_fallback_counter("io-error").inc()
            return None
        return _native_rg_decode(
            files, cols, hints, predicate, file_keys, concat_key, handles, kept
        )
    finally:
        for h in handles:
            h.close()


def _native_rg_decode(
    files: List[str],
    cols: List[str],
    hints: Dict[str, np.dtype],
    predicate,
    file_keys: list,
    concat_key,
    handles,
    kept: Optional[list],
) -> Optional[B.Batch]:
    from hyperspace_tpu import native

    # -- per-column plan: buffer dtype (None = strings -> object array) ------
    col_dtype: Dict[str, Optional[np.dtype]] = {}
    col_scratch32 = set()  # date32: int32 chunk scratch astype'd into datetime64[D]
    col_opt: Dict[str, bool] = {}
    try:
        for c in cols:
            nd = handles[0].column_numpy_dtype(c)
            hint = hints.get(c)
            if nd is None:
                dt = None
            elif hint is not None and nd.kind in ("i", "u"):
                if hint.itemsize == nd.itemsize:
                    dt = hint  # timestamps/date64: decode int64 straight into the view
                elif hint.kind == "M":
                    dt = hint
                    col_scratch32.add(c)
                else:
                    dt = nd
            else:
                dt = nd
            col_dtype[c] = dt
            col_opt[c] = any(h.column_optional(c) for h in handles)
    except native.NativeUnsupported:
        return None  # per-file path retries native and counts the fallback

    # -- per-file row plan + pruning (same counters as _read_row_groups) -----
    per_file_keep: List[List[int]] = []
    file_rows: List[int] = []
    file_skip: List[Optional[tuple]] = []  # (groups skipped, bytes skipped)
    fully_pruned: List[bool] = []
    pruned_any = False
    try:
        for f, h in zip(files, handles):
            keep = prune_row_groups(f, predicate) if predicate is not None else None
            if keep is None:
                ks = list(range(h.num_row_groups))
                file_skip.append(None)
            else:
                pruned_any = True
                ks = keep
                kept_set = set(ks)
                md = pq.read_metadata(f)
                sk_bytes = sum(
                    md.row_group(i).total_byte_size
                    for i in range(h.num_row_groups)
                    if i not in kept_set
                )
                scanned_c, skipped_c, bytes_c = _rg_counters()
                scanned_c.inc(len(ks))
                skipped_c.inc(h.num_row_groups - len(ks))
                bytes_c.inc(sk_bytes)
                file_skip.append((h.num_row_groups - len(ks), int(sk_bytes)))
            per_file_keep.append(ks)
            fully_pruned.append(keep is not None and not ks)
            file_rows.append(sum(h.rg_rows[g] for g in ks))
    except (OSError, pa.ArrowInvalid) as exc:
        rerr.count_io_error("io.footer", exc, swallowed=True)
        _native_fallback_counter("io-error").inc()
        return None

    total = sum(file_rows)
    starts: List[int] = []
    acc = 0
    for r in file_rows:
        starts.append(acc)
        acc += r
    padded = _padded_rows(total)

    # -- shared decode buffers, tail pre-filled like device._pad_to_bucket ---
    buffers: Dict[str, np.ndarray] = {}
    validity: Dict[str, np.ndarray] = {}
    for c in cols:
        dt = col_dtype[c]
        if dt is None:
            buffers[c] = np.empty(total, dtype=object)
        else:
            buf = np.empty(padded, dtype=dt)
            if padded > total:
                if dt == np.float64:
                    buf[total:] = np.nan
                elif dt.kind == "M":
                    buf.view(np.int64)[total:] = 0
                else:
                    buf[total:] = 0
            buffers[c] = buf
            if col_opt[c]:
                validity[c] = np.ones(total, dtype=np.uint8)

    # -- dictionary-shipping plan for low-cardinality string columns ---------
    # every surviving chunk must be fully dictionary-encoded with a dictionary
    # within maxDictEntries; chunk dictionaries remap into one global one so
    # codes are consistent across files/row groups
    chunks = [(fi, g) for fi in range(len(files)) for g in per_file_keep[fi]]
    dict_plan: Dict[str, tuple] = {}  # c -> (codes buffer, remaps, global uniques)
    if _MAX_DICT > 0:
        for c in cols:
            if col_dtype[c] is not None:
                continue
            try:
                if not all(
                    0 < handles[fi].rg_dict_count(g, c) <= _MAX_DICT for fi, g in chunks
                ):
                    continue
                dicts = [handles[fi].read_dict_rg_arrow(g, c) for fi, g in chunks]
            # not a pyarrow fallback: the column still decodes natively below,
            # just with materialized strings instead of shipped codes
            except native.NativeUnsupported:  # hscheck: disable=native-fallback
                continue
            # one global dictionary + per-chunk remaps from a single C++
            # hash pass (arrow dictionary_encode over the decoder's raw
            # buffers): a per-entry Python merge loop here once cost as much
            # as the C decode itself, and only the global uniques ever
            # materialize as Python strings
            remaps: Dict[tuple, np.ndarray] = {}
            if dicts:
                ends = np.cumsum([len(d) for d in dicts])
                enc = (
                    pa.concat_arrays(dicts) if len(dicts) > 1 else dicts[0]
                ).dictionary_encode()
                gu = enc.dictionary.to_numpy(zero_copy_only=False)
                inv = enc.indices.to_numpy().astype(np.int32, copy=False)
                remaps = {
                    key: inv[end - len(d) : end]
                    for key, d, end in zip(chunks, dicts, ends)
                }
            else:
                gu = np.empty(0, dtype=object)
            cbuf = np.empty(padded, dtype=np.int32)
            if padded > total:
                cbuf[total:] = 0
            dict_plan[c] = (cbuf, remaps, gu)

    # -- parallel chunk decode ------------------------------------------------
    bytes_c = _native_bytes_counter()

    def _decode_chunk(fi: int, g: int, c: str, start: int, nrows: int) -> None:
        h = handles[fi]
        codec = h.rg_codec(g, c)
        plan = dict_plan.get(c)
        if plan is not None:
            cbuf, remaps, _gu = plan
            codes = h.read_codes_rg(g, c)
            rm = remaps[(fi, g)]
            cbuf[start : start + nrows] = np.where(
                codes >= 0, rm[np.maximum(codes, 0)], np.int32(-1)
            )
            nb = nrows * 4
        elif col_dtype[c] is None:
            vals, v8, nb = h.read_binary_rg(g, c)
            if v8 is not None and not v8.all():
                vals[v8 == 0] = None
            buffers[c][start : start + nrows] = vals
        else:
            dst = buffers[c][start : start + nrows]
            v8 = validity[c][start : start + nrows] if c in validity else None
            if c in col_scratch32:
                scratch = np.empty(nrows, dtype=np.int32)
                h.read_fixed_rg_into(g, c, scratch, v8)
                dst[...] = scratch.astype(col_dtype[c])
            else:
                h.read_fixed_rg_into(g, c, dst, v8)
            nb = nrows * col_dtype[c].itemsize
        _native_decode_counter(codec).inc()
        bytes_c.inc(int(nb))

    # per-file scans (partition attach, file-name columns) call
    # read_parquet_batch FROM a decode-pool worker; submitting chunk tasks
    # back onto that same pool and blocking would deadlock once every worker
    # is such a caller — decode inline on this thread instead (still
    # zero-copy into the shared buffers, just serial for this one file)
    inline = threading.current_thread().name.startswith("hs-decode")
    pool = None if inline else _decode_pool()
    errors: Dict[int, List[BaseException]] = {}
    futs_by_file: List[list] = []
    all_futs: list = []
    try:
        for fi, f in enumerate(files):
            futs: list = []
            futs_by_file.append(futs)
            try:
                if FAULTS.active:
                    FAULTS.check("io.decode", f)  # the "before the C call" seam
            except Exception as exc:
                errors.setdefault(fi, []).append(exc)
                continue
            row = starts[fi]
            for g in per_file_keep[fi]:
                nrows = handles[fi].rg_rows[g]
                for c in cols:
                    if inline:
                        try:
                            _decode_chunk(fi, g, c, row, nrows)
                        except Exception as exc:
                            errors.setdefault(fi, []).append(exc)
                    else:
                        futs.append(
                            pool.submit(_decode_chunk, fi, g, c, row, nrows)
                        )
                row += nrows
            all_futs.extend(futs)
        for fi, f in enumerate(files):
            for fut in futs_by_file[fi]:
                try:
                    fut.result()
                except Exception as exc:
                    errors.setdefault(fi, []).append(exc)
            if fi not in errors and FAULTS.active:
                try:
                    FAULTS.check("io.decode", f)  # the "after the C call" seam
                except Exception as exc:
                    errors.setdefault(fi, []).append(exc)
    finally:
        # handles close right after we return — nothing may still be decoding
        if all_futs:
            from concurrent.futures import wait as _futures_wait

            _futures_wait(all_futs)

    if errors:
        # corrupt data surfaces typed and strikes quarantine — falling back
        # would re-read the same bad bytes (mirrors read_one's discipline)
        for fi, es in errors.items():
            for e in es:
                err = (
                    e
                    if isinstance(e, rerr.ReliabilityError)
                    else rerr.classify(e, path=files[fi])
                    if isinstance(e, (OSError, pa.ArrowInvalid, pa.ArrowTypeError))
                    else None
                )
                if isinstance(err, rerr.CorruptDataError):
                    rerr.count_io_error("io.decode", err)
                    if QUARANTINE.enabled:
                        QUARANTINE.note_corrupt(files[fi])
                    raise err from e
        # transient/dialect failures: count, then the per-file path answers
        # (with retry) — a consumed one-shot fault must not go unrecorded
        for es in errors.values():
            for e in es:
                if isinstance(e, native.NativeUnsupported):
                    _native_fallback_counter("dialect").inc()
                else:
                    rerr.count_io_error("io.decode", e, swallowed=True)
                    _native_fallback_counter("io-error").inc()
        return None

    # -- assemble: prefix views of the padded buffers, pyarrow null parity ---
    out: B.Batch = {}
    for c in cols:
        plan = dict_plan.get(c)
        if plan is not None:
            cbuf, _remaps, gu = plan
            codes_v = cbuf[:total]
            if gu.size:
                nulls = codes_v < 0
                if nulls.any():
                    exp = gu[np.where(nulls, np.int32(0), codes_v)]
                    exp[nulls] = None
                else:
                    exp = gu[codes_v]
            else:
                exp = np.full(total, None, dtype=object)
            out[c] = B.dict_backed(np.asarray(exp, dtype=object), codes_v, gu)
        elif col_dtype[c] is None:
            out[c] = buffers[c]
        else:
            vals = buffers[c][:total]
            v8 = validity.get(c)
            if v8 is not None and not v8.all():
                # parity with pyarrow's to_numpy (see native.read_columns)
                if vals.dtype.kind == "f":
                    vals = vals.copy()
                    vals[v8 == 0] = np.nan
                elif vals.dtype.kind == "M":
                    vals = vals.copy()
                    vals[v8 == 0] = np.datetime64("NaT")
                elif vals.dtype.kind == "b":
                    vals = vals.astype(object)
                    vals[v8 == 0] = None
                elif vals.dtype.kind in ("i", "u"):
                    vals = vals.astype(np.float64)
                    vals[v8 == 0] = np.nan
            out[c] = vals

    for fi, f in enumerate(files):
        with spans.span("decode", cat="io", file=os.path.basename(f)) as dsp:
            dsp.set(rows=file_rows[fi])
            if file_skip[fi] is not None:
                dsp.set(
                    rowgroups_skipped=file_skip[fi][0],
                    rowgroup_bytes_skipped=file_skip[fi][1],
                )
            trace.record("decode", "rowgroup-pruned" if fully_pruned[fi] else "native-rg")
        if QUARANTINE.enabled:
            QUARANTINE.note_ok(f)

    if not pruned_any:
        for fi, f in enumerate(files):
            s, e = starts[fi], starts[fi] + file_rows[fi]
            _io_cache_put(
                file_keys[fi], {c: out[c][s:e] for c in cols}, cold=concat_key is not None
            )
        if concat_key is not None:
            _io_cache_put(concat_key, dict(out))
    elif kept is not None:
        # only now: a scan that gave up above is read again per file
        kept.extend(
            (f, tuple(ks))
            for f, ks, skip in zip(files, per_file_keep, file_skip)
            if skip is not None
        )
    return out


def read_parquet_batch(
    files: List[str],
    columns: Optional[List[str]],
    predicate=None,
    kept: Optional[list] = None,
    committed=None,
) -> B.Batch:
    """Read ``columns`` of ``files`` into one concatenated batch, native-first.

    Schema-evolved datasets (a file missing a requested column, or differing
    per-file schemas when ``columns`` is None) go through a single
    dataset-level pyarrow read, which unifies schemas and null-fills — the
    per-file native path requires every file to carry every column.

    ``predicate`` (a pushed-down filter Expr) enables row-group min/max
    pruning: groups its statistics definitively exclude are never decoded.
    The caller's Filter still applies the predicate, so a cached full-file
    batch (more rows) is always an acceptable answer.

    ``kept`` (a list) is where the read reports which rows the batch holds:
    it receives one ``(file, kept row groups)`` entry for every file this read
    pruned and nothing for a file read whole, decoded or from the host cache.
    It stays empty exactly when the batch is every row of ``files`` in order;
    the executor keys resident device columns on it (_pruned_scan_key).

    ``committed`` is what the caller's index log entry recorded of these files
    (``Content.file_keys()``: path -> identity). The host cache is keyed on
    each file's identity, asked for once a file a call: from ``committed``
    where it knows the file, by a stat otherwise (file_identity.py).
    """
    from hyperspace_tpu import native

    file_keys = [_io_cache_key(k, columns) for k in file_identities(files, committed)]

    def _dataset_read() -> B.Batch:
        trace.record("decode", "pyarrow-dataset")
        try:
            # unify per-file schemas so evolved columns survive regardless of
            # file order (a bare dataset takes the FIRST fragment's schema)
            unified = pa.unify_schemas([pq.read_schema(f) for f in files])
            ds = pads.dataset(files, format="parquet", schema=unified)
        except (OSError, pa.ArrowInvalid, pa.ArrowTypeError) as exc:
            # schema unification is best-effort (first-fragment schema is a
            # correct fallback for homogeneous files); count the classified
            # failure — a truly bad file still raises out of to_table below
            rerr.count_io_error("io.footer", exc, swallowed=True)
            ds = pads.dataset(files, format="parquet")
        cols = columns
        if columns is not None and any("." in c and c not in ds.schema.names for c in columns):
            # nested struct paths (hybrid scan's appended-file side of a
            # nested index): project leaves into flat columns
            import pyarrow.compute as pc

            from hyperspace_tpu.plan.expr import strip_nested_prefix

            def resolve_path(dotted: str):
                # case-insensitive per segment (the resolver only exact-cases
                # the root; pc.field is case-sensitive)
                parts = dotted.split(".")
                out, fields = [], list(ds.schema)
                for i, p in enumerate(parts):
                    hit = next((f for f in fields if f.name.lower() == p.lower()), None)
                    if hit is None:
                        return parts  # let arrow raise its own error
                    out.append(hit.name)
                    if i < len(parts) - 1:
                        t = hit.type
                        fields = [t.field(j) for j in range(t.num_fields)] if pa.types.is_struct(t) else []
                return out

            cols = {}
            for c in columns:
                if c in ds.schema.names:
                    cols[c] = pc.field(c)
                else:
                    cols[c] = pc.field(*resolve_path(strip_nested_prefix(c)))
        t = ds.to_table(columns=cols)
        return B.table_to_batch(t)

    # a multi-file scan's CONCATENATED batch is itself cacheable (same
    # immutability argument as the per-file entries): re-concatenating 6M
    # rows cost ~0.7 s per execution of TPC-H q1 at sf=1. The entry lives in
    # the same byte-capped LRU; trace events mirror the per-file cached path
    # so dispatch goldens are insensitive to which cache tier answered.
    concat_key = None
    if columns is not None and len(files) > 1:
        concat_key = _concat_key(file_keys)
        got = _io_cache_get(concat_key)
        if got is not None:
            for _ in files:
                trace.record("decode", "cached")
            return got

    # fully-cached scan with an explicit projection: every cached batch holds
    # exactly ``columns``, so concatenation is schema-safe and the pq schema
    # pre-scan can be skipped. With columns=None per-file schemas may differ
    # (cached entries then have heterogeneous keys), so that case still goes
    # through the pre-scan below before trusting the cache.
    cached = [_io_cache_get(k) for k in file_keys]
    if columns is not None and cached and all(b is not None for b in cached):
        for _ in cached:
            trace.record("decode", "cached")
        if len(cached) == 1:
            return cached[0]
        out = B.concat(cached)
        if concat_key is not None:
            _io_cache_put(concat_key, out)
        return out

    # pre-scan schemas; any inconsistency -> unified dataset read. A corrupt
    # footer is NOT an inconsistency: falling back would re-read the same bad
    # bytes, so it surfaces typed (and strikes the owning index's breaker)
    try:
        schemas = []
        for f in files:
            if FAULTS.active:
                FAULTS.check("io.footer", f)
            try:
                schemas.append(pq.read_schema(f))
            except (pa.ArrowInvalid, pa.ArrowTypeError) as exc:
                err = rerr.classify(exc, path=f)
                rerr.count_io_error("io.footer", err)
                if QUARANTINE.enabled and isinstance(err, rerr.CorruptDataError):
                    QUARANTINE.note_corrupt(f)
                raise err from exc
    except OSError as exc:
        rerr.count_io_error("io.footer", exc, swallowed=True)
        return _dataset_read()
    evolved: set = set()
    unified: Optional[pa.Schema] = None
    if columns is None:
        names0 = list(schemas[0].names)
        if any(list(s.names) != names0 for s in schemas[1:]):
            return _dataset_read()
    else:
        missing = [f for f, s in zip(files, schemas) if any(c not in s.names for c in columns)]
        if missing:
            # schema-evolved files decode per file against the unified schema
            # (null-filling their missing columns) while native-dialect
            # siblings keep the native path — the old all-or-nothing gate sent
            # the WHOLE scan through one pyarrow dataset read
            if len(missing) == len(files):
                return _dataset_read()
            try:
                unified = pa.unify_schemas(schemas)
            except (pa.ArrowInvalid, pa.ArrowTypeError) as exc:
                rerr.count_io_error("io.footer", exc, swallowed=True)
                return _dataset_read()
            if any(c not in unified.names for c in columns):
                return _dataset_read()  # nested projection paths etc.
            evolved = set(missing)
            _native_fallback_counter("schema-evolved").inc(len(missing))

    if not evolved and not any(b is not None for b in cached):
        got = _native_rg_scan(files, columns, schemas, predicate, file_keys, concat_key, kept)
        if got is not None:
            return got

    # a read that caches its concatenation (below) is answered from that: its
    # files' batches beside it are a second copy of the same rows, cached cold
    concat_cached = concat_key is not None and predicate is None

    def read_one(f: str, schema, ckey) -> B.Batch:
        with spans.span("decode", cat="io", file=os.path.basename(f)) as dsp:
            got = _io_cache_get(ckey)
            if got is not None:
                trace.record("decode", "cached")
                return got
            if predicate is not None and f not in evolved:
                keep = prune_row_groups(f, predicate)
                if keep is not None:
                    got = _read_row_groups(f, columns, schema, keep, dsp, ckey)
                    if kept is not None:
                        kept.append((f, tuple(keep)))
                    return got

            def _decode() -> B.Batch:
                if FAULTS.active:
                    FAULTS.check("io.decode", f)
                if f in evolved:
                    # decode against the unified schema so this file's missing
                    # columns null-fill with their siblings' types
                    trace.record("decode", "pyarrow")
                    t = pads.dataset([f], format="parquet", schema=unified).to_table(
                        columns=columns
                    )
                    return B.table_to_batch(t)
                try:
                    cols = list(columns) if columns is not None else list(schema.names)
                    hints = _dtype_hints(schema, cols) if _NATIVE_ENABLED else None
                    if hints is None:
                        if _NATIVE_ENABLED:
                            _native_fallback_counter("dtype").inc()
                        out = None
                    else:
                        out = native.read_columns(f, cols, hints)
                except (native.NativeUnsupported, OSError, KeyError) as e:
                    # dialect mismatches are the expected fallback path; real
                    # IO failures falling through to the pyarrow re-read are
                    # classified and counted, never silently ignored
                    if isinstance(e, native.NativeUnsupported):
                        _native_fallback_counter("dialect").inc()
                    else:
                        rerr.count_io_error("io.decode", e, swallowed=True)
                        _native_fallback_counter("io-error").inc()
                    if os.environ.get("HS_DEBUG_DECODE_FALLBACK"):
                        import sys

                        print(f"DECODE-FALLBACK {f}: {type(e).__name__}: {e}", file=sys.stderr)
                    out = None
                if out is None:
                    trace.record("decode", "pyarrow")
                    t = pads.dataset([f], format="parquet").to_table(columns=columns)
                    out = B.table_to_batch(t)
                else:
                    trace.record("decode", "native")
                return out

            try:
                got = with_retry(_decode, op="io.decode")
            except rerr.ReliabilityError as exc:
                rerr.count_io_error("io.decode", exc)
                if QUARANTINE.enabled and isinstance(exc, rerr.CorruptDataError):
                    QUARANTINE.note_corrupt(f)
                raise
            except (OSError, pa.ArrowInvalid, pa.ArrowTypeError) as exc:
                err = rerr.classify(exc, path=f)
                rerr.count_io_error("io.decode", err)
                if QUARANTINE.enabled and isinstance(err, rerr.CorruptDataError):
                    QUARANTINE.note_corrupt(f)
                raise err from exc
            if QUARANTINE.enabled:
                QUARANTINE.note_ok(f)
            dsp.set(rows=B.num_rows(got))
            _io_cache_put(ckey, got, cold=concat_cached)
            return got

    # decode files concurrently (pyarrow and the native decoder release the
    # GIL); list order — bucket sortedness — is preserved by mapping, not by
    # completion. Fully-cached reads (here: the columns=None case, now known
    # schema-consistent) skip the pool: no decode to parallelize.
    if cached and all(b is not None for b in cached):
        for _ in cached:
            trace.record("decode", "cached")
        batches = cached
    elif len(files) > 1:
        # spans.wrap binds the submitting request's current span into the
        # pool workers — contextvars do NOT cross ThreadPoolExecutor on
        # their own, and decode spans must land in the caller's tree
        batches = list(_decode_pool().map(spans.wrap(read_one), files, schemas, file_keys))
    else:
        batches = [read_one(f, s, k) for f, s, k in zip(files, schemas, file_keys)]
    if not batches:
        return _dataset_read()
    if len(batches) == 1:
        return batches[0]
    out = B.concat(batches)
    # a predicate-pruned concatenation holds FEWER rows than the full scan;
    # caching it under the unpruned concat key would poison predicate-less
    # readers of the same files with silently missing rows
    if concat_cached:
        _io_cache_put(concat_key, out)
    return out
