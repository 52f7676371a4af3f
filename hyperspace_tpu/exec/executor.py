"""Host-path physical executor.

Executes a (possibly index-rewritten) logical plan over pyarrow + numpy. This
is the correctness baseline and the non-indexed fallback; index-accelerated
scans and joins are dispatched to the TPU device path (exec/device.py) when a
session mesh is available.

The reference delegates all of this to Spark's physical planner/executors;
here the framework owns it (SURVEY.md §7 design stance).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import pyarrow.dataset as pads

from hyperspace_tpu.exec import batch as B
from hyperspace_tpu.exec import trace
from hyperspace_tpu.exec.device import DeviceUnsupported
from hyperspace_tpu.exec.file_identity import (
    committed_keys,
    file_identities,
    leaf_files,
    scan_identity,
)
from hyperspace_tpu.obs import spans
from hyperspace_tpu.plan import logical as L
from hyperspace_tpu.plan.expr import (
    INPUT_FILE_NAME,
    Expr,
    InputFileName,
    as_bool_mask,
    extract_equi_join_keys,
    request_memo,
)
from hyperspace_tpu.reliability.errors import ReliabilityError

#: The only errors that send a streamed device path back to the materialized
#: one: the typed "not a device shape" signals (GroupCapacityExceeded is a
#: DeviceUnsupported) and the reliability taxonomy's lake-IO errors. A
#: TypeError, an XLA or Mosaic compile error, or a device runtime error
#: propagates — a correct host answer must never hide a broken device program.
_STREAM_FALLBACK_ERRORS = (DeviceUnsupported, ReliabilityError)


#: synthetic global-row-id column carried by the top-k host fallback
_TOPK_RID = "__hs_topk_rid__"


def _maybe_parallel(session, n_rows: Optional[int] = None):
    """The session's ``ShardedExecutor`` when ``hyperspace.parallel.enabled``
    is on (and, when a row count is known, the chunk clears
    ``hyperspace.parallel.minRows``); None routes to the single-device path."""
    if not session.conf.parallel_enabled:
        return None
    from hyperspace_tpu.parallel.executor import ShardedExecutor

    px = ShardedExecutor.maybe(session)
    if px is not None and n_rows is not None and not px.rows_ok(n_rows):
        return None
    return px


def _plan_needs_file_names(plan: L.LogicalPlan) -> bool:
    def expr_has(e: Expr) -> bool:
        if isinstance(e, InputFileName):
            return True
        return any(expr_has(c) for c in e.children())

    if isinstance(plan, L.Filter) and expr_has(plan.condition):
        return True
    return any(_plan_needs_file_names(c) for c in plan.children())


def _read_files(
    files: List[str],
    file_format: str,
    columns: Optional[List[str]],
    with_file_names: bool,
    partition_values: Optional[dict] = None,
    partition_dtypes: Optional[dict] = None,
    format_options: Optional[dict] = None,
    predicate=None,
    kept: Optional[list] = None,
    committed=None,
) -> B.Batch:
    """Read ``files`` into one batch. ``partition_values`` ({file -> {col ->
    typed value}}) attaches hive-partition columns — constant per file, absent
    from the file bytes — to each file's rows. ``predicate`` (the scan's
    pushed-down filter, re-applied by the Filter above) enables parquet
    row-group min/max pruning in the reader, which reports the groups it
    kept in ``kept`` (io.read_parquet_batch). ``committed`` is what the
    scan's log entry recorded of its files (file_identity.committed_keys)."""
    from hyperspace_tpu.exec.io import _decode_pool, read_parquet_batch

    if not files:
        # every file pruned (e.g. data-skipping removed all of them): empty
        # batch with the requested columns; dtype-less object arrays compare
        # fine against any literal on zero rows
        cols = list(columns or [])
        if with_file_names:
            cols.append(INPUT_FILE_NAME)
        return {c: np.empty(0, dtype=object) for c in cols}

    part_cols = set()
    if partition_values:
        for v in partition_values.values():
            part_cols.update(v)

    file_columns = columns
    attach: Optional[List[str]] = None
    if part_cols:
        if columns is None:
            attach = sorted(part_cols)
        else:
            attach = [c for c in columns if c in part_cols]
            file_columns = [c for c in columns if c not in part_cols]

    def read_one(f: str) -> B.Batch:
        from hyperspace_tpu.sources import formats as F

        if file_columns is not None and not file_columns:
            # every requested column is a partition column: the file is never
            # decoded, but its row count still shapes the output
            b: B.Batch = {}
            n = F.count_rows(f, file_format, format_options)
        elif file_format == "parquet":
            b = read_parquet_batch(
                [f], file_columns, predicate=predicate, kept=kept, committed=committed
            )
            n = B.num_rows(b)
        else:
            b = B.table_to_batch(F.read_table(f, file_format, file_columns, format_options))
            n = B.num_rows(b)
        if attach:
            from hyperspace_tpu.sources import partitions as P

            values = partition_values.get(f, {})
            for c in attach:
                dt = (partition_dtypes or {}).get(c, np.dtype(object))
                b[c] = P.column_array(values.get(c), dt, n)
        if with_file_names:
            b[INPUT_FILE_NAME] = np.full(B.num_rows(b), f, dtype=object)
        return b

    if with_file_names or attach:
        if len(files) > 1:
            # same fan-out as the plain-parquet path: per-file decode +
            # partition/file-name attachment are independent, and both the
            # native decoder and pyarrow release the GIL. spans.wrap carries
            # the caller's span context into the pool workers.
            from hyperspace_tpu.obs import spans

            return B.concat(list(_decode_pool().map(spans.wrap(read_one), files)))
        return B.concat([read_one(f) for f in files])
    if file_format == "parquet":
        return read_parquet_batch(
            list(files), columns, predicate=predicate, kept=kept, committed=committed
        )
    from hyperspace_tpu.sources import formats as F

    t = F.open_dataset(list(files), file_format, format_options).to_table(columns=columns)
    return B.table_to_batch(t)


def _prune_partitions(scan: L.Scan, condition) -> Optional[List[str]]:
    """Files of ``scan`` surviving the partition-column conjuncts of
    ``condition`` (None = no partitioning / nothing prunable)."""
    from hyperspace_tpu.plan.expr import split_conjunctive

    rel = scan.relation
    part_cols = set(getattr(rel, "partition_columns", []) or [])
    if not part_cols:
        return None
    terms = [t for t in split_conjunctive(condition) if set(t.references()) and set(t.references()) <= part_cols]
    if not terms:
        return None
    files = [fi.name for fi in rel.all_file_infos()]
    # vectorized: one "row" per file holding its partition values
    dtypes = getattr(rel, "partition_dtypes", {}) or {}
    from hyperspace_tpu.sources import partitions as P

    pvs = [rel.partition_values_for(f) for f in files]
    file_batch = {}
    for c in sorted(part_cols):
        dt = dtypes.get(c, np.dtype(object))
        vals = [pv.get(c) for pv in pvs]
        if dt == np.dtype(object):
            arr = np.empty(len(vals), dtype=object)
            arr[:] = vals
        else:
            arr = np.array([P.typed_value(None, dt) if v is None else v for v in vals], dtype=dt)
        file_batch[c] = arr
    mask = np.ones(len(files), dtype=bool)
    for t in terms:
        mask &= as_bool_mask(t.eval(file_batch))
    return [f for f, keep in zip(files, mask) if keep]


def _key_codes(arr: np.ndarray, asc: bool) -> np.ndarray:
    """Per-row int64 sort codes for one key column: rank by value (negated
    for descending), missing values (NaN/NaT/None) last in BOTH directions.
    The single ordering definition shared by the Sort node and windows."""
    n = arr.shape[0]
    if arr.dtype == object:
        missing = np.array(
            [v is None or (isinstance(v, float) and v != v) for v in arr], dtype=bool
        )
        conv = np.where(missing, "", arr.astype(str))
    elif arr.dtype.kind == "f":
        missing = np.isnan(arr)
        conv = np.where(missing, 0.0, arr)
    elif arr.dtype.kind == "M":
        missing = np.isnat(arr)
        fill = arr[~missing][0] if (~missing).any() else arr
        conv = np.where(missing, fill, arr)
    else:
        missing = np.zeros(n, dtype=bool)
        conv = arr
    _, codes = np.unique(conv, return_inverse=True)
    keyvals = (codes if asc else -codes).astype(np.int64)
    keyvals[missing] = np.iinfo(np.int64).max
    return keyvals


def _composite_codes(per_key: List[np.ndarray]) -> np.ndarray:
    """Collapse per-key int64 codes into one composite code per row (equal
    tuples share a code, ordering lexicographic)."""
    n = per_key[0].shape[0] if per_key else 0
    sort_order = np.lexsort(per_key[::-1])
    changed = np.zeros(n, dtype=bool)
    if n:
        changed[0] = False
        for kv in per_key:
            s = kv[sort_order]
            changed[1:] |= s[1:] != s[:-1]
    composite = np.cumsum(changed)
    out = np.empty(n, dtype=np.int64)
    out[sort_order] = composite
    return out


def _gather_spec(idx: np.ndarray):
    """Precompute the per-side gather inputs ONCE per join (the NaN mask and
    int cast are O(rows); recomputing them per payload column would waste
    exactly the work the slim merge saves): (direct_idx, None, None) for an
    all-matched int index, (shape, valid, ii) for a float index with NaN
    unmatched marks."""
    idx = np.asarray(idx)
    if idx.dtype.kind != "f":
        return (idx.astype(np.int64, copy=False), None, None)
    valid = ~np.isnan(idx)
    return (None, valid, idx[valid].astype(np.int64))


def _gather_with_missing(arr: np.ndarray, spec) -> np.ndarray:
    """Gather ``arr`` rows by a ``_gather_spec``; unmatched rows (pandas'
    outer merge marks them NaN) null-extend with the same dtype promotion
    pandas itself applies — ints to float64 NaN, bools to object, datetimes
    keep their unit with NaT."""
    direct, valid, ii = spec
    if direct is not None:
        return arr[direct]
    idx = valid  # shape source
    kind = arr.dtype.kind
    if kind in ("i", "u"):
        res = np.full(idx.shape, np.nan, dtype=np.float64)
        res[valid] = arr[ii].astype(np.float64)
    elif kind == "f":
        res = np.full(idx.shape, np.nan, dtype=arr.dtype)
        res[valid] = arr[ii]
    elif kind == "M":
        res = np.full(idx.shape, np.datetime64("NaT"), dtype=arr.dtype)
        res[valid] = arr[ii]
    elif kind == "m":
        res = np.full(idx.shape, np.timedelta64("NaT"), dtype=arr.dtype)
        res[valid] = arr[ii]
    else:  # strings/objects/bools null-extend as object NaN, like pandas
        res = np.full(idx.shape, np.nan, dtype=object)
        res[valid] = arr[ii]
    return res


def _order_codes(child: B.Batch, keys) -> np.ndarray:
    """One int64 composite code per row whose ordering equals the
    lexicographic (column, ascending) ordering — equal tuples share a code."""
    return _composite_codes([_key_codes(child[name], asc) for name, asc in keys])


def _window_column(child: B.Batch, spec, caches=None) -> np.ndarray:
    """Evaluate one window spec over the batch (pandas per-partition ops).
    ``caches`` memoizes partition ngroups and order codes across the sibling
    specs of one Window node (q47/q57 compute several windows over the same
    keys)."""
    import pandas as pd

    part_cache, codes_cache = caches if caches is not None else ({}, {})
    out_name, fn, arg, pcols, orders, cumulative = spec
    n = B.num_rows(child)
    # one int per row identifying its partition
    part = part_cache.get(tuple(pcols))
    if part is None:
        if pcols:
            part = pd.DataFrame({c: child[c] for c in pcols}).groupby(
                list(pcols), dropna=False, sort=False
            ).ngroup().to_numpy()
        else:
            part = np.zeros(n, dtype=np.int64)
        part_cache[tuple(pcols)] = part

    def order_codes():
        key = tuple(orders)
        got = codes_cache.get(key)
        if got is None:
            got = codes_cache[key] = _order_codes(child, orders)
        return got

    if fn in ("rank", "dense_rank", "row_number"):
        method = {"rank": "min", "dense_rank": "dense", "row_number": "first"}[fn]
        s = pd.Series(order_codes())
        return s.groupby(part).rank(method=method).astype(np.int64).to_numpy()

    pd_fn = {"sum": "sum", "min": "min", "max": "max", "avg": "mean", "count": "count"}[fn]
    vals = pd.Series(child[arg]) if arg is not None else pd.Series(np.ones(n, dtype=np.int64))
    if cumulative and orders:
        # explicit ROWS UNBOUNDED PRECEDING .. CURRENT ROW
        codes = order_codes()
        pos = np.lexsort((np.arange(n), part, codes))
        inv = np.empty(n, dtype=np.int64)
        inv[pos] = np.arange(n)
        sv = vals.iloc[pos].reset_index(drop=True)
        sp = part[pos]
        if fn == "count":
            cum = sv.notna().groupby(sp).cumsum()
        elif fn == "sum":
            # running sum skips NULLs (cumsum would leave NaN holes)
            cum = sv.fillna(0).groupby(sp).cumsum()
            all_null = (~sv.notna()).groupby(sp).cummin()  # NULL until a value
            cum[all_null.astype(bool)] = np.nan
        else:
            # expanding() emits rows grouped by partition: drop the group
            # level and sort back to sv's positional order before inverting
            cum = (
                sv.groupby(sp)
                .expanding()
                .agg(pd_fn)
                .reset_index(level=0, drop=True)
                .sort_index()
            )
        return np.asarray(cum)[inv]
    if fn == "count" and arg is None:
        return pd.Series(np.ones(n, dtype=np.int64)).groupby(part).transform("size").to_numpy()
    return vals.groupby(part).transform(pd_fn).to_numpy()


def _chain_to_scan(plan: L.LogicalPlan):
    """(wrappers, leaf) when ``plan`` is a chain of row-wise nodes
    (Project/Compute/Filter/Rename) over a single Scan/FileScan/IndexScan
    leaf — the shape the streaming executor can partition by files; (None,
    None) otherwise."""
    chain = []
    node = plan
    while isinstance(node, (L.Project, L.Compute, L.Filter, L.Rename)):
        chain.append(node)
        node = node.child
    if isinstance(node, (L.Scan, L.FileScan, L.IndexScan)):
        return chain, node
    return None, None


def _chain_needed_columns(chain, aggs=None, keys=None):
    """Source columns a scan chain references (roots of dotted paths
    included), for pruning the per-chunk scan."""
    needed = set()
    for node in chain:
        if isinstance(node, L.Project):
            needed |= set(node.columns)
        elif isinstance(node, L.Compute):
            for _, e in node.exprs:
                needed |= set(e.references())
        elif isinstance(node, L.Filter):
            needed |= set(node.condition.references())
        elif isinstance(node, L.Rename):
            needed |= set(node.mapping.keys())
    if aggs:
        needed |= {c for _, _, c in aggs if c is not None}
    if keys:
        needed |= set(keys)
    needed |= {n.split(".")[0] for n in needed if "." in n}
    return needed


def _chain_pushdown_condition(chain):
    """AND of the chain's Filter conditions that sit over only Projects —
    still expressed in source-column terms, so the scan's row-group pruning
    can evaluate them against file statistics. Compute/Rename rebind the
    namespace, so conditions above them don't push."""
    from hyperspace_tpu.plan.expr import BinaryOp

    cond = None
    for node in reversed(chain):  # leaf-most wrapper first
        if isinstance(node, L.Project):
            continue
        if isinstance(node, L.Filter):
            cond = node.condition if cond is None else BinaryOp("AND", cond, node.condition)
            continue
        break
    return cond


def _scan_aggregate_shape(plan: L.Aggregate):
    """``(scan, filter node or None, computes)`` when ``plan``'s child is the
    shape the device tier folds in one program: Projects, at most one
    Compute whose expressions read scan columns (computed aggregate inputs),
    and at most one Filter, below the Compute, over an index or file scan;
    None otherwise."""
    node = plan.child
    filter_node = None
    computes: list = []
    while isinstance(node, (L.Project, L.Compute, L.Filter)):
        if isinstance(node, L.Compute):
            if computes or filter_node is not None:
                return None
            computes = list(node.exprs)
        elif isinstance(node, L.Filter):
            if filter_node is not None:
                return None
            filter_node = node
        node = node.child
    if not isinstance(node, (L.IndexScan, L.FileScan)):
        return None
    names = {name for name, _ in computes}
    if names & set(plan.keys) or any(names & set(e.references()) for _, e in computes):
        return None  # a computed group key, or one computed from another
    if any(c in node.columns for c in names):
        return None  # a computed column shadows a scan column
    return node, filter_node, computes


def _request_aggregate_key(plan: L.Aggregate):
    """Key of a grouped aggregate over a scan in the request's memo: the
    sub-plan's exact fingerprint (structure, literals, the scan's relation or
    index version) and its output names, which the fingerprint leaves out
    (``sum(x) as a`` and ``sum(x) as b`` are one structure: the result cache
    relabels, this memo keeps them apart); None for any other shape. The memo
    lives for one request, which reads one snapshot, so the fingerprint says
    which files too."""
    if not plan.keys or _scan_aggregate_shape(plan) is None:
        return None
    from hyperspace_tpu.serving.fingerprint import plan_fingerprint

    return ("agg", plan_fingerprint(plan).exact, tuple(plan.output_columns))


def _footer_rows(node, keys) -> Optional[int]:
    """Rows of scan ``node`` from its Parquet files' footers, memoized on
    each file's identity (``keys``: ``scan_identity(node)``), so a file set
    is asked once; None where that cannot be known without reading."""
    if keys is None or (isinstance(node, L.FileScan) and node.file_format != "parquet"):
        return None
    from hyperspace_tpu.exec import device as D

    return sum(D._file_num_rows(k) for k in keys)


def _read_scan_files(scan, *args, **kwargs) -> B.Batch:
    """``_read_files`` for a scan leaf under its pushed-down predicate. What
    the read kept is left on the leaf for ``_kept_groups``: a leaf that
    carries a predicate is this execution's own clone, read once."""
    kwargs["committed"] = committed_keys(scan)
    predicate = getattr(scan, "pushdown_predicate", None)
    if predicate is None:
        return _read_files(*args, **kwargs)
    kept: list = []
    batch = _read_files(*args, predicate=predicate, kept=kept, **kwargs)
    # sorted: files decode concurrently and report in the order they finish
    scan.kept_row_groups = tuple(sorted(kept)) or None
    return batch


def _kept_groups(scan):
    """Kept signature of the batch that was read for ``scan``: None when it
    holds every row of ``scan.files`` — nothing was pushed down, the reader
    pruned nothing, or a cache that ignores the predicate answered — else
    the ``(file, kept row groups)`` of the files the read pruned."""
    return getattr(scan, "kept_row_groups", None)


def _pruned_scan_key(key, kept):
    """Device-cache key of a scan's batch: the scan identity ``key``, branded
    with the read's kept signature (``_kept_groups``) and nothing else.

    Invariant: two batches under one key hold the same rows in the same
    order. The identity fixes the files, their versions and their order; a
    read that pruned adds which row groups of which files survived, because
    two predicates can prune the same files to EQUAL row counts but
    DIFFERENT rows. A whole read shares the plain identity with every other
    predicate, literal and call site over that file set, so its columns stay
    resident on the device."""
    if key is None or kept is None:
        return key
    return key + (("rg-kept", kept),)


_BUCKET_PRUNE_COUNTERS: dict = {}


def _bucket_pruned(scan, count: bool = False) -> bool:
    """True when ``scan`` is an IndexScan narrowed to the buckets an
    equality's literal hashes to (``rules/utils.prune_index_buckets``). Its
    filter and aggregate are answered on the host, whatever
    ``deviceMinRows`` says: those rows are not among the device-resident
    columns (keyed on whole reads), every bucket has a row count of its own
    (a first-seen program shape), and comparing one bucket is microseconds
    of host work. With ``count`` the scan, read under a Filter, is counted
    in ``hs_index_bucket_prune_total{result=pruned|full}``."""
    if not isinstance(scan, L.IndexScan):
        return False
    pruned = scan.pruned_buckets is not None
    if count:
        result = "pruned" if pruned else "full"
        c = _BUCKET_PRUNE_COUNTERS.get(result)
        if c is None:
            from hyperspace_tpu.obs.metrics import REGISTRY

            c = _BUCKET_PRUNE_COUNTERS[result] = REGISTRY.counter(
                "hs_index_bucket_prune_total",
                "Index scans read under a Filter, by whether an equality on the "
                "bucket column narrowed them to its buckets",
                result=result,
            )
        c.inc()
    return pruned


def _rebuild_chain(chain, leaf: L.LogicalPlan) -> L.LogicalPlan:
    """Clone the row-wise wrappers over a replacement leaf (bottom-up)."""
    node = leaf
    for wrapper in reversed(chain):
        node = wrapper.with_children([node])
    return node


def _leaf_subset(leaf: L.LogicalPlan, files: List[str], needed=None) -> L.LogicalPlan:
    """A scan leaf over only ``files``; a relation-backed Scan becomes a
    FileScan carrying the relation's format/partition metadata (and pruned
    to ``needed`` columns — chunked decode pays per chunk, so decoding
    unreferenced columns would multiply the waste)."""
    import copy

    if isinstance(leaf, (L.FileScan, L.IndexScan)):
        clone = copy.copy(leaf)
        clone.files = list(files)
        return clone
    rel = leaf.relation
    cols = list(leaf.output_columns)
    if needed is not None:
        lowered = {n.lower() for n in needed}
        kept = [c for c in cols if c.lower() in lowered]
        cols = kept or cols
    pv = pd_ = None
    part_cols = list(getattr(rel, "partition_columns", []) or [])
    if part_cols:
        pv = {f: rel.partition_values_for(f) for f in files}
        dts = getattr(rel, "partition_dtypes", None)
        pd_ = dict(dts) if dts else None
    return L.FileScan(
        files,
        rel.physical_format,
        cols,
        partition_values=pv,
        partition_dtypes=pd_,
        format_options=getattr(rel, "options", None) or None,
    )


def _chunk_files_by_bytes(leaf, files: List[str], target_bytes: int, keys=None) -> List[List[str]]:
    """Greedy size-bounded groups of a scan leaf's ``files`` (a single file
    above the target forms its own group). Sizes are those of the files'
    identities ``keys`` (file_identity), asked for here unless the caller
    already has them."""
    if keys is None:
        keys = file_identities(files, committed_keys(leaf))
    groups: List[List[str]] = []
    cur: List[str] = []
    cur_bytes = 0
    for f, key in zip(files, keys):
        sz = key[1] if key is not None else target_bytes  # unknown -> isolate conservatively
        if cur and cur_bytes + sz > target_bytes:
            groups.append(cur)
            cur, cur_bytes = [], 0
        cur.append(f)
        cur_bytes += sz
    if cur:
        groups.append(cur)
    return groups


#: aggregate functions with a decomposable partial state (Spark's
#: partial/final split); distinct forms accumulate uniques (bounded by
#: distinct cardinality, not row count)
_STREAMABLE_AGGS = {
    "count", "sum", "min", "max", "avg", "stddev_samp",
    "count_distinct", "sum_distinct", "avg_distinct",
}


def host_aggregate(batch: B.Batch, keys: List[str], aggs) -> B.Batch:
    """The host pandas aggregate over an in-memory batch — the semantic
    reference every device/streamed aggregate path must reproduce byte-for-
    byte (NULL sums via min_count=1, dropna=False grouping, appearance-
    ordered groups via sort=False)."""
    import pandas as pd

    batch = {k: v for k, v in batch.items() if k != INPUT_FILE_NAME}
    n = B.num_rows(batch)

    def series(col_name: str) -> np.ndarray:
        from hyperspace_tpu.plan.expr import get_column

        got = batch.get(col_name)
        if got is None:
            got = get_column(batch, col_name)
        if got is None:
            raise KeyError(f"Aggregate input column {col_name!r} not found")
        return got

    _PD_FN = {"avg": "mean", "sum": "sum", "min": "min", "max": "max"}

    def _global_agg(fn: str, col_name: Optional[str]):
        if fn == "count":
            return n if col_name is None else int(pd.Series(series(col_name)).count())
        s = pd.Series(series(col_name))
        if fn == "count_distinct":
            return int(s.nunique(dropna=True))
        if fn in ("sum_distinct", "avg_distinct"):
            d = s.dropna().drop_duplicates()
            return d.sum(min_count=1) if fn == "sum_distinct" else d.mean()
        if fn == "stddev_samp":
            return s.std(ddof=1)
        if fn == "sum":
            # SQL: SUM over zero rows (or all NULLs) is NULL, not 0 —
            # pandas' min_count=0 default returns 0
            return s.sum(min_count=1)
        return getattr(s, _PD_FN[fn])()

    if not keys:
        out: B.Batch = {}
        for name, fn, col_name in aggs:
            out[name] = np.asarray([_global_agg(fn, col_name)])
        return out

    # object/string group keys factorize to int codes BEFORE entering the
    # frame: pandas' (Arrow-backed) string column construction was the
    # top cost of TPC-H q1's aggregate at sf=1 (0.6 s of 3.0 s), and the
    # groupby only needs key IDENTITY — real values map back at the end.
    # use_na_sentinel=False gives NaN its own code, matching dropna=False.
    key_uniques = {}
    frame_cols = {}
    agg_inputs = {c for _, _, c in aggs if c is not None}
    for k in keys:  # series(): dotted keys too
        arr = series(k)
        # a key that also feeds an aggregate (min(x) ... GROUP BY x)
        # must keep its real values — codes order by appearance
        if arr.dtype.kind in ("O", "U", "S") and k not in agg_inputs:
            codes, uniques = pd.factorize(arr, use_na_sentinel=False)
            frame_cols[k] = codes
            key_uniques[k] = uniques
        else:
            frame_cols[k] = arr
    for name, fn, col_name in aggs:
        if col_name is not None and col_name not in frame_cols:
            frame_cols[col_name] = series(col_name)
    df = pd.DataFrame(frame_cols)
    grouped = df.groupby(keys, dropna=False, sort=False)
    out = {}
    pieces = {}
    for name, fn, col_name in aggs:
        if fn == "count" and col_name is None:
            pieces[name] = grouped.size()
        elif fn == "count":
            pieces[name] = grouped[col_name].count()
        elif fn == "count_distinct":
            pieces[name] = grouped[col_name].nunique(dropna=True)
        elif fn == "sum_distinct":
            pieces[name] = grouped[col_name].agg(
                lambda s: s.dropna().drop_duplicates().sum(min_count=1)
            )
        elif fn == "avg_distinct":
            pieces[name] = grouped[col_name].agg(lambda s: s.dropna().drop_duplicates().mean())
        elif fn == "stddev_samp":
            pieces[name] = grouped[col_name].std(ddof=1)
        elif fn == "sum":
            # an all-NULL group must sum to NULL (SQL), not pandas' 0
            pieces[name] = grouped[col_name].sum(min_count=1)
        else:
            pieces[name] = getattr(grouped[col_name], _PD_FN[fn])()
    result = pd.DataFrame(pieces).reset_index()
    for k in keys:
        vals = result[k].to_numpy()
        uniq = key_uniques.get(k)
        out[k] = uniq[vals] if uniq is not None else vals
    for name, _, _ in aggs:
        out[name] = result[name].to_numpy()
    return out


def aggregate_batch(session, keys, aggs, batch: B.Batch) -> B.Batch:
    """Aggregate an already-materialized batch — the serving micro-batch
    path's final step. Grouped shapes try the device segment-reduction
    engine (``scan_key=None``: the batch is transient, nothing to cache);
    everything else, and every fallback, runs the host pandas path."""
    conf = session.conf
    keys = list(keys)
    aggs = list(aggs)
    if (
        keys
        and conf.device_execution_enabled
        and conf.agg_device_grouped_enabled
        and B.num_rows(batch) >= conf.device_exec_min_rows
    ):
        from hyperspace_tpu.exec import device as D

        try:
            got = D.device_grouped_aggregate(
                session,
                batch,
                None,
                keys,
                aggs,
                scan_key=None,
                max_groups=conf.agg_max_groups,
                cap_floor=conf.agg_capacity_floor,
                parallel=_maybe_parallel(session, B.num_rows(batch)),
            )
            trace.record("agg", "device-grouped-batch")
            return got
        except D.GroupCapacityExceeded:
            trace.fallback("agg", "spill")
        except D.DeviceUnsupported:
            trace.fallback("agg", "unsupported")
    return host_aggregate(batch, keys, aggs)


class Executor:
    def __init__(self, session):
        self.session = session

    def _prime_staging_pad(self) -> None:
        """Materialize the session mesh before the first scan decode so the
        native fast path pads its buffers to the device count up front
        (session._note_mesh -> io.set_staging_pad) — otherwise the first
        query's chunks decode with pad=1 and lose the zero-copy device_put
        handoff. A mesh failure is a failing ``jax.devices()`` and raises."""
        if self.session.conf.io_native_enabled:
            self.session.mesh

    def execute(
        self,
        plan: L.LogicalPlan,
        required_columns: Optional[List[str]] = None,
        prepruned: bool = False,
    ) -> B.Batch:
        from hyperspace_tpu.plan.expr import subquery_scope

        self._prime_staging_pad()

        # execution-time column pruning for EVERY plan (Catalyst runs
        # ColumnPruning unconditionally; ApplyHyperspace only prunes plans
        # it rewrites, and hyperspace-off queries never saw it at all —
        # TPC-H q7 carried 48-column join intermediates for ~10 referenced
        # columns). The approved-plan goldens pin the rule-relevant
        # optimized plan, like the reference's NORMALIZED approvals, so the
        # mechanical Project-over-scan layer stays out of them; the
        # dispatch trace still records what actually runs. Fallback keeps
        # the never-break-a-query contract. ``prepruned`` lets the serving
        # plan cache skip this walk for templates pruned once at compile.
        if not prepruned:
            try:
                from hyperspace_tpu.rules.utils import prune_columns

                plan = prune_columns(plan)
            except Exception:  # pruning must never kill a query
                # visible in recorded dispatch traces (and so in the goldens):
                # a silent fallback here once hid a RecursionError that cost
                # 3x on every view-sharing query
                trace.record("prune", "fallback-unpruned")

        # sub-plans referenced more than once (a CTE used N times holds ONE
        # plan object) execute once per collect; only those roots memoize.
        # NOTE: joins served by the device bucketed-SMJ path decode their
        # sides from index files directly (with their own byte-capped
        # caches), so this memo pays off on the host execution paths
        from hyperspace_tpu.rules.utils import shared_subplan_ids

        self._shared = shared_subplan_ids(plan)
        self._memo: Dict[Tuple[int, bool], B.Batch] = {}
        try:
            with subquery_scope():  # each subquery runs once per execute
                with_file_names = _plan_needs_file_names(plan)
                batch = self._exec(plan, with_file_names)
        finally:
            self._memo = {}
            self._shared = set()
        if required_columns is not None:
            batch = B.select(batch, required_columns)
        elif INPUT_FILE_NAME in batch:
            batch = {k: v for k, v in batch.items() if k != INPUT_FILE_NAME}
        return batch

    def execute_stream(self, plan: L.LogicalPlan):
        """Yield result batches incrementally (DataFrame.to_local_iterator).

        Streamed shapes: a (Project over) compatible bucketed Join yields
        per-bucket chunks via the streaming SMJ; a row-wise chain over one
        scan yields per-file-group chunks. Everything else yields the one
        materialized batch — streaming is an execution strategy, never an
        API restriction (Spark's toLocalIterator contract)."""
        from hyperspace_tpu.plan.expr import subquery_scope
        from hyperspace_tpu.rules.utils import prune_columns, shared_subplan_ids

        self._prime_staging_pad()
        try:
            plan = prune_columns(plan)
        except Exception:
            trace.record("prune", "fallback-unpruned")
        self._shared = shared_subplan_ids(plan)
        self._memo = {}
        try:
            with subquery_scope():
                if _plan_needs_file_names(plan):
                    batch = self._exec(plan, True)
                    yield {k: v for k, v in batch.items() if k != INPUT_FILE_NAME}
                    return
                node = plan
                if isinstance(node, L.Limit):
                    gen = self._stream_limit_node(node)
                    if gen is not None:
                        yield from gen
                        return
                proj = None
                if isinstance(node, L.Project):
                    proj, node = list(node.columns), node.child
                # a Filter directly above a Join fuses into the streaming
                # join paths (per-chunk mask for the SMJ, jitted post-join
                # program for the broadcast probe) instead of forcing the
                # materialized shape
                post_filter = None
                if isinstance(node, L.Filter) and isinstance(node.child, L.Join):
                    post_filter, node = node.condition, node.child
                if isinstance(node, L.Join) and self.session.conf.device_execution_enabled:
                    from hyperspace_tpu.exec import device as D
                    from hyperspace_tpu.exec import join_stream as JS

                    if D.join_sides_compatible(node) is not None:
                        gen = D.stream_bucketed_join(self.session, node)
                        try:
                            first = next(gen)
                        except StopIteration:
                            return
                        except D.DeviceUnsupported:
                            gen = None
                        if gen is not None:
                            from hyperspace_tpu.plan.expr import as_bool_mask

                            def shape(chunk):
                                if post_filter is not None:
                                    chunk = B.mask_rows(
                                        chunk, as_bool_mask(post_filter.eval(chunk))
                                    )
                                return B.select(chunk, proj) if proj else chunk

                            trace.record("join", "host-span-smj-stream")
                            yield shape(first)
                            for chunk in gen:
                                yield shape(chunk)
                            return
                    if JS.broadcast_spec(self.session, node) is not None:
                        gen = JS.stream_broadcast_join(
                            self, node, post_filter=post_filter, project=proj
                        )
                        try:
                            first = next(gen)
                        except StopIteration:
                            return
                        except D.DeviceUnsupported:
                            gen = None
                        if gen is not None:
                            yield first
                            yield from gen
                            return
                chain, leaf = _chain_to_scan(plan)
                if leaf is not None:
                    files = leaf_files(leaf)
                    groups = _chunk_files_by_bytes(
                        leaf, files, max(1, self.session.conf.stream_chunk_bytes)
                    )
                    if len(groups) > 1:
                        needed = _chain_needed_columns(chain) | set(plan.output_columns)
                        yield from self._stream_chunks(chain, leaf, groups, needed)
                        return
                batch = self._exec(plan, False)
                yield {k: v for k, v in batch.items() if k != INPUT_FILE_NAME}
        finally:
            self._memo = {}
            self._shared = set()

    def _stream_chunks(
        self, chain, leaf, groups, needed, leaf_only=False, stage_extra=None,
        dynamic_pushdown=None,
    ):
        """Yield one executed chain batch per file group, overlapping chunk
        k+1's decode + H2D staging with chunk k's execution via ScanPipeline
        (the tentpole's stage-1/2/3 split). Pushed-down Filter conditions are
        attached to each leaf clone for row-group pruning; the serial path
        (pipeline disabled, or a chain that needs file names) executes the
        same clones, so streamed results are identical either way.

        ``leaf_only=True`` yields ``(leaf_clone, chain_plan, leaf_batch)``
        instead of executed batches: the device grouped-aggregate stream
        consumes raw leaf chunks (the predicate fuses into its program) but
        must still be able to run the chain over the same prefetched batch
        when it falls back mid-stream. ``stage_extra`` names additional
        columns (group keys, aggregate inputs) the H2D staging hook uploads
        alongside the predicate columns.

        ``dynamic_pushdown`` is a zero-arg callable returning a *currently
        valid* extra pruning predicate (or None) — the top-k stream's running
        k-th-value threshold. It is evaluated inside each chunk's decode
        thunk, so prefetched chunks pick up whatever threshold the fold has
        reached by the time their decode starts (stale thresholds are merely
        conservative; pruning is row-group granularity and never row-exact).
        H2D staging stays off with a dynamic predicate, as it was when the
        scan key carried the predicate's text; keyed on what each read kept
        it could be turned on, which no test or benchmark cell covers yet."""
        conf = self.session.conf
        pushed = _chain_pushdown_condition(chain) if conf.rowgroup_pruning_enabled else None
        leaves, subs = [], []
        for g in groups:
            lf = _leaf_subset(leaf, g, needed)
            if pushed is not None and isinstance(lf, (L.FileScan, L.IndexScan)):
                lf.pushdown_predicate = pushed
            leaves.append(lf)
            subs.append(_rebuild_chain(chain, lf))
        wfns = [_plan_needs_file_names(s) for s in subs]

        def apply_dynamic(i):
            # refresh the chunk leaf's pruning predicate at decode time (the
            # top-k threshold tightens as earlier chunks fold)
            if dynamic_pushdown is None or not isinstance(
                leaves[i], (L.FileScan, L.IndexScan)
            ):
                return
            dp = dynamic_pushdown()
            if dp is None:
                return
            from hyperspace_tpu.plan.expr import BinaryOp

            leaves[i].pushdown_predicate = (
                dp if pushed is None else BinaryOp("AND", pushed, dp)
            )

        if not conf.pipeline_enabled or len(groups) < 2 or any(wfns):
            # leaf-batch prefetch can't also carry file-name columns; such
            # chains (rare: InputFileName in a filter) stay serial
            for i, (sub, wfn) in enumerate(zip(subs, wfns)):
                apply_dynamic(i)
                if leaf_only:
                    yield leaves[i], sub, self._exec(leaves[i], False)
                else:
                    yield self._exec(sub, wfn)
            return

        from hyperspace_tpu.exec import device as D
        from hyperspace_tpu.exec.pipeline import ScanPipeline

        # H2D staging (stage 2) applies when the chunk will take the device
        # filter path: Filter directly over the scan leaf
        dev_cond = None
        if (
            conf.device_execution_enabled
            and chain
            and isinstance(chain[-1], L.Filter)
            and isinstance(leaves[0], (L.FileScan, L.IndexScan))
        ):
            dev_cond = chain[-1].condition
        staging = (dev_cond is not None or stage_extra) and dynamic_pushdown is None

        def stage(i, batch):
            if B.num_rows(batch) < conf.device_exec_min_rows:
                return
            key = _pruned_scan_key(scan_identity(leaves[i]), _kept_groups(leaves[i]))
            # stage onto the mesh the consumer will execute over, so the
            # sharded path's device-cache lookups (keyed by mesh fingerprint)
            # hit the columns placed here
            D.stage_filter_columns(
                self.session, batch, dev_cond, key, extra_columns=stage_extra,
                parallel=_maybe_parallel(self.session, B.num_rows(batch)),
            )

        def weigh(batch):
            return sum(int(getattr(a, "nbytes", 0)) for a in batch.values())

        def decode(i):
            apply_dynamic(i)
            return self._exec(leaves[i], False)

        pipe = ScanPipeline(
            [(lambda i=i: decode(i)) for i in range(len(leaves))],
            depth=max(1, conf.pipeline_depth),
            max_buffered_bytes=conf.pipeline_max_buffered_bytes,
            weigh=weigh,
            stage=stage if staging else None,
        )
        try:
            for i, leaf_batch in enumerate(pipe):
                if leaf_only:
                    yield leaves[i], subs[i], leaf_batch
                    continue
                prev = getattr(self, "_leaf_override", None)
                self._leaf_override = (leaves[i], leaf_batch)
                try:
                    with spans.span("execute", cat="pipeline", chunk=i):
                        out = self._exec(subs[i], False)
                finally:
                    self._leaf_override = prev
                yield out
        finally:
            pipe.close()

    def _exec(self, plan: L.LogicalPlan, with_file_names: bool) -> B.Batch:
        # hits hand out shallow copies so callers may add derived keys
        # without cross-talk (arrays themselves are never mutated)
        if id(plan) in getattr(self, "_shared", ()):
            key = (id(plan), with_file_names)
            hit = self._memo.get(key)
            if hit is not None:
                return dict(hit)
            batch = self._exec_inner(plan, with_file_names)
            self._memo[key] = batch
            return dict(batch)
        return self._exec_inner(plan, with_file_names)

    def _exec_inner(self, plan: L.LogicalPlan, with_file_names: bool) -> B.Batch:
        # per-operator span: node-type name + result rows/bytes. One
        # contextvar read on the disabled path (spans.span returns the shared
        # null CM), so this sits on the recursion unconditionally.
        cm = spans.span(type(plan).__name__, cat="exec")
        if cm is spans._NULL_CM:
            return self._exec_node(plan, with_file_names)
        with cm as sp:
            batch = self._exec_node(plan, with_file_names)
            try:
                sp.set(
                    rows=B.num_rows(batch),
                    bytes=int(sum(getattr(a, "nbytes", 0) for a in batch.values())),
                )
            except Exception:
                pass
            return batch

    def _exec_node(self, plan: L.LogicalPlan, with_file_names: bool) -> B.Batch:
        # pipelined streaming hands the current chunk's prefetched leaf batch
        # to the consumer's chain execution through this override (identity
        # match: each chunk's leaf clone is unique to that chunk)
        ov = getattr(self, "_leaf_override", None)
        if ov is not None and plan is ov[0]:
            return dict(ov[1])

        if isinstance(plan, L.Scan):
            return self._exec_scan(plan, with_file_names)

        if isinstance(plan, L.FileScan):
            bucket_cache = getattr(self.session, "bucket_cache", None)
            if (
                bucket_cache is not None
                and not with_file_names
                and plan.files
                and plan.file_format == "parquet"
                and not plan.partition_values
                and not plan.format_options
            ):
                trace.record("scan", "bucket-cache-filescan")
                return bucket_cache.read(list(plan.files), list(plan.columns))
            return _read_scan_files(
                plan,
                list(plan.files),
                plan.file_format,
                list(plan.columns),
                with_file_names,
                partition_values=plan.partition_values,
                partition_dtypes=plan.partition_dtypes,
                format_options=plan.format_options,
            )

        if isinstance(plan, L.IndexScan):
            if plan.pruned_buckets is not None:
                trace.record("scan", f"index-bucket-pruned({len(plan.pruned_buckets)} buckets)")
            else:
                trace.record("scan", "index")
            fcols = plan.file_columns if plan.file_columns is not None else list(plan.columns)
            bucket_cache = getattr(self.session, "bucket_cache", None)
            if bucket_cache is not None and not with_file_names and plan.files:
                batch = bucket_cache.read(
                    list(plan.files), list(fcols), committed=committed_keys(plan)
                )
            else:
                batch = _read_scan_files(
                    plan, list(plan.files), "parquet", list(fcols), with_file_names
                )
            if plan.file_columns is not None:
                # nested index columns are stored under their flat
                # __hs_nested. name; present them under the output name
                renamed: B.Batch = {}
                for out, fc in zip(plan.columns, fcols):
                    renamed[out] = batch[fc]
                if INPUT_FILE_NAME in batch:
                    renamed[INPUT_FILE_NAME] = batch[INPUT_FILE_NAME]
                return renamed
            return batch

        if isinstance(plan, L.Filter):
            rg_ok = self.session.conf.rowgroup_pruning_enabled
            leaf = plan.child  # the node the batch is read for
            if isinstance(plan.child, L.Scan):
                # partition pruning: conjuncts over partition columns decide
                # per-file from path-derived values which files to read at all
                # (Spark's PartitioningAwareFileIndex.listFiles role)
                files = _prune_partitions(plan.child, plan.condition)
                child = self._exec_scan(
                    plan.child,
                    with_file_names,
                    files=files,
                    predicate=plan.condition if rg_ok else None,
                )
            elif getattr(plan.child, "pushdown_predicate", None) is not None:
                # a streamed leaf subset arrives with its pushdown already
                # attached (_stream_chunks); just execute it
                child = self._exec(plan.child, with_file_names)
            elif (
                rg_ok
                and isinstance(plan.child, (L.FileScan, L.IndexScan))
                and id(plan.child) not in self._shared
            ):
                # push the predicate down for row-group pruning on a CLONE:
                # the original node may be referenced by plan caches or
                # shared subtrees, which must keep full-read semantics
                import copy

                _bucket_pruned(plan.child, count=True)
                leaf = copy.copy(plan.child)
                leaf.pushdown_predicate = plan.condition
                child = self._exec(leaf, with_file_names)
            else:
                _bucket_pruned(plan.child, count=True)
                child = self._exec(plan.child, with_file_names)
            with spans.span("filter-mask", cat="exec"):
                mask = self._filter_mask(plan, child, kept=_kept_groups(leaf))
            with spans.span("filter-apply", cat="exec"):
                return B.mask_rows(child, mask)

        if isinstance(plan, L.Project):
            # projection pushdown into a directly-scanned source: decode ONLY
            # the projected columns (the column-pruned plan shape is
            # Project-over-Scan; reading all 16 lineitem columns to keep 7
            # doubled TPC-H q1's scan cost). Shared scans are pruned to one
            # shared Project, so the _exec memo above still deduplicates.
            if (
                isinstance(plan.child, L.Scan)
                and id(plan.child) not in self._shared
                and set(plan.columns) <= set(plan.child.output_columns)
            ):
                got = self._exec_scan(
                    plan.child, with_file_names, columns=list(plan.columns)
                )
                if with_file_names and INPUT_FILE_NAME in got:
                    return got
                return B.select(got, list(plan.columns))
            child = self._exec(plan.child, with_file_names)
            cols = list(plan.columns)
            if with_file_names and INPUT_FILE_NAME in child:
                cols = cols + [INPUT_FILE_NAME]
            return B.select(child, cols)

        if isinstance(plan, L.Compute):
            from hyperspace_tpu.plan.expr import EMPTY_SCALAR, NullableBool

            child = self._exec(plan.child, with_file_names)
            out = dict(child)
            n = B.num_rows(child)
            for name, expr in plan.exprs:
                v = expr.eval(child)
                if v is EMPTY_SCALAR:  # NULL scalar subquery -> NULL column
                    v = np.full(n, np.nan)
                elif isinstance(v, NullableBool):
                    # a three-valued boolean projected as a SELECT item keeps
                    # its NULLs (Spark yields NULL, not false — so IS NULL on
                    # the alias stays correct)
                    from hyperspace_tpu.plan.expr import _to_value_array

                    v = _to_value_array(v)
                v = np.asarray(v)
                if v.ndim == 0:
                    v = np.broadcast_to(v, (n,)).copy()
                out[name] = v
            return out

        if isinstance(plan, L.Join):
            return self._exec_join(plan, with_file_names)

        if isinstance(plan, L.Aggregate):
            return self._exec_aggregate(plan, with_file_names)

        if isinstance(plan, L.Sort):
            if not with_file_names:
                got = self._try_sorted_run_merge(plan)
                if got is not None:
                    return got
            child = self._exec(plan.child, with_file_names)
            from hyperspace_tpu.plan.expr import get_column

            order = np.arange(B.num_rows(child))
            # least-significant key first: stable argsorts compose into the
            # lexicographic order over all keys. Keys sort by rank (np.unique
            # codes): negation-safe for every dtype, and missing values
            # (NaN/None) rank last in BOTH directions like pandas.
            for name, asc in reversed(plan.keys):
                arr = get_column(child, name)
                if arr is None:
                    raise KeyError(f"Sort key {name!r} not found")
                keyvals = _key_codes(arr[order], asc)
                order = order[np.argsort(keyvals, kind="stable")]
            return {k: v[order] for k, v in child.items()}

        if isinstance(plan, L.Limit):
            if isinstance(plan.child, L.Sort) and not with_file_names:
                # ORDER BY ... LIMIT k: index-order merge first (no sort at
                # all), then the streaming device top-k; both are
                # byte-identical to host-sort-then-slice
                got = self._try_sorted_run_merge(plan.child, limit=plan.n)
                if got is None:
                    got = self._try_streaming_topk(plan.child, plan.n)
                if got is not None:
                    return got
            child = self._exec(plan.child, with_file_names)
            return {k: v[: plan.n] for k, v in child.items()}

        if isinstance(plan, L.Window):
            child = self._exec(plan.child, with_file_names)
            out = dict(child)
            caches = ({}, {})  # partition ngroups / order codes, shared by specs
            for spec in plan.specs:
                out[spec[0]] = _window_column(child, spec, caches)
            return out

        if isinstance(plan, L.Rename):
            child = self._exec(plan.child, with_file_names)
            return {plan.mapping.get(k, k): v for k, v in child.items()}

        if isinstance(plan, (L.Union, L.BucketUnion)):
            return B.concat([self._exec(c, with_file_names) for c in plan.children()])

        if isinstance(plan, L.SetOp):
            left = self._exec(plan.left, with_file_names)
            right = self._exec(plan.right, with_file_names)
            lcols = plan.left.output_columns
            rcols = plan.right.output_columns
            n_l = B.num_rows(left)
            # code rows over the CONCATENATION of both sides so equal values
            # of different dtypes (int64 vs float64 from a CAST or nullable
            # column) share a code; NULLs (NaN/NaT/None) compare equal via
            # the shared _key_codes missing handling
            per_key = []
            for lc, rc in zip(lcols, rcols):
                a, b = left[lc], right[rc]
                try:
                    both = np.concatenate([a, b])
                except (TypeError, ValueError):
                    both = np.concatenate([a.astype(object), b.astype(object)])
                per_key.append(_key_codes(both, True))
            comp = _composite_codes(per_key) if per_key else np.zeros(0, dtype=np.int64)
            l_codes, r_codes = comp[:n_l], comp[n_l:]
            rset = np.zeros(int(comp.max()) + 1 if comp.size else 1, dtype=bool)
            rset[r_codes] = True
            hit = rset[l_codes]
            first = np.zeros(n_l, dtype=bool)
            if n_l:
                _, first_idx = np.unique(l_codes, return_index=True)
                first[first_idx] = True  # distinct semantics
            keep = first & (hit if plan.kind == "intersect" else ~hit)
            take = np.nonzero(keep)[0]
            return {c: left[c][take] for c in lcols}

        if isinstance(plan, L.Repartition):
            # Host path: in-memory data has no physical bucketing; pass through.
            return self._exec(plan.child, with_file_names)

        raise NotImplementedError(f"Cannot execute {type(plan).__name__}")

    def _exec_scan(
        self,
        plan: L.Scan,
        with_file_names: bool,
        files: Optional[List[str]] = None,
        columns: Optional[List[str]] = None,
        predicate=None,
    ) -> B.Batch:
        rel = plan.relation
        if files is None:
            files = [fi.name for fi in rel.all_file_infos()]
        if not files:
            # empty after pruning: typed empty columns from the schema
            from hyperspace_tpu.sources import schema as schema_codec

            batch: B.Batch = {
                f.name: np.empty(0, dtype=schema_codec.arrow_to_numpy_dtype(f.type))
                for f in rel.schema
                if columns is None or f.name in columns
            }
            if with_file_names:
                batch[INPUT_FILE_NAME] = np.empty(0, dtype=object)
            return batch
        part_cols = list(getattr(rel, "partition_columns", []) or [])
        pv = pd = None
        if part_cols:
            pv = {f: rel.partition_values_for(f) for f in files}
            pd_ = getattr(rel, "partition_dtypes", None)
            pd = dict(pd_) if pd_ else None
        return _read_files(
            files,
            rel.physical_format,
            columns,
            with_file_names,
            pv,
            pd,
            format_options=getattr(rel, "options", None) or None,
            predicate=predicate,
        )

    @staticmethod
    def _lineage_not_in(condition) -> Optional[Tuple[str, list]]:
        """Match the hybrid-scan delete filter ``NOT (col IN int-literals)``
        (rules/utils._hybrid_scan_plan); returns (column, ids) or None."""
        from hyperspace_tpu.plan.expr import Col, In, Lit, Not

        if not (isinstance(condition, Not) and isinstance(condition.child, In)):
            return None
        inner = condition.child
        if not isinstance(inner.child, Col):
            return None
        ids = []
        for lit in inner.values:
            if not (isinstance(lit, Lit) and isinstance(lit.value, (int, np.integer))):
                return None
            ids.append(int(lit.value))
        return inner.child.name, ids

    def _filter_mask(self, plan: L.Filter, child: B.Batch, kept=None) -> np.ndarray:
        """Predicate evaluation: device path over index/file scans when the
        session mesh is available, host numpy otherwise. ``kept`` is the kept
        signature of the read that produced ``child`` (``_kept_groups``)."""
        if (
            self.session.conf.device_execution_enabled
            and isinstance(plan.child, (L.IndexScan, L.FileScan))
            and not _bucket_pruned(plan.child)
        ):
            # hybrid-scan lineage delete filter: fused device anti-semi-join
            # instead of the general predicate path (which has no IN support)
            # or the host NumPy set-op
            lineage = self._lineage_not_in(plan.condition)
            if lineage is not None and self.session.conf.lifecycle_device_lineage_enabled:
                if B.num_rows(child) >= self.session.conf.lifecycle_device_lineage_min_rows:
                    from hyperspace_tpu.exec import device as D
                    from hyperspace_tpu.exec.lineage import lineage_delete_mask

                    col, ids = lineage
                    px = _maybe_parallel(self.session, B.num_rows(child))
                    try:
                        mask = lineage_delete_mask(
                            self.session,
                            child,
                            col,
                            ids,
                            scan_key=_pruned_scan_key(scan_identity(plan.child), kept),
                            parallel=px,
                        )
                        trace.record("filter", "device-lineage")
                        return mask
                    except D.DeviceUnsupported:
                        trace.record("filter", "host-fallback")
                        trace.fallback("lineage", "unsupported")
                        return as_bool_mask(plan.condition.eval(child))
                trace.fallback("lineage", "min-rows")
                trace.record("filter", "host")
                return as_bool_mask(plan.condition.eval(child))
            if B.num_rows(child) >= self.session.conf.device_exec_min_rows:
                from hyperspace_tpu.exec import device as D

                px = _maybe_parallel(self.session, B.num_rows(child))
                try:
                    mask = D.device_filter_mask(
                        self.session,
                        child,
                        plan.condition,
                        scan_key=_pruned_scan_key(scan_identity(plan.child), kept),
                        parallel=px,
                    )
                    trace.record("filter", "device-sharded" if px is not None else "device")
                    return mask
                except D.DeviceUnsupported:
                    trace.record("filter", "host-fallback")
                    trace.fallback("filter", "unsupported")
                    return as_bool_mask(plan.condition.eval(child))
            trace.fallback("filter", "min-rows")
        trace.record("filter", "host")
        return as_bool_mask(plan.condition.eval(child))

    def _exec_aggregate(self, plan: L.Aggregate, with_file_names: bool) -> B.Batch:
        """A grouped aggregate over one scan is evaluated once a request: a
        CTE that the plan reads twice (TPC-H Q15's ``revenue0``: a join side
        and ``select max(total_revenue)``) is inlined as two equal sub-plans,
        run by two executors of the same request, and an equality between
        their float sums holds only if both are the same evaluation. The
        request's memo (``plan.expr.request_memo``) keeps the group table
        under the sub-plan's fingerprint, literals and index version in."""
        key = None if with_file_names else _request_aggregate_key(plan)
        memo = request_memo() if key is not None else None
        if memo is not None and key in memo:
            trace.record("agg", "request-memo")
            return dict(memo[key])
        got = self._aggregate_tiers(plan, with_file_names)
        if memo is not None:
            memo[key] = got
            return dict(got)  # a caller may add columns to its copy
        return got

    def _aggregate_tiers(self, plan: L.Aggregate, with_file_names: bool) -> B.Batch:
        # fused device path for global aggregates over an (optionally
        # filtered) index/file scan: predicate + reductions run in one jitted
        # program over HBM-resident columns; only scalars transfer back
        child = None
        if not with_file_names and self.session.conf.device_execution_enabled:
            # an aggregate over a join of two index scans, from the columns of
            # both resident on the device: tried before the host bucket walk,
            # decided before any file is opened (and so before the join's own
            # stream gate is reached)
            got = self._try_resident_join_aggregate(plan)
            if got is not None:
                trace.record("agg", "device-join-scan")
                return got
            # fused aggregate over a bucketed join: spans give each pair's
            # multiplicity, so no join output is ever materialized (global
            # aggregates, or grouped by the join keys)
            join_node = plan.child
            computes = []
            while isinstance(join_node, (L.Project, L.Compute)):
                if isinstance(join_node, L.Compute):
                    # computed aggregate inputs / group keys (q3's
                    # sum(l_extendedprice * (1 - l_discount))): single-side
                    # expressions evaluate per bucket inside
                    # aggregate_over_bucketed_join
                    computes.extend(join_node.exprs)
                join_node = join_node.child
            if isinstance(join_node, L.Join):
                from hyperspace_tpu.exec import device as D

                with spans.span("agg-fused-bucketed-join", cat="exec") as tier:
                    try:
                        got = D.aggregate_over_bucketed_join(
                            self.session, plan, join_node, computes=computes
                        )
                        trace.record("agg", "fused-bucketed-join")
                        return got
                    except D.DeviceUnsupported:
                        trace.fallback("agg", "join-unsupported")
                        tier.set(fallback="join-unsupported")
        # the tiers of an aggregate over a scan chain, chosen from what the
        # code observes: the device tier where the scan's columns are
        # resident or can be (it reads the scan once an index version, and a
        # resident hit opens no file); the out-of-core stream where the scan
        # is stream-sized and its columns cannot stay on the device; the
        # device tier over a materialized batch for every smaller scan
        if not with_file_names:
            # walks the scan's file sizes: asked at most once, and not at all
            # by an aggregate whose columns are resident
            stream_plan = functools.cache(lambda: self._streaming_plan(plan))
            if self.session.conf.device_execution_enabled:
                got, scan_batch, filter_node, kept = self._try_device_aggregate(plan, stream_plan)
                if got is not None:
                    trace.record(
                        "agg", "device-grouped-scan" if plan.keys else "device-fused-scan"
                    )
                    return got
                if scan_batch is not None:
                    # the device gate already materialized the scan — reuse it
                    # instead of re-reading parquet on the host fallback
                    child = self._exec_chain_over(plan.child, scan_batch, filter_node, kept)
            stream = stream_plan() if child is None else None
            if stream is not None:
                got = self._try_streaming_aggregate(plan, stream)
                if got is not None:
                    trace.record("agg", "streamed-partial")
                    return got

        if child is None:
            child = self._exec(plan.child, with_file_names)
        with spans.span("agg-host", cat="exec"):
            trace.agg_rows("host", B.num_rows(child))
            return host_aggregate(child, list(plan.keys), list(plan.aggs))

    def _exec_chain_over(self, chain_root, scan_batch: B.Batch, filter_node, kept) -> B.Batch:
        """The rows ``chain_root`` (an aggregate's child: Projects and at most
        one Compute over an optional Filter over the scan) gives over the
        scan's already-read ``scan_batch``."""
        if filter_node is not None:
            with spans.span("filter-mask", cat="exec"):
                mask = self._filter_mask(filter_node, scan_batch, kept=kept)
            with spans.span("filter-apply", cat="exec"):
                scan_batch = B.mask_rows(scan_batch, mask)
        below = filter_node if filter_node is not None else _chain_to_scan(chain_root)[1]
        if chain_root is below:
            return scan_batch
        prev = getattr(self, "_leaf_override", None)
        self._leaf_override = (below, scan_batch)
        try:
            return self._exec(chain_root, False)
        finally:
            self._leaf_override = prev

    # -- streamed Limit shapes (execute_stream) -------------------------------

    def _stream_limit_node(self, plan: L.Limit):
        """Streamed execution of a root Limit: ORDER BY...LIMIT dispatches to
        the sorted-run merge / device top-k (one result batch), a bare Limit
        early-terminates the scan pipeline. Returns a generator, or None to
        fall back to the materialized path."""
        if isinstance(plan.child, L.Sort):
            got = self._try_sorted_run_merge(plan.child, limit=plan.n)
            if got is None:
                got = self._try_streaming_topk(plan.child, plan.n)
            if got is None:
                return None

            def one():
                yield got

            return one()
        return self._stream_limit(plan)

    def _stream_limit(self, plan: L.Limit):
        """Early-terminating bare Limit: stop pulling source chunks once n
        rows are collected. Closing the chunk generator propagates into
        ScanPipeline.close(), which cancels every queued decode (the
        mid-stream-close discipline of the streaming joins)."""
        if plan.n <= 0:
            return None
        conf = self.session.conf
        chain, leaf = _chain_to_scan(plan.child)
        if leaf is None:
            return None
        files = leaf_files(leaf)
        groups = _chunk_files_by_bytes(leaf, files, max(1, conf.stream_chunk_bytes))
        if len(groups) < 2:
            return None
        needed = _chain_needed_columns(chain) | set(plan.output_columns)

        def gen():
            remaining = int(plan.n)
            chunks = self._stream_chunks(chain, leaf, groups, needed)
            try:
                for batch in chunks:
                    batch = {c: v for c, v in batch.items() if c != INPUT_FILE_NAME}
                    rows = B.num_rows(batch)
                    if rows >= remaining:
                        trace.record("limit", "early-stop-stream")
                        yield {c: np.asarray(v)[:remaining] for c, v in batch.items()}
                        return
                    if rows:
                        remaining -= rows
                        yield batch
            finally:
                # deterministic cancel of queued decodes, even when our own
                # consumer abandons mid-iteration
                chunks.close()

        return gen()

    # -- streaming device top-k (ORDER BY ... LIMIT k) ------------------------

    def _try_streaming_topk(self, sort_plan: L.Sort, k: int) -> Optional[B.Batch]:
        """ORDER BY ... LIMIT k over a multi-chunk scan chain as a streaming
        device top-k fold (exec/topk.TopKStream): no full materialization,
        one compile per (key count, capacity, shape bucket), byte-identical
        to host-sort-then-slice. Returns None (caller materializes) when the
        shape or configuration doesn't stream."""
        conf = self.session.conf
        if not (conf.topk_enabled and conf.device_execution_enabled):
            return None
        if not sort_plan.keys or k <= 0 or k > conf.topk_max_k:
            return None
        chain, leaf = _chain_to_scan(sort_plan.child)
        if leaf is None:
            return None
        files = leaf_files(leaf)
        if len(files) < 2:
            return None
        groups = _chunk_files_by_bytes(leaf, files, max(1, conf.stream_chunk_bytes))
        if len(groups) < 2:
            return None
        try:
            return self._streaming_topk(sort_plan, k, chain, leaf, groups)
        except _STREAM_FALLBACK_ERRORS:
            trace.record("topk", "stream-fallback")
            return None

    def _streaming_topk(self, sort_plan, k, chain, leaf, groups) -> Optional[B.Batch]:
        from hyperspace_tpu.exec import device as D
        from hyperspace_tpu.exec.topk import TopKStream

        conf = self.session.conf
        needed = _chain_needed_columns(chain) | set(sort_plan.output_columns)
        needed |= {c for c, _ in sort_plan.keys}
        stream = TopKStream(
            self.session, sort_plan.keys, k, parallel=_maybe_parallel(self.session)
        )
        # the running k-th-value threshold prunes row groups of chunks not
        # yet decoded; only sound when pruning is on and the chain cannot
        # rebind the primary key column
        dynamic = None
        if (
            conf.topk_threshold_pushdown
            and conf.rowgroup_pruning_enabled
            and all(isinstance(nd, (L.Filter, L.Project)) for nd in chain)
        ):
            dynamic = stream.threshold_condition
        host_parts: Optional[List[B.Batch]] = None
        host_rid = 0
        for batch in self._stream_chunks(
            chain, leaf, groups, needed, dynamic_pushdown=dynamic
        ):
            batch = {c: v for c, v in batch.items() if c != INPUT_FILE_NAME}
            if host_parts is None:
                try:
                    stream.update(batch)
                    continue
                except D.DeviceUnsupported as e:
                    # mid-stream fallback: the pool is a superset of the
                    # top-k of every folded row, so (pool + this and later
                    # chunks) re-sorted on host stays byte-identical
                    trace.fallback("topk", str(e) or type(e).__name__)
                    host_parts = (
                        [stream.pool_rows_with_rid(_TOPK_RID)]
                        if stream.has_data
                        else []
                    )
                    host_rid = stream.rows_seen
            n = B.num_rows(batch)
            part = dict(batch)
            part[_TOPK_RID] = host_rid + np.arange(n, dtype=np.int64)
            host_rid += n
            host_parts.append(part)
        if host_parts is None:
            if not stream.has_data:
                return None  # every chunk came back empty — materialize
            trace.record(
                "topk",
                "device-topk-stream-sharded"
                if stream.parallel is not None
                else "device-topk-stream",
            )
            return stream.finalize()
        parts = [p for p in host_parts if B.num_rows(p)]
        if not parts:
            return None
        from hyperspace_tpu.plan.expr import get_column

        merged = B.concat(parts)
        # stable composite sort with the global row id as the base order —
        # exactly the host Sort's tie semantics
        order = np.argsort(np.asarray(merged[_TOPK_RID]), kind="stable")
        for name, asc in reversed(sort_plan.keys):
            arr = get_column(merged, name)
            if arr is None:
                raise KeyError(f"Sort key {name!r} not found")
            codes = _key_codes(np.asarray(arr)[order], asc)
            order = order[np.argsort(codes, kind="stable")]
        take = order[:k]
        trace.record("topk", "host-candidate-fallback")
        return {c: np.asarray(v)[take] for c, v in merged.items() if c != _TOPK_RID}

    # -- sort elimination: streamed merge of sorted index runs ----------------

    def _try_sorted_run_merge(self, sort_plan: L.Sort, limit=None) -> Optional[B.Batch]:
        """Replace a Sort whose order the covering index already provides
        (within-bucket sort order, plan/ordering.sort_run_eligibility) with a
        k-way merge of per-file runs. Why-not reasons land in dispatch traces
        and the QueryProfile report when the rewrite cannot fire."""
        from hyperspace_tpu.plan import ordering as ORD

        leaf, chain, reason = ORD.sort_run_eligibility(sort_plan)
        if leaf is None:
            # record once per query: the bare-Sort call (the Limit wrapper
            # retries through the Sort branch anyway)
            if reason is not None and limit is None:
                trace.record("sort", f"merge-why-not: {reason}")
            return None
        try:
            return self._merge_sorted_runs(sort_plan, chain, leaf, limit)
        except Exception:
            trace.record("sort", "merge-fallback")
            return None

    def _merge_sorted_runs(self, sort_plan, chain, leaf, limit) -> Optional[B.Batch]:
        import heapq

        from hyperspace_tpu.plan.expr import get_column

        files = leaf_files(leaf)
        if len(files) < 2:
            return None  # a single run needs no merge; host path is fine
        needed = _chain_needed_columns(chain) | set(sort_plan.output_columns)
        needed |= {c for c, _ in sort_plan.keys}
        runs = []
        for f in files:
            sub = _rebuild_chain(chain, _leaf_subset(leaf, [f], needed))
            runs.append(self._exec(sub, False))
        lens = [B.num_rows(r) for r in runs]
        total = B.concat(runs)
        n = B.num_rows(total)
        bounds = np.cumsum([0] + lens)
        # rank codes over the concatenation: one consistent code space for
        # all runs, same NULLS LAST / DESC semantics as the host Sort
        codes = []
        for name, asc in sort_plan.keys:
            arr = get_column(total, name)
            if arr is None:
                raise KeyError(f"Sort key {name!r} not found")
            codes.append(_key_codes(np.asarray(arr), asc))

        def run_monotone(s: int, e: int) -> bool:
            if e - s < 2:
                return True
            lt = np.zeros(e - s - 1, dtype=bool)
            eq = np.ones(e - s - 1, dtype=bool)
            for c in codes:
                seg = c[s:e]
                lt |= eq & (seg[1:] < seg[:-1])
                eq &= seg[1:] == seg[:-1]
            return not lt.any()

        run_orders = []
        repaired = 0
        for i in range(len(runs)):
            s, e = int(bounds[i]), int(bounds[i + 1])
            if run_monotone(s, e):
                run_orders.append(np.arange(s, e, dtype=np.int64))
            else:
                # physical order disagrees with the requested order (NULL
                # placement, float total-order rotation, stale layout):
                # stable-repair the run; the merge stays byte-identical
                repaired += 1
                sl = np.lexsort(tuple(c[s:e] for c in reversed(codes)))
                run_orders.append(s + sl.astype(np.int64))
        # k-way heap merge; ties across runs resolve by global position ==
        # the host stable sort's tie order (within a run the repair is
        # stable, so sequential emission preserves it too)
        take_n = n if limit is None else min(int(limit), n)
        heap = []
        for ro in run_orders:
            if ro.size:
                i0 = int(ro[0])
                heapq.heappush(heap, (tuple(c[i0] for c in codes), i0, ro, 1))
        out_idx = np.empty(take_n, dtype=np.int64)
        taken = 0
        while heap and taken < take_n:
            _, idx, ro, nxt = heapq.heappop(heap)
            out_idx[taken] = idx
            taken += 1
            if nxt < ro.size:
                i0 = int(ro[nxt])
                heapq.heappush(heap, (tuple(c[i0] for c in codes), i0, ro, nxt + 1))
        trace.record(
            "sort",
            "index-order-merge"
            + ("-limit" if limit is not None else "")
            + (f"-repaired:{repaired}" if repaired else ""),
        )
        return {c: np.asarray(v)[out_idx] for c, v in total.items()}

    def _streaming_plan(self, plan: L.Aggregate):
        """``(chain, leaf, groups, needed)`` when ``plan`` can run as an
        out-of-core aggregate: its child is a scan chain over more source
        bytes than conf ``exec.stream.aggMinBytes`` and its aggregates have
        decomposable partial states; None when the shape, size, or aggregate
        set doesn't stream. Sizes come from the files' identities (an
        index's from its log entry): nothing is opened to decide."""
        conf = self.session.conf
        min_bytes = conf.stream_agg_min_bytes
        if not min_bytes or min_bytes <= 0:
            return None
        if any(fn not in _STREAMABLE_AGGS for _, fn, _ in plan.aggs):
            return None
        chain, leaf = _chain_to_scan(plan.child)
        if leaf is None:
            return None
        files = leaf_files(leaf)
        if len(files) < 2:
            return None
        keys = file_identities(files, committed_keys(leaf))
        if None in keys or sum(k[1] for k in keys) < min_bytes:
            return None
        groups = _chunk_files_by_bytes(leaf, files, max(1, conf.stream_chunk_bytes), keys)
        if len(groups) < 2:
            return None
        return chain, leaf, groups, _chain_needed_columns(chain, plan.aggs, plan.keys)

    def _try_streaming_aggregate(self, plan: L.Aggregate, stream) -> Optional[B.Batch]:
        """Out-of-core aggregate over ``stream`` (``_streaming_plan``):
        execute the scan chain in file chunks and merge decomposable partial
        states — Spark's partial/final aggregation split, which is what lets
        the reference aggregate over tables far larger than executor memory.
        Returns None (caller materializes) when a chunk falls back."""
        chain, leaf, groups, needed = stream
        with spans.span("agg-streamed-partial", cat="exec") as tier:
            try:
                return self._streaming_aggregate(plan, chain, leaf, groups, needed)
            except _STREAM_FALLBACK_ERRORS:
                trace.record("agg", "stream-fallback")
                tier.set(fallback="stream-fallback")
                return None

    def _streaming_aggregate(self, plan, chain, leaf, groups, needed) -> B.Batch:
        import pandas as pd

        grouped = bool(plan.keys)
        # distinct-form aggregates accumulate (group keys +) unique values;
        # everything else carries closed-form partial states
        plain = [(i, n, fn, c) for i, (n, fn, c) in enumerate(plan.aggs)
                 if not fn.endswith("_distinct")]
        distinct = [(i, n, fn, c) for i, (n, fn, c) in enumerate(plan.aggs)
                    if fn.endswith("_distinct")]

        partial_frames: List = []          # grouped plain partials
        distinct_frames = {i: [] for i, *_ in distinct}  # per-agg pair frames
        g_state: Dict[int, Any] = {}       # global plain partials

        def fold_chunk(batch):
            with spans.span("agg-host-combine", cat="exec"):
                trace.agg_rows("host", B.num_rows(batch))
                host_fold(batch)

        def host_fold(batch):
            batch = {k: v for k, v in batch.items() if k != INPUT_FILE_NAME}
            n = B.num_rows(batch)

            def series(col):
                from hyperspace_tpu.plan.expr import get_column

                got = batch.get(col)
                if got is None:
                    got = get_column(batch, col)
                if got is None:
                    raise KeyError(f"Aggregate input column {col!r} not found")
                return got

            if grouped:
                frame_cols = {k: series(k) for k in plan.keys}
                for _i, _n, _fn, c in plain:
                    if c is not None and c not in frame_cols:
                        frame_cols[c] = series(c)
                df = pd.DataFrame(frame_cols)
                gb = df.groupby(list(plan.keys), dropna=False, sort=False)
                pieces = {}
                for i, name, fn, c in plain:
                    p = f"__p{i}"
                    if fn == "count":
                        pieces[p] = gb.size() if c is None else gb[c].count()
                    elif fn == "sum":
                        pieces[p] = gb[c].sum(min_count=1)
                    elif fn == "min":
                        pieces[p] = gb[c].min()
                    elif fn == "max":
                        pieces[p] = gb[c].max()
                    elif fn == "avg":
                        pieces[p + "_s"] = gb[c].sum(min_count=1)
                        pieces[p + "_c"] = gb[c].count()
                    elif fn == "stddev_samp":
                        pieces[p + "_n"] = gb[c].count()
                        pieces[p + "_s"] = gb[c].sum(min_count=1)
                        # float64 BEFORE squaring: int64 values near 2^32
                        # would wrap the sum-of-squares negative
                        pieces[p + "_ss"] = gb[c].apply(
                            lambda s: float((s.dropna().astype(np.float64) ** 2).sum())
                        )
                if pieces:
                    partial_frames.append(pd.DataFrame(pieces).reset_index())
                elif distinct:
                    # keys-only partial so groups with only-distinct aggs
                    # still materialize every group
                    partial_frames.append(
                        pd.DataFrame({k: frame_cols[k] for k in plan.keys})
                        .drop_duplicates()
                    )
                for i, name, fn, c in distinct:
                    pair = pd.DataFrame(
                        {**{k: series(k) for k in plan.keys}, "__v": series(c)}
                    ).drop_duplicates()
                    distinct_frames[i].append(pair)
            else:
                for i, name, fn, c in plain:
                    s = pd.Series(series(c)) if c is not None else None
                    st = g_state.get(i)
                    if fn == "count":
                        v = n if c is None else int(s.count())
                        g_state[i] = (st or 0) + v
                    elif fn in ("sum", "min", "max"):
                        part = getattr(s, {"sum": "sum", "min": "min", "max": "max"}[fn])(
                            **({"min_count": 1} if fn == "sum" else {})
                        )
                        g_state.setdefault(i, []).append(part)
                    elif fn == "avg":
                        sc = g_state.setdefault(i, [0.0, 0])
                        cnt = int(s.count())
                        if cnt:
                            sc[0] += float(s.sum())
                            sc[1] += cnt
                    elif fn == "stddev_samp":
                        sc = g_state.setdefault(i, [0, 0.0, 0.0])
                        d = s.dropna().astype(np.float64)
                        sc[0] += int(d.shape[0])
                        sc[1] += float(d.sum())
                        sc[2] += float((d**2).sum())
                for i, name, fn, c in distinct:
                    u = pd.Series(series(c)).dropna().drop_duplicates()
                    distinct_frames[i].append(u.to_frame("__v"))

        # device grouped streaming: fuse the chain's predicate into the
        # grouped segment-reduction program over each RAW leaf chunk and keep
        # the running partial-aggregate table on device, merged chunk-to-chunk
        # — the scan never materializes on host. Any mid-stream fallback
        # (cardinality spill, dtype drift) converts the device partial into
        # ONE host partial frame and continues with the pandas fold below.
        conf = self.session.conf
        stream = None
        fuse_cond = None
        stage_extra = None
        if (
            grouped
            and not distinct
            and conf.device_execution_enabled
            and conf.agg_device_grouped_enabled
            # the per-chunk leaf CLONES are always FileScan/IndexScan
            # (_leaf_subset converts a relation Scan), so any chain of
            # Filters/Projects fuses; Compute/Rename rebind the namespace
            # the fused predicate and keys are expressed in
            and all(isinstance(nd, (L.Filter, L.Project)) for nd in chain)
        ):
            from hyperspace_tpu.exec import device as D

            fuse_cond = _chain_pushdown_condition(chain)
            stage_extra = sorted(
                set(plan.keys) | {c for _, _, _, c in plain if c is not None}
            )
            stream = D.GroupedAggStream(
                self.session,
                list(plan.keys),
                list(plan.aggs),
                max_groups=conf.agg_max_groups,
                cap_floor=conf.agg_capacity_floor,
                # capacity hint shared across repeated runs of the same
                # query shape over the same file set (skips the first
                # chunk's right-sizing re-run once cardinality is known)
                hint_key=("stream",) + tuple(leaf_files(leaf)),
                # per-stream mode decision (chunk sizes aren't known yet):
                # minRows gates the one-shot ops, not stream chunks
                parallel=_maybe_parallel(self.session),
            )

        # chunks arrive through the prefetch pipeline: chunk k+1 decodes (and
        # stages) while this loop folds chunk k's partials
        if stream is None:
            for batch in self._stream_chunks(chain, leaf, groups, needed):
                fold_chunk(batch)
        else:
            device_ok = True
            for lf, sub, leaf_batch in self._stream_chunks(
                chain, leaf, groups, needed, leaf_only=True, stage_extra=stage_extra
            ):
                if device_ok:
                    nb = B.num_rows(leaf_batch)
                    if nb and nb < conf.device_exec_min_rows:
                        trace.fallback("agg", "min-rows")
                        device_ok = False
                    else:
                        key = _pruned_scan_key(scan_identity(lf), _kept_groups(lf))
                        try:
                            stream.update(leaf_batch, fuse_cond, scan_key=key)
                            continue
                        except D.GroupCapacityExceeded as e:
                            trace.fallback("agg", "spill")
                            device_ok = False
                            if stream.has_data:
                                partial_frames.append(stream.to_partial_frame(plain))
                            if getattr(e, "folded", False):
                                continue  # chunk already merged into the partial
                        except D.DeviceUnsupported:
                            trace.fallback("agg", "unsupported")
                            device_ok = False
                            if stream.has_data:
                                partial_frames.append(stream.to_partial_frame(plain))
                # host fold of this (and every later) chunk, executing the
                # chain over the SAME prefetched leaf batch
                prev = getattr(self, "_leaf_override", None)
                self._leaf_override = (lf, leaf_batch)
                try:
                    fold_chunk(self._exec(sub, False))
                finally:
                    self._leaf_override = prev
            if device_ok and stream.has_data:
                trace.record(
                    "agg",
                    "device-grouped-stream-sharded"
                    if getattr(stream, "_parallel", None) is not None
                    else "device-grouped-stream",
                )
                return stream.finalize()

        if grouped:
            merged = pd.concat(partial_frames, ignore_index=True)
            gb = merged.groupby(list(plan.keys), dropna=False, sort=False)
            final = {}
            for i, name, fn, c in plain:
                p = f"__p{i}"
                if fn == "count":
                    final[name] = gb[p].sum().astype(np.int64)
                elif fn == "sum":
                    final[name] = gb[p].sum(min_count=1)
                elif fn == "min":
                    final[name] = gb[p].min()
                elif fn == "max":
                    final[name] = gb[p].max()
                elif fn == "avg":
                    s_, c_ = gb[p + "_s"].sum(min_count=1), gb[p + "_c"].sum()
                    final[name] = s_ / c_.where(c_ > 0)
                elif fn == "stddev_samp":
                    n_ = gb[p + "_n"].sum()
                    s_ = gb[p + "_s"].sum(min_count=1)
                    ss_ = gb[p + "_ss"].sum()
                    var = (ss_ - (s_**2) / n_.where(n_ > 0)) / (n_ - 1).where(n_ > 1)
                    final[name] = np.sqrt(var.clip(lower=0))
            result = pd.DataFrame(final).reset_index() if final else (
                merged[list(plan.keys)].drop_duplicates().reset_index(drop=True)
            )
            for i, name, fn, c in distinct:
                pairs = pd.concat(distinct_frames[i], ignore_index=True).drop_duplicates()
                pairs = pairs[pairs["__v"].notna()]
                pgb = pairs.groupby(list(plan.keys), dropna=False, sort=False)["__v"]
                if fn == "count_distinct":
                    dser = pgb.nunique(dropna=True)
                elif fn == "sum_distinct":
                    dser = pgb.sum(min_count=1)
                else:  # avg_distinct
                    dser = pgb.mean()
                dser.name = name
                result = result.merge(dser.reset_index(), on=list(plan.keys), how="left")
                if fn == "count_distinct":
                    result[name] = result[name].fillna(0).astype(np.int64)
            out: B.Batch = {}
            for k in plan.keys:
                out[k] = result[k].to_numpy()
            for name, _, _ in plan.aggs:
                out[name] = result[name].to_numpy()
            return out

        out = {}
        for i, name, fn, c in plain:
            st = g_state.get(i)
            if fn == "count":
                out[name] = np.asarray([st or 0])
            elif fn in ("sum", "min", "max"):
                s = pd.Series(st or [])
                v = getattr(s, {"sum": "sum", "min": "min", "max": "max"}[fn])(
                    **({"min_count": 1} if fn == "sum" else {})
                )
                out[name] = np.asarray([v])
            elif fn == "avg":
                s_, c_ = st or (0.0, 0)
                out[name] = np.asarray([s_ / c_ if c_ else np.nan])
            elif fn == "stddev_samp":
                n_, s_, ss_ = st or (0, 0.0, 0.0)
                if n_ > 1:
                    var = max(0.0, (ss_ - s_ * s_ / n_) / (n_ - 1))
                    out[name] = np.asarray([np.sqrt(var)])
                else:
                    out[name] = np.asarray([np.nan])
        for i, name, fn, c in distinct:
            u = pd.concat(distinct_frames[i], ignore_index=True)["__v"].drop_duplicates()
            u = u[u.notna()]
            if fn == "count_distinct":
                out[name] = np.asarray([int(u.shape[0])])
            elif fn == "sum_distinct":
                out[name] = np.asarray([u.sum(min_count=1) if len(u) else np.nan])
            else:
                out[name] = np.asarray([u.mean() if len(u) else np.nan])
        return {name: out[name] for name, _, _ in plan.aggs}

    def _try_device_aggregate(self, plan: L.Aggregate, stream_plan):
        """The device tier of an aggregate over a scan: ``Aggregate`` over
        Projects, at most one ``Compute`` (computed aggregate inputs) and a
        ``Filter`` below it, over an index or file scan. Returns (result,
        scan_batch, filter_node, kept): result=None means the caller runs
        another tier — reusing scan_batch (the materialized scan, pre-filter)
        when this one read it. ``kept`` is the kept signature of that read
        (``_kept_groups``); the caller must thread it into any further
        device-cache use of scan_batch, or a pruned batch gets branded with
        an unpruned key.

        The scan's columns are looked up on the device first, by the scan's
        identity (its files' identities, ``exec/file_identity.py``: a refresh
        or optimize is another identity): when all are resident the program
        runs over them and no file is opened. Otherwise the scan is read
        whole, once an index version, and its columns uploaded to stay.
        Decided before anything is read, from the files' footers: too few
        rows for the device (``deviceMinRows``), or columns that outweigh the
        device cache's budget (``deviceCacheBytes``; fallback ``over-cap``).
        Then the caller streams, or folds on the host."""
        conf = self.session.conf
        none = (None, None, None, None)
        shape = _scan_aggregate_shape(plan)
        if shape is None:
            return none
        node, filter_node, computes = shape
        if plan.keys and not conf.agg_device_grouped_enabled:
            return none
        from hyperspace_tpu.exec import device as D

        if _bucket_pruned(node, count=filter_node is not None):
            return None, self._exec(node, with_file_names=False), filter_node, _kept_groups(node)
        condition = filter_node.condition if filter_node is not None else None
        computed = {name for name, _ in computes}
        needed = sorted(
            (set(condition.references()) if condition is not None else set())
            | {r for _, e in computes for r in e.references()}
            | {c for _, _, c in plan.aggs if c is not None and c not in computed}
            | set(plan.keys)
        )
        cols = D.ScanColumns(
            self.session, scan_identity(node), needed, lambda: self._exec(node, with_file_names=False)
        )
        min_rows = conf.device_exec_min_rows
        rows = None  # of a scan that is not resident: known before it is read
        if not cols.resident:
            rows = _footer_rows(node, cols.scan_key)
            if rows is None:  # no footer says
                if stream_plan() is not None:
                    return none  # a stream-sized scan is not read to learn its rows
                rows = cols.rows
            if rows < min_rows:
                trace.fallback("agg", "min-rows")
                return None, cols.loaded, filter_node, _kept_groups(node)
        name = "agg-device-grouped-scan" if plan.keys else "agg-device-fused-scan"
        with spans.span(name, cat="exec", resident="hit" if cols.resident else "miss") as tier:
            got = None
            try:
                if rows is not None:
                    D.check_fits_device_cache(rows, len(needed))
                got = self._device_aggregate(plan, cols, condition, computes)
                tier.set(rows=cols.rows)
            except D.ResidentOverCap:
                trace.fallback("agg", "over-cap")
                tier.set(fallback="over-cap")
            except D.GroupCapacityExceeded:
                trace.fallback("agg", "spill")
                tier.set(fallback="spill")
            except D.DeviceUnsupported:
                trace.fallback("agg", "unsupported")
                tier.set(fallback="unsupported")
            # not answered here: the caller reuses the scan if this tier read it
            return got, cols.loaded, filter_node, _kept_groups(node)

    def _try_resident_join_aggregate(self, plan: L.Aggregate) -> Optional[B.Batch]:
        """The resident join-aggregate tier (``exec/join_agg.py``): an
        ``Aggregate`` over an inner equi-``Join`` of two index-scan chains,
        answered by one device program over the columns of both scans and the
        build side's table, all resident. None means the caller runs the
        tiers behind it (the host bucket walk, the join tiers, ``agg-host``).

        Chosen from what the code observes, before any file is read: the
        scans' identities (their log entries': a refresh or optimize is
        another identity), their rows (the resident columns' own, else the
        files' footers), ``deviceMinRows`` for both together, and whether
        what is not resident yet of the two scans' needed columns and the
        build side's table fits the device cache's budget
        (``deviceCacheBytes``; fallback ``over-cap``). The probe side is the
        scan with more rows. Another shape is not this tier's and is not
        counted; every refusal past that is a
        ``hs_device_fallback_total{op=agg}``."""
        from hyperspace_tpu.exec import device as D
        from hyperspace_tpu.exec import join_agg as JA

        conf = self.session.conf
        shape = JA.join_aggregate_shape(plan)
        if shape is None or (plan.keys and not conf.agg_device_grouped_enabled):
            return None
        sides = []
        for side in (shape.left, shape.right):
            identity = scan_identity(side.scan)
            if identity is None:
                return None
            cols = D.ScanColumns(
                self.session, identity, JA.side_columns(side, shape.reads),
                functools.partial(self._exec, side.scan, with_file_names=False),
            )
            rows = cols.rows if cols.resident else _footer_rows(side.scan, identity)
            if rows is None:
                return None
            sides.append((rows, side, cols))
        sides.sort(key=lambda s: s[0], reverse=True)  # stable: the left side probes a tie
        (rows_p, probe, cols_p), (rows_b, build, cols_b) = sides
        if rows_p + rows_b < conf.device_exec_min_rows:
            trace.fallback("agg", "min-rows")
            return None
        table_resident = JA.resident_table(cols_b, build.key) is not None
        resident = cols_p.resident and cols_b.resident and table_resident
        with spans.span("agg-device-join-scan", cat="exec", resident="hit" if resident else "miss") as tier:
            try:
                if conf.parallel_enabled:
                    raise D.DeviceUnsupported("no sharded form of the resident join-aggregate")
                if not resident:
                    JA.check_fits([
                        (rows_p, cols_p.missing, 0),
                        (rows_b, cols_b.missing, 0 if table_resident else JA.table_budget_bytes(rows_b)),
                    ])
                got, found = JA.device_join_aggregate(
                    self.session, probe, build, cols_p, cols_b, shape.computes,
                    list(plan.keys), list(plan.aggs), max_groups=conf.agg_max_groups,
                )
                tier.set(**found)
                return got
            except D.ResidentOverCap:
                trace.fallback("agg", "over-cap")
                tier.set(fallback="over-cap")
            except D.DeviceUnsupported:
                trace.fallback("agg", "unsupported")
                tier.set(fallback="unsupported")
        return None

    def _device_aggregate(self, plan: L.Aggregate, cols, condition, computes) -> B.Batch:
        """One program over the scan's device columns: ``fused-agg``,
        ``grouped-agg-dense`` or ``grouped-agg-keyed``. What none of them
        takes (a float group key, keys spanning more than 32 bits, a third
        aggregate input) goes to the sort-based engine over the host batch,
        which has no computed inputs; so does a grouped aggregate on a mesh the session shards
        queries over (``hyperspace.parallel.enabled``)."""
        from hyperspace_tpu.exec import device as D

        conf = self.session.conf
        parallel = None
        if plan.keys and conf.parallel_enabled:
            parallel = _maybe_parallel(self.session, cols.rows)
        if parallel is None:
            try:
                return D.device_scan_aggregate(
                    self.session, cols, condition, computes, list(plan.keys), list(plan.aggs),
                    max_groups=conf.agg_max_groups, cap_floor=conf.agg_capacity_floor,
                )
            except D.GroupCapacityExceeded:
                raise  # more groups than maxGroups is the same count for the engine below
            except D.DeviceUnsupported:
                if not plan.keys or computes:
                    raise
        elif computes:
            raise D.DeviceUnsupported("computed inputs under a sharded grouped aggregate")
        return D.device_grouped_aggregate(
            self.session,
            cols.batch(),
            condition,
            list(plan.keys),
            list(plan.aggs),
            scan_key=cols.scan_key,
            max_groups=conf.agg_max_groups,
            cap_floor=conf.agg_capacity_floor,
            parallel=parallel,
        )

    def _exec_join(self, plan: L.Join, with_file_names: bool) -> B.Batch:
        """Join tiers in order: bucketed SMJ (device or host spans), broadcast
        hash stream, then the generic host merge. Each tier that runs is a
        child span named after its dispatch detail."""
        fallback = chosen = None
        if not with_file_names and self.session.conf.device_execution_enabled:
            # deviceExecution=False is the kill switch back to the pandas
            # merge below — it routes around the whole bucketed-SMJ stack
            from hyperspace_tpu.exec import device as D
            from hyperspace_tpu.exec import join_stream as JS

            try:
                return D.dispatch_bucketed_join(self.session, plan)
            except D.DeviceUnsupported:
                pass  # next tier: broadcast hash join
            spec = JS.broadcast_spec(self.session, plan)
            if spec is not None and JS.probes_group_table(plan, spec):
                chosen = "group-table"  # a decision, not a fallback: nothing is counted as one
            else:
                with spans.span("join-broadcast-hash-stream", cat="exec") as tier:
                    try:
                        return JS.dispatch_broadcast_join(self, plan, spec)
                    except D.DeviceUnsupported:
                        trace.fallback("join", "unsupported")
                        tier.set(fallback="unsupported")
                        fallback = "unsupported"
        # the host tier: its span's own time (children are the two sides'
        # scans) is the pandas merge and the payload gathers
        with spans.span("join-generic-merge", cat="exec") as tier:
            if fallback:
                tier.set(fallback=fallback)
            if chosen:
                tier.set(chosen=chosen)
            trace.record("join", "generic-merge")
            return self._generic_merge_join(plan, with_file_names)

    def _generic_merge_join(self, plan: L.Join, with_file_names: bool) -> B.Batch:
        """Generic (non-bucketed) equi-join via a pandas hash merge over slim
        key frames; see the slim-merge note below."""
        import pandas as pd

        pairs = extract_equi_join_keys(plan.condition)
        if pairs is None:
            raise NotImplementedError("Only conjunctive equi-joins are supported")
        left = self._exec(plan.left, with_file_names)
        right = self._exec(plan.right, with_file_names)
        left = {k: v for k, v in left.items() if k != INPUT_FILE_NAME}
        right = {k: v for k, v in right.items() if k != INPUT_FILE_NAME}

        def materialize_key(batch: B.Batch, name: str) -> bool:
            """Ensure ``name`` is a column of ``batch``; a dotted nested key
            is extracted from its root struct column on demand, and casing
            resolves like the analyzer's (Spark-default case-insensitive)."""
            if name in batch:
                return True
            from hyperspace_tpu.plan.expr import get_column

            got = get_column(batch, name)
            if got is not None:
                batch[name] = got
                return True
            lowered = {k.lower(): k for k in batch}
            actual = lowered.get(name.lower())
            if actual is not None:
                batch[name] = batch[actual]
                return True
            return False

        # validate key sides (columns may arrive swapped from the user)
        lkeys, rkeys = [], []
        for a, b in pairs:
            if materialize_key(left, a) and materialize_key(right, b):
                lkeys.append(a)
                rkeys.append(b)
            elif materialize_key(left, b) and materialize_key(right, a):
                lkeys.append(b)
                rkeys.append(a)
            else:
                raise ValueError(f"Join keys ({a}, {b}) not found in the two sides")
        left_cols = list(left)
        right_cols = list(right)

        # rename duplicated right-side columns up front so every output column
        # resolves to one unambiguous source; naming must match the plan's
        # (join_output_names). Only the KEY columns enter pandas: payload
        # columns would round-trip through pandas' (Arrow-backed) column
        # construction and back — measured at ~65% of TPC-H q7's join time
        # for string payloads — so the merge works on slim key+row-id frames
        # and every payload column is gathered from the original numpy
        # arrays by matched row id afterwards.
        _, rename = L.join_output_names(left_cols, right_cols)
        right_named = {rename.get(k, k): v for k, v in right.items()}
        rkeys_renamed = [rename.get(k, k) for k in rkeys]
        ldf = pd.DataFrame(
            {**{k: left[k] for k in lkeys}, "__lrow": np.arange(B.num_rows(left))}
        )
        rdf = pd.DataFrame(
            {
                **{k: right_named[k] for k in rkeys_renamed},
                "__rrow": np.arange(B.num_rows(right)),
            }
        )
        if plan.residual is None:
            spill = self.session.conf.join_spill_min_rows
            if spill and spill > 0 and max(len(ldf), len(rdf)) > spill:
                merged = self._partitioned_merge(
                    ldf, rdf, lkeys, rkeys_renamed, plan.how, spill
                )
            else:
                merged = ldf.merge(rdf, left_on=lkeys, right_on=rkeys_renamed, how=plan.how)
        else:
            merged = self._residual_join(
                plan, ldf, rdf, lkeys, rkeys_renamed, left, right_named
            )
        lspec = _gather_spec(merged["__lrow"].to_numpy())
        rspec = _gather_spec(merged["__rrow"].to_numpy())
        out: B.Batch = {}
        for name in plan.output_columns:
            if name in merged.columns:  # key columns, incl. renamed right keys
                out[name] = merged[name].to_numpy()
            elif name in left:
                out[name] = _gather_with_missing(left[name], lspec)
            elif name in right_named:
                out[name] = _gather_with_missing(right_named[name], rspec)
            else:
                raise KeyError(f"Join output column {name!r} missing")
        # USING-style joins coalesce the key across sides (Spark's
        # df.join(other, on="k") semantics): a right/outer join's unmatched
        # rows show the RIGHT side's key under the left name, not NULL
        if plan.how in ("right", "outer") and plan.using_pairs:
            for lk, rk in plan.using_pairs:
                rkr = rename.get(rk, rk)
                if lk in out and rkr in merged.columns:
                    lv = out[lk]
                    mask = pd.isna(lv)
                    if mask.any():
                        out[lk] = np.where(mask, merged[rkr].to_numpy(), lv)
        return out

    @staticmethod
    def _partitioned_merge(ldf, rdf, lkeys, rkeys, how: str, spill_rows: int):
        """Grace-style partitioned hash merge: both slim key frames split by
        a shared key hash and each partition merges independently, bounding
        the merge intermediate (hash table + indexers) to ~1/P of the
        unpartitioned spike. Correct for every join type because hash
        partitions are disjoint by key: each row joins (or null-extends)
        entirely within its partition — the same argument Spark's shuffled
        hash join rests on. Equal values hash equally across the two sides'
        dtypes (keys coerce to a common type before hashing), and NaN keys
        hash deterministically, so pandas' NaN-matches-NaN merge semantics
        are preserved partition-locally."""
        import pandas as pd

        from hyperspace_tpu.ops.encode import hash_input_uint32
        from hyperspace_tpu.ops.hashing import bucket_ids_np

        n_parts = max(2, -(-max(len(ldf), len(rdf)) // spill_rows))

        # partitioning is only sound when equal-under-pandas keys hash
        # equally on both sides: coerce numeric pairs to a common dtype and
        # normalize -0.0 to +0.0 (pandas merges them equal; their IEEE bit
        # patterns hash apart); any key pair outside that guarantee (object
        # vs numeric, mismatched datetime units) falls back to the single
        # merge rather than silently dropping matches
        def keyed(df, keys, other_df, other_keys):
            planes = []
            for k, ok in zip(keys, other_keys):
                a = df[k].to_numpy()
                b = other_df[ok].to_numpy()
                if a.dtype != b.dtype:
                    if a.dtype.kind in "iuf" and b.dtype.kind in "iuf":
                        a = a.astype(np.result_type(a.dtype, b.dtype), copy=False)
                    else:
                        return None
                if a.dtype.kind == "f":
                    a = a + 0.0  # -0.0 -> +0.0; NaN unchanged
                planes.append(hash_input_uint32(a))
            return bucket_ids_np(planes, n_parts)

        lids = keyed(ldf, lkeys, rdf, rkeys)
        rids = keyed(rdf, rkeys, ldf, lkeys)
        if lids is None or rids is None:
            return ldf.merge(rdf, left_on=lkeys, right_on=rkeys, how=how)
        trace.record("join", f"generic-merge-partitioned({n_parts})")
        parts = []
        for p in range(n_parts):
            lp = ldf[lids == p]
            rp = rdf[rids == p]
            if len(lp) == 0 and len(rp) == 0:
                continue
            if how == "inner" and (len(lp) == 0 or len(rp) == 0):
                continue
            if how == "left" and len(lp) == 0:
                continue
            if how == "right" and len(rp) == 0:
                continue
            parts.append(lp.merge(rp, left_on=lkeys, right_on=rkeys, how=how))
        if not parts:
            return ldf.iloc[:0].merge(rdf.iloc[:0], left_on=lkeys, right_on=rkeys, how=how)
        return pd.concat(parts, ignore_index=True, sort=False)

    @staticmethod
    def _residual_join(plan: L.Join, ldf, rdf, lkeys, rkeys, left, right_named):
        """Join with a non-equi ON residual: equi-match pairs, keep only
        pairs satisfying the residual, then null-extend the unmatched side
        rows for outer joins — ON-clause semantics, which a post-join filter
        cannot express for left/right/full joins (a failing pair must
        null-extend, not disappear). Residual references use post-join
        (renamed) column names; NULL residual results drop the pair
        (three-valued, like any SQL predicate). ``ldf``/``rdf`` are the slim
        key+row-id frames; residual inputs gather from the original arrays."""
        import pandas as pd

        from hyperspace_tpu.plan.expr import as_bool_mask

        pairs = ldf.merge(rdf, left_on=lkeys, right_on=rkeys, how="inner")
        if len(pairs):
            # only the referenced columns feed the predicate (the planner
            # resolved them to exact post-join names)
            refs = plan.residual.references()
            li = pairs["__lrow"].to_numpy()
            ri = pairs["__rrow"].to_numpy()
            batch = {}
            for c in refs:
                if c in pairs.columns:
                    batch[c] = pairs[c].to_numpy()
                elif c in left:
                    batch[c] = left[c][li]
                elif c in right_named:
                    batch[c] = right_named[c][ri]
            keep = as_bool_mask(plan.residual.eval(batch))
            # a constant residual (ON ... AND 1 = 0) evaluates 0-d: broadcast
            keep = np.broadcast_to(np.asarray(keep, dtype=bool), (len(pairs),))
            surviving = pairs[keep]
        else:
            surviving = pairs
        parts = [surviving]
        if plan.how in ("left", "outer"):
            lost = ldf[~np.isin(np.arange(len(ldf)), surviving["__lrow"].to_numpy())]
            parts.append(lost)  # right columns null-extend via concat
        if plan.how in ("right", "outer"):
            lost_r = rdf[~np.isin(np.arange(len(rdf)), surviving["__rrow"].to_numpy())]
            parts.append(lost_r)  # left columns null-extend
        return pd.concat(parts, ignore_index=True, sort=False) if len(parts) > 1 else surviving
