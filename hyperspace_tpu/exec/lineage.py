"""Device-side lineage delete filtering for the hybrid-scan path.

When an index carries deleted source files, the rewritten plan filters the
index side with ``NOT (lineage_id IN deleted_ids)`` (rules/utils.py
``_hybrid_scan_plan``). The host evaluates that as a NumPy set-op per row
batch; this module replaces it with a fused device anti-semi-join: the
deleted-id list is sorted, padded and replicated, the lineage column is
row-sharded, and membership is a ``searchsorted`` lookup — the same
sorted-lookup machinery the bucketed SMJ span search uses
(exec/join_stream.py), fused into a single elementwise program.

Properties the HLO contract pins down (``lineage-antijoin``):

- **zero collectives** — the lookup is elementwise over the resident column
  shard against a replicated id table; GSPMD must not shuffle rows;
- inherits the global forbidden-op rules (no host callbacks, no bounded
  dynamic shapes).

The id table pads to a geometric bucket with an int64-max sentinel so the
program skeleton stays stable as deletes accumulate; correctness does not
rely on the sentinel (a ``pos < n_ids`` guard with the *live* id count rides
along as a traced scalar). The lineage column shares the device residency
cache with the predicate path — same ``(scan_key, column, mesh_fp)`` keys,
same codec format — so commit-driven purges cover it for free.

Fallbacks (unsupported dtype, missing column, device-disabled) are counted
by the caller as ``hs_device_fallback_total{op="lineage"}`` via
``exec.trace.fallback`` and the host NOT-IN oracle serves the batch.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from hyperspace_tpu.check import hlo_lint as _hlo_lint
from hyperspace_tpu.exec import batch as B
from hyperspace_tpu.exec.device import (
    DeviceUnsupported,
    _cached_predicate_jit,
    _device_cache_put,
    _mesh_fp,
    _note_compile,
    _program_key,
    _put_encoded,
    bucket_rows,
    count_column_forms,
    ensure_x64,
    fetch,
    join_planes,
    launch,
    put,
    resident_column,
)

_hlo_lint.register_contract(
    "lineage-antijoin",
    collectives={},
    description="hybrid-scan delete filter: sorted-lookup anti-semi-join, shuffle-free",
)

#: sorted-ascending pad value for the replicated id table — strictly greater
#: than any real lineage file id, so padding preserves sort order and can
#: never report a false membership
_ID_SENTINEL = np.iinfo(np.int64).max

#: id tables are tiny relative to columns; a small geometric floor keeps the
#: number of distinct table shapes (and hence retraces) logarithmic in the
#: delete count without padding 3 ids to 4096
_ID_BUCKET_FLOOR = 64


def _antijoin_fn(col, ids, n_ids):
    import jax.numpy as jnp

    c = join_planes(col).astype(jnp.int64)
    pos = jnp.searchsorted(ids, c)
    pos_c = jnp.clip(pos, 0, ids.shape[0] - 1)
    found = (pos < n_ids) & (jnp.take(ids, pos_c) == c)
    return ~found  # keep-mask: True for rows NOT in the deleted set


def lineage_delete_mask(
    session,
    batch: B.Batch,
    column: str,
    deleted_ids,
    scan_key=None,
    parallel=None,
) -> np.ndarray:
    """Keep-mask for ``NOT (column IN deleted_ids)`` computed on device;
    byte-identical to the host NumPy oracle. Raises
    :class:`DeviceUnsupported` when the column is absent or non-integral —
    the caller falls back to the host path and counts the fallback."""
    ensure_x64()
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    if column not in batch:
        raise DeviceUnsupported(f"lineage column {column!r} missing from batch")
    n = B.num_rows(batch)
    if n == 0:
        return np.zeros(0, dtype=bool)
    col_np = batch[column]
    if col_np.dtype.kind not in ("i", "u"):
        raise DeviceUnsupported(f"lineage column dtype {col_np.dtype} is not integral")

    ids = np.unique(np.asarray(list(deleted_ids), dtype=np.int64))
    if ids.size == 0:
        return np.ones(n, dtype=bool)

    mesh = parallel.mesh if parallel is not None else session.mesh
    n_dev = mesh.devices.size
    axis = mesh.axis_names[0]
    row_sharding = NamedSharding(mesh, P(axis))
    replicated = NamedSharding(mesh, P())
    fp = _mesh_fp(mesh)

    # column residency: same key + value format as device_filter_mask, so
    # staging, predicate evaluation and lineage filtering share one entry
    ckey = (scan_key, column, fp) if scan_key is not None else None
    cached = resident_column(ckey, n)
    if cached is not None:
        dev_col = cached[0]
    else:
        dev_col, codec, nbytes = _put_encoded(session, mesh, row_sharding, n_dev, col_np)
        if ckey is not None:
            _device_cache_put(ckey, (dev_col, codec, n), nbytes)

    m = bucket_rows(int(ids.size), floor=_ID_BUCKET_FLOOR)
    ids_padded = np.full(m, _ID_SENTINEL, dtype=np.int64)
    ids_padded[: ids.size] = ids
    dev_ids = put(ids_padded, "filter-cols", replicated)
    n_ids = jax.device_put(np.int64(ids.size), replicated)

    key = _program_key("lineage-antijoin", mesh)
    jitted = _cached_predicate_jit(key, _antijoin_fn, "lineage-antijoin")
    _note_compile(key, (dev_col.shape, dev_ids.shape))
    _hlo_lint.maybe_verify(
        session.conf, "lineage-antijoin", key, jitted, (dev_col, dev_ids, n_ids)
    )
    count_column_forms((dev_col,))
    with launch("lineage-antijoin"):
        mask = jitted(dev_col, dev_ids, n_ids)
    return fetch(mask, "filter-mask", "lineage-antijoin")[:n]
