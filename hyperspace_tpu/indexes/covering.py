"""CoveringIndex — the flagship index.

A vertical slice (indexed + included columns) of the source data,
hash-bucketed on the indexed columns into ``num_buckets`` bucket files and
sorted by the indexed columns within each bucket, so that

  - filter queries scan only the index slice (and only the matching bucket,
    when bucket pruning applies), and
  - equi-joins on the indexed columns run without any shuffle.

(ref: HS/index/covering/CoveringIndex.scala:30-280,
 HS/index/covering/CoveringIndexConfig.scala:39-200)

The build replaces Spark's ``repartition(numBuckets, cols)`` shuffle +
per-partition sort + bucketed Parquet write
(ref: CoveringIndex.scala:54-69, DataFrameWriterExtensions.scala:50-68) with a
single jitted device program: encode -> hash -> ``bucket_sort_perm`` (XLA sort)
-> host gather -> per-bucket Parquet write. Optional lineage materializes a
``_data_file_id`` column mapping each index row to its source file
(ref: CoveringIndex.scala:227-279); here the id is attached at decode time
instead of via a broadcast join.

Bucket id is encoded in the data file name: ``part-<bucket>-<tag>.parquet``.
"""

from __future__ import annotations

import os
import re
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.dataset as pads
import pyarrow.parquet as pq

from hyperspace_tpu import config as C
from hyperspace_tpu.indexes import registry
from hyperspace_tpu.indexes.base import CreateContext, Index, IndexConfig, UpdateMode
from hyperspace_tpu.models.log_entry import Content, DerivedDataset
from hyperspace_tpu.obs import spans
from hyperspace_tpu.obs.metrics import REGISTRY
from hyperspace_tpu.plan.logical import BucketSpec
from hyperspace_tpu.plan.resolver import resolve_columns_against_schema
from hyperspace_tpu.sources import schema as schema_codec

_BUCKET_FILE_RE = re.compile(r"part-(\d+)-")

# what a build counts (stage seconds are spans.stage's, cat "build"; see
# docs/observability.md "Inside the program")
_BUILD_ROWS = REGISTRY.counter(
    "hs_build_rows_total", "Source rows bucketed and sorted by index builds"
)
_BUILD_SOURCE_BYTES = REGISTRY.counter(
    "hs_build_source_bytes_total",
    "Arrow bytes of the source columns decoded by index builds, keys and payload",
)
# thread-seconds inside the write pool, NOT wall: eight workers add up to
# eight seconds a second; the pool's wall is the take-write stage
_TAKE_SECONDS = spans.stage_seconds("take", "build")
_WRITE_SECONDS = spans.stage_seconds("write", "build")
# the mesh build's exchange: whether ``bucket % n_devices`` and
# ``tpu.rebucket.capacityFactor`` fit the data
_EXCHANGE_RETRIES = REGISTRY.counter(
    "hs_build_exchange_retries_total",
    "Re-runs of the mesh build's exchange at doubled slot capacity (skew overflow)",
)
_EXCHANGE_SLOTS = {
    kind: REGISTRY.counter(
        "hs_build_exchange_slots_total",
        "Slots the mesh build's exchange downloaded: valid = rows that arrived, "
        "shipped = n_devices^2 x capacity a chunk",
        kind=kind,
    )
    for kind in ("valid", "shipped")
}


def _exchange_rows(device: int):
    return REGISTRY.counter(
        "hs_build_exchange_rows_total",
        "Valid rows each device of the mesh ended up owning after the build's exchange",
        device=str(device),
    )

#: Version of the bucket hash function the index's data files were
#: partitioned with. Bumped whenever ops/hashing changes bucket placement
#: (v2 = round-5 value-consistent int/float normalization). An index
#: stamped with an older version still serves correct index-only scans,
#: but the optimizer must not trust its bucket LAYOUT (no bucket pruning,
#: no shuffle-free joins) until a full refresh/optimize re-buckets it —
#: see rules/utils.transform_plan_to_use_index.
BUCKET_HASH_VERSION = 2
_BUCKET_HASH_VERSION_PROP = "bucketHashVersion"


def bucket_of_file(path: str) -> Optional[int]:
    m = _BUCKET_FILE_RE.match(os.path.basename(path))
    return int(m.group(1)) if m else None


def _bucket_file_name(bucket: int) -> str:
    return f"part-{bucket:05d}-{uuid.uuid4().hex[:12]}.parquet"


class CoveringIndex(Index):
    kind = "CoveringIndex"
    kind_abbr = "CI"

    def __init__(
        self,
        indexed_columns: List[str],
        included_columns: List[str],
        num_buckets: int,
        schema_json: str = "",
        lineage: bool = False,
        extra_properties: Optional[Dict[str, Any]] = None,
    ):
        self._indexed = list(indexed_columns)
        self._included = list(included_columns)
        self.num_buckets = int(num_buckets)
        self.schema_json = schema_json
        self.lineage = bool(lineage)
        self._extra = dict(extra_properties or {})

    # --- identity ----------------------------------------------------------
    @property
    def indexed_columns(self) -> List[str]:
        return list(self._indexed)

    @property
    def included_columns(self) -> List[str]:
        return list(self._included)

    @property
    def referenced_columns(self) -> List[str]:
        return self._indexed + self._included

    @property
    def properties(self) -> Dict[str, Any]:
        props = {
            "indexedColumns": self._indexed,
            "includedColumns": self._included,
            "numBuckets": self.num_buckets,
            "schemaJson": self.schema_json,
            C.LINEAGE_PROPERTY: str(self.lineage).lower(),
        }
        props.update(self._extra)
        return props

    def with_new_properties(self, properties: Dict[str, Any]) -> "CoveringIndex":
        extra = {k: v for k, v in properties.items()
                 if k not in ("indexedColumns", "includedColumns", "numBuckets", "schemaJson", C.LINEAGE_PROPERTY)}
        return CoveringIndex(self._indexed, self._included, self.num_buckets,
                             self.schema_json, self.lineage, extra)

    @classmethod
    def from_derived_dataset(cls, dd: DerivedDataset) -> "CoveringIndex":
        p = dd.properties
        extra = {k: v for k, v in p.items()
                 if k not in ("indexedColumns", "includedColumns", "numBuckets", "schemaJson", C.LINEAGE_PROPERTY)}
        return cls(
            list(p["indexedColumns"]),
            list(p.get("includedColumns", [])),
            int(p["numBuckets"]),
            p.get("schemaJson", ""),
            str(p.get(C.LINEAGE_PROPERTY, "false")).lower() == "true",
            extra,
        )

    def bucket_spec(self) -> BucketSpec:
        """(ref: HS/index/covering/CoveringIndex.scala:173-177)"""
        return BucketSpec(self.num_buckets, tuple(self._indexed), tuple(self._indexed))

    @property
    def bucket_hash_version(self) -> int:
        """Hash-function version the data files were bucketed with; entries
        predating the property default to 1 (the pre-normalization hash)."""
        return int(self._extra.get(_BUCKET_HASH_VERSION_PROP, 1))

    def can_handle_deleted_files(self) -> bool:
        return self.lineage

    def stats(self) -> Dict[str, Any]:
        return {
            "indexedColumns": self._indexed,
            "includedColumns": self._included,
            "numBuckets": self.num_buckets,
        }

    # --- build -------------------------------------------------------------
    def write(self, ctx: CreateContext, df) -> None:
        """Build index data for ``df`` into ``ctx.index_data_path``
        (ref: CoveringIndex.scala:54-69 write = repartition + saveWithBuckets).

        Without lineage the build is pipelined: only the key columns are
        decoded before the device program launches; the payload columns decode
        while the permutation rides back from the device."""
        from hyperspace_tpu.plan.logical import Scan

        # write() re-buckets ALL data (create, full refresh, overwrite-mode
        # incremental): the index is now consistent with the current hash
        self._extra[_BUCKET_HASH_VERSION_PROP] = str(BUCKET_HASH_VERSION)

        plan = df.plan
        if isinstance(plan, Scan) and not self.lineage:
            # STREAMING build: source files are decoded in groups of
            # ~batchRows rows and fed straight into the pipelined device
            # build, so host memory is bounded by O(2 chunks + largest
            # file), never by table size — the discipline that lets a
            # TPC-H SF100 (600M-row) build run on a bounded-RAM host. The
            # reference gets this for free from Spark's streaming executors
            # (ref: CoveringIndex.scala:54-69 repartition+saveWithBuckets);
            # here the build owns its own out-of-core chunking.
            relation = plan.relation
            resolved = self._resolve_all(ctx, relation.schema)
            columns = [r.normalized_name for r in resolved]
            key_res = [r for r in resolved if r.normalized_name in self._indexed]
            payload = [r for r in resolved if r.normalized_name not in self._indexed]
            batch_rows = ctx.session.conf.build_batch_rows
            files = [fi.name for fi in relation.all_file_infos()]
            # per-file reads lose the unified-dataset schema the one-shot
            # path had (Arrow casts/null-fills fragments against it); conform
            # every per-file projection to the resolved schema so sources
            # with per-file schema drift still build one consistent index
            key_schema = pa.schema([_arrow_field_for(r, relation.schema) for r in key_res])
            payload_schema = pa.schema(
                [_arrow_field_for(r, relation.schema) for r in payload]
            )

            def groups():
                # each file's dataset is constructed ONCE and serves both the
                # key and payload projections: for materialized formats
                # (avro/text) construction IS the decode, so reusing it keeps
                # the build at one decode per file (the group holds its
                # files' tables until the chunk is written — bounded by
                # group size, same O(chunk) discipline)
                pending_ds: List = []
                pending_keys: List[pa.Table] = []
                rows = 0

                def emit():
                    kt = (
                        pa.concat_tables(pending_keys)
                        if len(pending_keys) > 1
                        else pending_keys[0]
                    )
                    grp_ds = list(pending_ds)

                    def group_payload_fn() -> Optional[pa.Table]:
                        if not payload:
                            return None
                        parts = [
                            _project_conform(d, payload, payload_schema) for d in grp_ds
                        ]
                        return pa.concat_tables(parts) if len(parts) > 1 else parts[0]

                    return kt, group_payload_fn

                for f in files:
                    with spans.stage("decode-keys", "build"):
                        ds_f = relation.arrow_dataset([f])
                        kt = _project_conform(ds_f, key_res, key_schema)
                    # emit BEFORE a file that would cross batchRows: groups
                    # stay under the cap (only a single file larger than
                    # batchRows exceeds it, and that group slices evenly),
                    # so no group leaves a sliver chunk paying a full
                    # device launch for a handful of rows
                    if batch_rows and pending_ds and rows + kt.num_rows > batch_rows:
                        yield emit()
                        pending_ds, pending_keys, rows = [], [], 0
                    pending_ds.append(ds_f)
                    pending_keys.append(kt)
                    rows += kt.num_rows
                    if batch_rows and rows >= batch_rows:
                        yield emit()
                        pending_ds, pending_keys, rows = [], [], 0
                if pending_ds:
                    yield emit()

            # the distributed-vs-single-device decision needs TOTAL rows
            # (conf distributedMinRows), which streaming never sees at once;
            # parquet footers give it for free, other formats fall back to
            # sizing by the first chunk
            total_rows = None
            if relation.physical_format == "parquet":
                try:
                    total_rows = sum(pq.read_metadata(f).num_rows for f in files)
                except Exception:
                    total_rows = None

            write_bucketed_groups(
                groups(),
                self._indexed,
                self.num_buckets,
                ctx.index_data_path,
                column_order=columns,
                batch_rows=batch_rows,
                session=ctx.session,
                total_rows=total_rows,
            )
            schema = pa.schema([_arrow_field_for(r, relation.schema) for r in resolved])
            self.schema_json = schema_codec.schema_to_json(schema)
            return

        table = self._index_data_table(ctx, df)
        write_bucketed(
            table,
            self._indexed,
            self.num_buckets,
            ctx.index_data_path,
            batch_rows=ctx.session.conf.build_batch_rows,
            session=ctx.session,
        )
        self.schema_json = schema_codec.schema_to_json(table.schema)

    def _resolve_all(self, ctx: CreateContext, schema: pa.Schema):
        """Resolve indexed/included columns, normalizing nested paths with the
        ``__hs_nested.`` prefix; nested indexing is gated on conf
        (ref: CoveringIndexConfig nested normalization, ResolverUtils.scala:44-105).

        Names may arrive already normalized (refresh/optimize revive the index
        from its log entry) — strip the prefix before re-resolving against the
        source schema."""
        from hyperspace_tpu.plan.resolver import ResolvedColumn

        def denorm(names):
            return [ResolvedColumn.from_normalized(n).name for n in names]

        resolved = resolve_columns_against_schema(denorm(self.referenced_columns), schema)
        if any(r.is_nested for r in resolved):
            conf = getattr(getattr(ctx, "session", None), "conf", None)
            if conf is not None and not conf.nested_column_enabled:
                raise ValueError(
                    "Indexing nested columns requires "
                    f"{C.keys.NESTED_COLUMN_ENABLED}=true"
                )
        self._indexed = [r.normalized_name for r in resolve_columns_against_schema(denorm(self._indexed), schema)]
        self._included = [r.normalized_name for r in resolve_columns_against_schema(denorm(self._included), schema)]
        return resolved

    def _index_data_table(self, ctx: CreateContext, df) -> pa.Table:
        """The vertical slice (+ optional lineage column) as one arrow table
        (ref: createIndexData, CoveringIndex.scala:227-279)."""
        from hyperspace_tpu.plan.logical import Scan

        plan = df.plan
        if not isinstance(plan, Scan):
            raise ValueError(
                "createIndex expects a plain source scan (project/filter on top "
                "of a supported relation); got: " + type(plan).__name__
            )
        relation = plan.relation
        resolved = self._resolve_all(ctx, relation.schema)
        projection = _nested_projection(resolved)

        if not self.lineage:
            return relation.arrow_dataset().to_table(columns=projection)

        # lineage: attach _data_file_id per source file at decode time
        # (arrow_dataset so hive-partition columns resolve per file)
        tables = []
        for fi in relation.all_file_infos():
            fid = ctx.file_id_tracker.add_file(fi)
            t = relation.arrow_dataset([fi.name]).to_table(columns=projection)
            t = t.append_column(C.DATA_FILE_NAME_ID, pa.array(np.full(t.num_rows, fid, dtype=np.int64)))
            tables.append(t)
        return pa.concat_tables(tables)


def _project_conform(ds, resolved, schema: pa.Schema) -> pa.Table:
    """Project ``resolved`` columns out of one file's dataset and conform the
    result to the unified ``schema`` (cast drifted dtypes; null-fill columns
    the file predates). The one-shot build's single dataset did this
    implicitly via Arrow's unified dataset schema; per-file streaming reads
    must do it explicitly or schema-evolved sources crash mid-build."""
    try:
        t = ds.to_table(columns=_nested_projection(resolved))
    except (KeyError, pa.ArrowInvalid, pa.ArrowKeyError):
        # a projected column is missing from this file (schema evolution):
        # decode what the file has, extract what resolves (nested leaves via
        # struct_field — the normalized __hs_nested. name never matches a
        # physical column), and null-fill only what's genuinely absent
        import pyarrow.compute as pc

        full = ds.to_table()
        arrays = []
        for r, f in zip(resolved, schema):
            parts = r.name.split(".")
            arr = full.column(parts[0]) if parts[0] in full.column_names else None
            for seg in parts[1:]:
                if arr is None:
                    break
                try:
                    arr = pc.struct_field(arr, seg)
                except (KeyError, pa.ArrowInvalid, pa.ArrowKeyError, TypeError):
                    arr = None
            arrays.append(arr if arr is not None else pa.nulls(full.num_rows, f.type))
        return pa.table(dict(zip(schema.names, arrays))).cast(schema)
    if t.schema != schema:
        t = t.cast(schema)
    return t


def _nested_projection(resolved) -> Dict[str, Any]:
    """Arrow dataset projection dict: normalized output name -> field ref
    (nested paths project the struct leaf into a flat column)."""
    import pyarrow.compute as pc

    out: Dict[str, Any] = {}
    for r in resolved:
        out[r.normalized_name] = pc.field(*r.name.split(".")) if r.is_nested else pc.field(r.name)
    return out


def _arrow_field_for(resolved_col, schema: pa.Schema) -> pa.Field:
    """The (leaf) arrow field a resolved column projects to, named by its
    normalized (flat) name."""
    parts = resolved_col.name.split(".")
    field = schema.field(parts[0])
    for p in parts[1:]:
        field = field.type.field(p)
    return pa.field(resolved_col.normalized_name, field.type)


def _take_write(chunk: pa.Table, rows_of_bucket: np.ndarray, out_dir: str, bucket: int) -> str:
    """One bucket's sorted run: gather its rows, write its file. Runs on the
    write pool; its two halves are thread-seconds of stages ``take`` and
    ``write``."""
    path = os.path.join(out_dir, _bucket_file_name(bucket))
    t0 = time.perf_counter()
    rows = chunk.take(pa.array(rows_of_bucket))
    t1 = time.perf_counter()
    # uncompressed PLAIN is the index-file dialect: the native decoder
    # (hyperspace_tpu/native) mmaps these and memcpys column chunks into
    # device-feedable buffers with zero decompression work
    pq.write_table(rows, path, use_dictionary=False, compression="NONE")
    _TAKE_SECONDS.inc(t1 - t0)
    _WRITE_SECONDS.inc(time.perf_counter() - t1)
    return path


def write_bucketed(
    table: pa.Table,
    bucket_sort_columns: List[str],
    num_buckets: int,
    out_dir: str,
    payload_fn=None,
    column_order: Optional[List[str]] = None,
    batch_rows: Optional[int] = None,
    session=None,
    _chunks=None,
    _total_rows: Optional[int] = None,
) -> List[str]:
    """Device-accelerated bucketed + sorted Parquet write.

    One fused device program (ops/sort.bucket_sort_build: hash -> bucket ->
    multi-key sort -> Pallas histogram) returns the clustering permutation and
    per-bucket counts. The pipeline overlaps every host stage with the device
    round trip:

      decode keys -> launch device program -> async perm fetch
                      || payload_fn() decodes the non-key columns
      fetch done  -> per-bucket (arrow take + parquet write) in a thread pool
                     (both release the GIL in C++)

    ``table`` must hold at least ``bucket_sort_columns``; ``payload_fn``, if
    given, is called after the device launch and returns the remaining
    columns (row-aligned with ``table``) or None. ``column_order`` fixes the
    output column order.

    ``batch_rows`` (> 0) caps rows per device program: larger tables are
    processed in chunks, each writing its own sorted run per bucket (the
    multi-run state incremental refresh also produces; optimize compacts
    it). Returns written file paths — bucket order within each chunk,
    chunk-major with repeated bucket ids when chunking kicks in.

    When ``session`` is given and its mesh spans more than one device (and the
    table clears conf ``hyperspace.tpu.build.distributedMinRows``), each chunk
    runs the DISTRIBUTED program instead: rows shard across the mesh, hash on
    device, one ``all_to_all`` routes every row to its owning device
    (bucket % n_devices), and each device sorts its buckets locally — the
    TPU-native replacement for the reference's cluster-wide
    ``repartition(numBuckets, cols)`` shuffle (ref: CoveringIndex.scala:54-69).
    Exchange-capacity overflow (skew) retries with doubled slot capacity until
    the exchange fits. Bucket file contents are identical to the single-device
    build's (same rows, same within-bucket order).
    """
    from hyperspace_tpu.exec import device as D
    from hyperspace_tpu.exec.batch import table_to_batch
    from hyperspace_tpu.ops import encode
    from hyperspace_tpu.ops.sort import bucket_sort_build, padded_size

    os.makedirs(out_dir, exist_ok=True)
    n = table.num_rows
    if n == 0:
        return []

    mesh = None
    capacity_factor = 2.0
    if session is not None:
        m = session.mesh
        # streaming callers pass the true total (``table`` is only the first
        # chunk there); distributedMinRows gates on the BUILD size, not the
        # chunk size. The whole distributed build sits behind the default-off
        # hyperspace.parallel.* master switch: off means the byte-identical
        # single-logical-device build below.
        if (
            session.conf.parallel_enabled
            and session.conf.parallel_build_enabled
            and m.devices.size > 1
            and (_total_rows if _total_rows is not None else n)
            >= session.conf.distributed_build_min_rows
        ):
            mesh = m
            capacity_factor = session.conf.rebucket_capacity_factor

    def _encode_keys(chunk: pa.Table):
        """Count the chunk and encode its key columns: (rows, key planes,
        kinds, host hash planes)."""
        _BUILD_ROWS.inc(chunk.num_rows)
        _BUILD_SOURCE_BYTES.inc(chunk.nbytes)
        with spans.stage("encode-keys", "build"):
            batch = table_to_batch(chunk.select(bucket_sort_columns))
            keys, kinds, host_hashes = encode.encode_sort_columns(
                [batch[c] for c in bucket_sort_columns]
            )
        return chunk.num_rows, keys, kinds, host_hashes

    def _launch(chunk: pa.Table) -> dict:
        """Host encode + device program dispatch + async d2h start. Returns
        the in-flight state; nothing here blocks on the device."""
        cn, keys, kinds, host_hashes = _encode_keys(chunk)
        with spans.stage("h2d-launch", "build"):
            np2 = padded_size(cn)
            dev_keys = [D.put(np.pad(k, (0, np2 - cn)), "build-keys") for k in keys]
            dev_hashes = [D.put(np.pad(h, (0, np2 - cn)), "build-keys") for h in host_hashes]
            with D.launch("index-build"):
                perm, counts = bucket_sort_build(dev_keys, dev_hashes, kinds, num_buckets, cn)
            counts.copy_to_host_async()
            # the permutation comes back in pieces so bucket writes can start
            # while later pieces are still in flight (device->host is the
            # narrow link)
            n_pieces = min(8, max(1, np2 // (1 << 18)))
            piece_len = np2 // n_pieces
            pieces = [perm[i * piece_len : (i + 1) * piece_len] for i in range(n_pieces)]
            for p in pieces:
                p.copy_to_host_async()
        return {"chunk": chunk, "np2": np2, "counts": counts, "pieces": pieces}

    def _prepare_chunk(state: dict, chunk_payload_fn) -> pa.Table:
        """Shared host prep before bucket writes: attach the lazily-decoded
        payload columns, fix the output column order, and collapse to
        single-chunk columns so per-bucket takes don't re-resolve chunk
        offsets (a numpy-gather variant measured equal within noise; arrow
        take keeps string/date columns on one code path)."""
        chunk = state["chunk"]
        if chunk_payload_fn is not None:
            with spans.stage("decode-payload", "build"):
                payload = chunk_payload_fn()
            if payload is not None:
                _BUILD_SOURCE_BYTES.inc(payload.nbytes)
                for name in payload.column_names:
                    chunk = chunk.append_column(payload.schema.field(name), payload.column(name))
        with spans.stage("combine", "build"):
            if column_order:
                chunk = chunk.select(column_order)
            chunk = chunk.combine_chunks()
        return chunk

    def _finish(state: dict, chunk_payload_fn) -> List[str]:
        """Drain the permutation and write the per-bucket sorted parquet
        files; host-heavy, overlapped with the NEXT chunk's device work."""
        np2 = state["np2"]
        chunk = _prepare_chunk(state, chunk_payload_fn)
        with spans.stage("d2h-counts", "build"):
            counts_np = np.asarray(state["counts"])
            D.link_bytes("d2h", "build-counts", counts_np.nbytes)
        boundaries = np.concatenate([[0], np.cumsum(counts_np)])

        from concurrent.futures import ThreadPoolExecutor

        perm_np = np.empty(np2, dtype=np.int32)
        arrived = 0
        next_piece = 0
        futures = []
        pieces = state["pieces"]
        with spans.stage("take-write", "build"), ThreadPoolExecutor(max_workers=8) as ex:
            for b in range(num_buckets):
                lo, hi = int(boundaries[b]), int(boundaries[b + 1])
                if hi <= lo:
                    continue
                while arrived < hi:
                    with spans.stage("d2h-perm", "build"):
                        piece = np.asarray(pieces[next_piece])  # blocks for this piece only
                    perm_np[arrived : arrived + piece.shape[0]] = piece
                    arrived += piece.shape[0]
                    next_piece += 1
                futures.append(ex.submit(_take_write, chunk, perm_np[lo:hi], out_dir, b))
            out = [f.result() for f in futures]
        D.link_bytes("d2h", "build-perm", perm_np.nbytes)  # every piece was sent for
        return out

    def _launch_mesh(chunk: pa.Table) -> dict:
        """Distributed variant of ``_launch``: shard the encoded key planes
        over the mesh, dispatch the exchange program, and start async fetches.
        The returned state carries the device inputs so ``_finish_mesh`` can
        retry with doubled capacity on exchange overflow."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from hyperspace_tpu.ops.bucketize import _next_pow2, distributed_bucket_sort_build

        cn, keys, kinds, host_hashes = _encode_keys(chunk)
        with spans.stage("h2d-launch", "build"):
            n_dev = int(mesh.devices.size)
            per_dev = padded_size(-(-cn // n_dev))
            pad_n = per_dev * n_dev
            sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
            dev_keys = [D.put(np.pad(k, (0, pad_n - cn)), "build-keys", sharding) for k in keys]
            dev_hashes = [
                D.put(np.pad(h, (0, pad_n - cn)), "build-keys", sharding) for h in host_hashes
            ]
            row_idx = D.put(np.arange(pad_n, dtype=np.int32), "build-keys", sharding)
            capacity = min(
                _next_pow2(int(per_dev / n_dev * capacity_factor)), _next_pow2(per_dev)
            )
            with D.launch("index-build-exchange"):
                bkts, ridx, vld, ovf = distributed_bucket_sort_build(
                    mesh, dev_keys, dev_hashes, kinds, row_idx, cn, num_buckets, capacity
                )
            for a in (ovf, bkts, ridx, vld):
                a.copy_to_host_async()
        return {
            "chunk": chunk,
            "bkts": bkts,
            "ridx": ridx,
            "vld": vld,
            "ovf": ovf,
            "n_dev": n_dev,
            "capacity": capacity,
            "per_dev": per_dev,
            "retry": (dev_keys, dev_hashes, kinds, row_idx, cn),
        }

    def _finish_mesh(state: dict, chunk_payload_fn) -> List[str]:
        """Drain the distributed program's outputs and write per-bucket sorted
        parquet files. Buckets live wholly on their owner device, so each
        device shard yields its own contiguous bucket runs."""
        from hyperspace_tpu.ops.bucketize import _next_pow2, distributed_bucket_sort_build

        chunk = _prepare_chunk(state, chunk_payload_fn)
        capacity, per_dev = state["capacity"], state["per_dev"]
        bkts, ridx, vld, ovf = state["bkts"], state["ridx"], state["vld"], state["ovf"]
        with spans.stage("exchange-drain", "build"):
            while True:
                with spans.stage("d2h-counts", "build"):
                    ovf_np = np.asarray(ovf)
                D.link_bytes("d2h", "build-counts", ovf_np.nbytes)
                if int(ovf_np.sum()) == 0:
                    break
                # skew overflowed a destination's slots: double capacity and
                # rerun (a source holds per_dev rows total, so capacity ==
                # per_dev always fits and the loop terminates)
                if capacity >= per_dev:
                    raise RuntimeError(
                        "distributed build exchange overflow at full capacity "
                        f"(capacity={capacity}, per_dev={per_dev})"
                    )
                capacity = min(_next_pow2(capacity * 2), _next_pow2(per_dev))
                _EXCHANGE_RETRIES.inc()
                dev_keys, dev_hashes, kinds, row_idx, cn = state["retry"]
                with D.launch("index-build-exchange"):
                    bkts, ridx, vld, ovf = distributed_bucket_sort_build(
                        mesh, dev_keys, dev_hashes, kinds, row_idx, cn, num_buckets, capacity
                    )
            with spans.stage("d2h-perm", "build"):
                bkts_np = np.asarray(bkts)
                ridx_np = np.asarray(ridx)
                vld_np = np.asarray(vld)
            D.link_bytes("d2h", "build-perm", bkts_np.nbytes + ridx_np.nbytes + vld_np.nbytes)

        from concurrent.futures import ThreadPoolExecutor

        n_dev = state["n_dev"]
        shard_len = bkts_np.shape[0] // n_dev
        _EXCHANGE_SLOTS["shipped"].inc(vld_np.shape[0])
        _EXCHANGE_SLOTS["valid"].inc(int(vld_np.sum()))
        futures = []
        with spans.stage("take-write", "build"), ThreadPoolExecutor(max_workers=8) as ex:
            for d in range(n_dev):
                sl = slice(d * shard_len, (d + 1) * shard_len)
                v_d = vld_np[sl]
                nv = int(v_d.sum())  # valid rows sort to the shard's prefix
                _exchange_rows(d).inc(nv)
                if nv == 0:
                    continue
                b_v = bkts_np[sl][:nv]
                r_v = ridx_np[sl][:nv]
                bounds = np.searchsorted(b_v, np.arange(num_buckets + 1))
                for b in range(d, num_buckets, n_dev):
                    lo, hi = int(bounds[b]), int(bounds[b + 1])
                    if hi > lo:
                        futures.append(ex.submit(_take_write, chunk, r_v[lo:hi], out_dir, b))
            out = [f.result() for f in futures]
        return out

    launch, finish = (_launch_mesh, _finish_mesh) if mesh is not None else (_launch, _finish)

    if _chunks is not None:
        # write_bucketed_groups' streaming entry: the chunk iterator replaces
        # the single-table slicing entirely (``table`` only sized the mesh
        # decision above)
        return _pipelined_chunks(_chunks, launch, finish)

    if batch_rows is not None and batch_rows > 0 and n > batch_rows:
        # chunked build, software-pipelined one chunk deep: chunk k+1's
        # device program (and its d2h transfers) runs while chunk k's host
        # side drains and writes parquet. Each chunk writes its own sorted
        # run per bucket — the multi-run state incremental refresh also
        # produces (UpdateMode.Merge); the join path re-sorts lazily and
        # optimize compacts. Peak device footprint is two chunks
        # (~2x batchRows rows); payload decodes lazily per chunk slice.
        return _pipelined_chunks(
            _sliced_chunks(table, payload_fn, batch_rows), launch, finish
        )

    return finish(launch(table), payload_fn)


def _sliced_chunks(table: pa.Table, payload_fn, batch_rows: int):
    """Yield (key_chunk, chunk_payload_fn) slices of one materialized table;
    the payload (if any) decodes ONCE lazily and is sliced per chunk. Chunks
    are EQUAL-size (ceil division) rather than batch_rows + remainder, so no
    sliver chunk pays a full device launch for a handful of rows."""
    payload_cell: List[Optional[pa.Table]] = []

    def full_payload() -> Optional[pa.Table]:
        if not payload_cell:
            payload_cell.append(payload_fn() if payload_fn is not None else None)
        return payload_cell[0]

    n = table.num_rows
    n_chunks = max(1, -(-n // batch_rows))
    size = -(-n // n_chunks)
    for off in range(0, n, size):
        chunk_pf = None
        if payload_fn is not None:

            def chunk_pf(off=off):
                p = full_payload()
                return p.slice(off, size) if p is not None else None

        yield table.slice(off, size), chunk_pf


def _pipelined_chunks(chunks, launch, finish) -> List[str]:
    """Drive (key_chunk, payload_fn) pairs through the launch/finish pipeline
    one chunk deep: chunk k+1's device program runs while chunk k's host side
    drains and writes parquet."""
    paths: List[str] = []
    in_flight: Optional[tuple] = None
    for key_chunk, chunk_payload_fn in chunks:
        state = launch(key_chunk)
        if in_flight is not None:
            paths.extend(finish(*in_flight))
        in_flight = (state, chunk_payload_fn)
    if in_flight is not None:
        paths.extend(finish(*in_flight))
    return paths


def write_bucketed_groups(
    groups,
    bucket_sort_columns: List[str],
    num_buckets: int,
    out_dir: str,
    column_order: Optional[List[str]] = None,
    batch_rows: Optional[int] = None,
    session=None,
    total_rows: Optional[int] = None,
) -> List[str]:
    """Out-of-core variant of :func:`write_bucketed`: ``groups`` is an
    ITERABLE of ``(key_table, payload_fn)`` pairs (each key_table holds the
    bucket/sort columns for one group of source rows; ``payload_fn()``
    lazily decodes that group's remaining columns, row-aligned). Groups are
    consumed strictly in order and sliced to ``batch_rows`` chunks, so peak
    host memory is O(2 chunks + one group's payload) regardless of total
    table size. Each chunk writes its own sorted run per bucket — the
    multi-run state the reference's incremental refresh also produces
    (ref: actions/RefreshIncrementalAction.scala:115-128); optimize
    compacts runs.

    The build path streams source FILES through this (indexes/covering.py
    ``CoveringIndex.write``), which is what lets a TPC-H SF100 build run
    with bounded RAM; the reference inherits the same property from Spark's
    streaming executors (ref: CoveringIndex.scala:54-69)."""
    os.makedirs(out_dir, exist_ok=True)

    def flattened():
        for key_table, payload_fn in groups:
            kn = key_table.num_rows
            if kn == 0:
                continue
            if batch_rows is not None and 0 < batch_rows < kn:
                yield from _sliced_chunks(key_table, payload_fn, batch_rows)
            else:
                yield key_table, payload_fn

    flat = flattened()
    first = next(flat, None)
    if first is None:
        return []

    import itertools as _it

    # payload_fn/batch_rows are NOT passed: the _chunks stream already
    # carries per-chunk payload closures and was sliced above — write_bucketed
    # reads neither on the _chunks path (and must not re-slice)
    return write_bucketed(
        first[0],  # fallback sizer for the mesh decision when total_rows=None
        bucket_sort_columns,
        num_buckets,
        out_dir,
        column_order=column_order,
        session=session,
        _chunks=_it.chain([first], flat),
        _total_rows=total_rows,
    )


class CoveringIndexConfig(IndexConfig):
    """(ref: HS/index/covering/CoveringIndexConfig.scala:39-200)"""

    def __init__(self, index_name: str, indexed_columns: List[str], included_columns: Optional[List[str]] = None):
        if not index_name:
            raise ValueError("Index name must not be empty")
        if not indexed_columns:
            raise ValueError("indexed_columns must not be empty")
        included_columns = list(included_columns or [])
        lowered = [c.lower() for c in indexed_columns + included_columns]
        if len(set(lowered)) != len(lowered):
            raise ValueError("Duplicate columns across indexed/included columns are not allowed")
        self._name = index_name
        self._indexed = list(indexed_columns)
        self._included = included_columns

    @property
    def index_name(self) -> str:
        return self._name

    @property
    def indexed_columns(self) -> List[str]:
        return list(self._indexed)

    @property
    def included_columns(self) -> List[str]:
        return list(self._included)

    @property
    def referenced_columns(self) -> List[str]:
        return self._indexed + self._included

    def create_index(self, ctx: CreateContext, df, properties: Dict[str, str]) -> CoveringIndex:
        """(ref: CoveringIndexConfig createIndex :92-116)"""
        index = CoveringIndex(
            self._indexed,
            self._included,
            num_buckets=ctx.session.conf.num_buckets,
            lineage=ctx.session.conf.lineage_enabled,
            extra_properties=dict(properties),
        )
        index.write(ctx, df)
        return index

    def __repr__(self) -> str:
        return f"CoveringIndexConfig({self._name!r}, indexed={self._indexed}, included={self._included})"

    class Builder:
        """Fluent builder (ref: CoveringIndexConfig builder, :118-200)."""

        def __init__(self):
            self._name: Optional[str] = None
            self._indexed: List[str] = []
            self._included: List[str] = []

        def indexName(self, name: str) -> "CoveringIndexConfig.Builder":
            if self._name:
                raise ValueError("indexName is already set")
            self._name = name
            return self

        index_name = indexName

        def indexBy(self, *columns: str) -> "CoveringIndexConfig.Builder":
            self._indexed.extend(columns)
            return self

        index_by = indexBy

        def include(self, *columns: str) -> "CoveringIndexConfig.Builder":
            self._included.extend(columns)
            return self

        def create(self) -> "CoveringIndexConfig":
            if not self._name:
                raise ValueError("indexName must be set")
            return CoveringIndexConfig(self._name, self._indexed, self._included)

    @staticmethod
    def builder() -> "CoveringIndexConfig.Builder":
        return CoveringIndexConfig.Builder()


registry.register(CoveringIndex.kind, CoveringIndex.from_derived_dataset)
