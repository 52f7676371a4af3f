"""Distributed re-bucketing: the TPU-native replacement for Spark's shuffle.

``rebucket`` moves each row to the device that owns its bucket
(``device = bucket % n_devices``) with ONE ``all_to_all`` over ICI inside a
``shard_map`` — replacing the JVM hash-shuffle behind
``repartition(numBuckets, cols)`` (ref: HS/index/covering/CoveringIndex.scala:54-69)
and the on-the-fly re-bucketing of appended data in hybrid scan
(ref: HS/index/covering/CoveringIndexRuleUtils.scala:357-417).

Rows are exchanged in fixed-capacity slots (static shapes for XLA): each
device reserves ``capacity`` rows for every destination; a validity mask marks
real rows. Capacity overflow is detected and reported so callers can retry
with a larger factor — the skew-handling strategy (SURVEY.md §7 hard parts).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Tuple

import jax

from hyperspace_tpu.utils.x64 import ensure_x64


import jax.numpy as jnp  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402


def _stage_for_exchange(values, dest, n_dev: int, capacity: int, fill=0, valid=None):
    """Scatter local rows into a (n_dev, capacity) staging grid keyed by
    destination device; rows beyond capacity are dropped (and counted).
    ``valid`` (optional bool mask) excludes padding rows from the exchange —
    needed when staging the output of a previous exchange phase."""
    n_loc = dest.shape[0]
    if valid is not None:
        dest = jnp.where(valid, dest, n_dev)  # invalid rows -> scratch bin
    order = jnp.argsort(dest, stable=True)
    dest_sorted = dest[order]
    counts = jnp.bincount(dest, length=n_dev)  # scratch bin excluded
    offsets = jnp.cumsum(counts) - counts
    offsets_ext = jnp.concatenate([offsets, jnp.zeros((1,), offsets.dtype)])
    rank = jnp.arange(n_loc) - offsets_ext[jnp.minimum(dest_sorted, n_dev)]
    in_slot = (dest_sorted < n_dev) & (rank < capacity)
    slot = jnp.minimum(dest_sorted, n_dev - 1) * capacity + jnp.clip(rank, 0, capacity - 1)
    slot = jnp.where(in_slot, slot, n_dev * capacity)  # overflow/invalid -> scratch

    staged = []
    for v in values:
        v_sorted = v[order]
        buf = jnp.full((n_dev * capacity + 1,), fill, dtype=v.dtype)
        buf = buf.at[slot].set(v_sorted)
        staged.append(buf[:-1].reshape(n_dev, capacity))
    mask = jnp.zeros((n_dev * capacity + 1,), dtype=bool).at[slot].set(in_slot)
    return staged, mask[:-1].reshape(n_dev, capacity), counts


_UNSIGNED_BY_WIDTH = {1: "uint8", 2: "uint16", 4: "uint32"}


def _to_planes(v):
    """Split an array into bit-exact int32 planes (1 plane for <=32-bit
    dtypes, hi/lo planes for 64-bit) so a whole exchange can ride ONE
    all_to_all regardless of column dtypes. Sub-32-bit values travel as
    their BIT PATTERNS (bitcast to the same-width unsigned, zero-extended) —
    never value casts, so bfloat16/float16/float8 survive exactly."""
    from jax import lax

    dt = v.dtype
    if dt == jnp.bool_:
        return [v.astype(jnp.int32)]
    if dt.itemsize == 8:
        u = lax.bitcast_convert_type(v, jnp.uint64)
        hi = lax.bitcast_convert_type((u >> jnp.uint64(32)).astype(jnp.uint32), jnp.int32)
        lo = lax.bitcast_convert_type((u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32), jnp.int32)
        return [hi, lo]
    if dt == jnp.int32:
        return [v]
    width = jnp.dtype(_UNSIGNED_BY_WIDTH[dt.itemsize])
    u = v if dt == width else lax.bitcast_convert_type(v, width)
    if dt.itemsize == 4:
        return [lax.bitcast_convert_type(u, jnp.int32)]
    return [u.astype(jnp.int32)]  # zero-extend the bit pattern


def _from_planes(planes, dt):
    """Inverse of ``_to_planes``."""
    from jax import lax

    dt = jnp.dtype(dt)
    if dt == jnp.bool_:
        return planes[0].astype(jnp.bool_)
    if dt.itemsize == 8:
        hi = lax.bitcast_convert_type(planes[0], jnp.uint32).astype(jnp.uint64)
        lo = lax.bitcast_convert_type(planes[1], jnp.uint32).astype(jnp.uint64)
        return lax.bitcast_convert_type((hi << jnp.uint64(32)) | lo, dt)
    if dt == jnp.int32:
        return planes[0]
    width = jnp.dtype(_UNSIGNED_BY_WIDTH[dt.itemsize])
    if dt.itemsize == 4:
        u = lax.bitcast_convert_type(planes[0], width)
    else:
        u = planes[0].astype(width)  # truncate back to the original bits
    return u if dt == width else lax.bitcast_convert_type(u, dt)


def _exchange_packed(staged, mask, axis):
    """The one-collective exchange: every staged (n_dev, capacity) buffer and
    the slot mask are split into bit-exact int32 planes, stacked into a single
    (n_dev, capacity, planes) tensor, exchanged with ONE tiled ``all_to_all``
    over ``axis``, and unpacked back to the original dtypes. One collective
    launch per exchange phase — the compiled-HLO property ``dryrun_multichip``
    and tests/test_hlo_collectives.py assert (SURVEY.md §2.9: build = one
    all-to-all; hierarchical = one per phase)."""
    dts = [v.dtype for v in staged]
    planes = []
    for v in staged:
        planes.extend(_to_planes(v))
    planes.extend(_to_planes(mask))
    packed = jnp.stack(planes, axis=-1)
    out = jax.lax.all_to_all(packed, axis, split_axis=0, concat_axis=0, tiled=True)
    out = out.reshape(-1, out.shape[-1])
    res, i = [], 0
    for dt in dts:
        k = 2 if jnp.dtype(dt).itemsize > 4 and dt != jnp.bool_ else 1
        res.append(_from_planes([out[:, i + j] for j in range(k)], dt))
        i += k
    out_mask = out[:, i].astype(jnp.bool_)
    return res, out_mask


def rebucket(
    mesh: Mesh,
    arrays: Dict[str, "jax.Array"],
    bucket_ids: "jax.Array",
    capacity: int,
) -> Tuple[Dict[str, "jax.Array"], "jax.Array", "jax.Array", "jax.Array"]:
    """Exchange rows so device ``d`` ends up holding exactly the rows with
    ``bucket % n_devices == d``.

    Args:
      mesh: 1-D device mesh; inputs must be sharded along its axis.
      arrays: name -> (n,) numeric arrays (row-aligned).
      bucket_ids: (n,) int32 bucket of each row.
      capacity: per-source-per-destination row slots (static).

    Returns:
      (out_arrays, out_buckets, valid_mask, overflow): each output has shape
      (n_devices * capacity,) per device shard — n_dev*n_dev*capacity global —
      with ``valid_mask`` marking real rows. ``overflow`` is the per-device
      count of rows dropped because a destination slot overflowed (callers
      must check it is all zero and retry with larger capacity otherwise).
    """
    ensure_x64()
    axis = mesh.axis_names[0]
    n_dev = mesh.shape[axis]
    names = list(arrays)
    values = [arrays[n] for n in names]

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis),) * (len(values) + 1),
        out_specs=(P(axis),) * (len(values) + 3),
    )
    def exchange(*args):
        *vals, buckets = args
        dest = (buckets % n_dev).astype(jnp.int32)
        # stage the bucket-id array together with the data columns: one
        # argsort/bincount/scatter pass serves all of them
        staged, mask, counts = _stage_for_exchange([*vals, buckets], dest, n_dev, capacity)
        sent = jnp.minimum(counts, capacity)
        overflow = jnp.sum(counts - sent)

        out, out_mask = _exchange_packed(staged, mask, axis)
        return (*out, out_mask, overflow[None])

    results = _hlo_lint.named("index-rebucket", exchange)(*values, bucket_ids)
    out_arrays = dict(zip(names, results[: len(names)]))
    out_buckets, valid, overflow = results[len(names)], results[len(names) + 1], results[len(names) + 2]
    return out_arrays, out_buckets, valid, overflow


def rebucket_and_sort(
    mesh: Mesh,
    arrays: Dict[str, "jax.Array"],
    hash_inputs: List["jax.Array"],
    sort_keys: List["jax.Array"],
    num_buckets: int,
    capacity: int,
):
    """Full distributed index-build step: hash -> all_to_all -> per-device
    stable sort by (bucket, sort keys). Invalid (padding) rows sort to the end.

    This is the device program the driver's ``dryrun_multichip`` compiles: the
    entire reference hot path (ref: SURVEY.md §3.1 boxed region) as one XLA
    computation over the mesh.
    """
    ensure_x64()
    from hyperspace_tpu.ops.hashing import bucket_ids_jnp
    from hyperspace_tpu.ops.sort import lex_argsort

    axis = mesh.axis_names[0]

    @partial(shard_map, mesh=mesh, in_specs=(P(axis),) * len(hash_inputs), out_specs=P(axis))
    def assign(*hi):
        return bucket_ids_jnp(list(hi), num_buckets)

    buckets = assign(*hash_inputs)
    n_keys = len(sort_keys)
    key_names = [f"__sk{i}" for i in range(n_keys)]
    all_arrays = {**arrays, **dict(zip(key_names, sort_keys))}
    out, out_buckets, valid, overflow = rebucket(mesh, all_arrays, buckets, capacity)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis),) * (n_keys + 2 + len(arrays)),
        out_specs=(P(axis),) * (2 + len(arrays)),
    )
    def local_sort(buckets_, valid_, *cols):
        sort_cols = cols[:n_keys]
        data_cols = cols[n_keys:]
        # invalid rows last: sort primarily by ~valid, then bucket, then keys
        order = lex_argsort([(~valid_).astype(jnp.int32), buckets_] + list(sort_cols))
        return (buckets_[order], valid_[order], *[c[order] for c in data_cols])

    sorted_res = local_sort(out_buckets, valid, *[out[k] for k in key_names], *[out[k] for k in arrays])
    sorted_buckets, sorted_valid = sorted_res[0], sorted_res[1]
    sorted_arrays = dict(zip(list(arrays), sorted_res[2:]))
    return sorted_arrays, sorted_buckets, sorted_valid, overflow


def _next_pow2(x: int) -> int:
    return max(8, 1 << (max(int(x) - 1, 1)).bit_length())


from functools import lru_cache  # noqa: E402

from hyperspace_tpu.check import hlo_lint as _hlo_lint  # noqa: E402

# Declared HLO contracts for the build/exchange programs (SURVEY.md §2.9:
# build = exactly ONE all-to-all; hierarchical re-bucketing = one per phase).
# The single-phase contracts also apply to the plane-packed `rebucket`
# program — tests jit-wrap it and assert against "index-rebucket".
_hlo_lint.register_contract(
    "index-build-exchange",
    collectives={"all-to-all": (1, 1)},
    description="distributed index build: rows cross devices in exactly one plane-packed all-to-all",
)
_hlo_lint.register_contract(
    "index-rebucket",
    collectives={"all-to-all": (1, 1)},
    description="incremental re-bucketing: one plane-packed all-to-all",
)
_hlo_lint.register_contract(
    "hierarchical-exchange",
    collectives={"all-to-all": (2, 2)},
    description="2-D (dcn, ici) re-bucketing: one all-to-all per phase, rows cross DCN once",
)


@lru_cache(maxsize=64)
def _build_exchange_program(mesh: Mesh, kinds: Tuple[str, ...], num_buckets: int, capacity: int):
    """Jitted distributed index-build step for one (mesh, key kinds,
    num_buckets, capacity) class:

      per-device hash (device-reconstructed for numeric kinds, host plane for
      strings; bit-exact vs the single-device program ops/sort._build_sorted)
      -> bucket ids -> ONE all_to_all routing each row to its owner device
      (bucket % n_devices) -> per-device sort by (valid desc, bucket, keys...,
      global row index).

    Carrying the global row index instead of payload columns keeps the
    exchange narrow: the host gathers arbitrary-typed payload rows by index
    afterwards, exactly like the single-device build's permutation fetch.
    Replaces the reference's cluster-wide ``repartition(numBuckets, cols)``
    (ref: HS/index/covering/CoveringIndex.scala:54-69).
    """
    import jax.numpy as jnp
    from jax import lax

    from hyperspace_tpu.ops.hashing import bucket_ids_jnp
    from hyperspace_tpu.ops.sort import _device_hash32, lex_argsort

    axis = mesh.axis_names[0]
    n_dev = mesh.shape[axis]
    n_keys = len(kinds)
    n_str = sum(1 for k in kinds if k == "s")

    def run(keys, host_hashes, row_idx, n_valid):
        valid = row_idx < n_valid

        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(axis),) * (n_keys + n_str + 2),
            out_specs=(P(axis), P(axis), P(axis), P(axis)),
        )
        def exchange(*args):
            ks = args[:n_keys]
            hh = args[n_keys : n_keys + n_str]
            ridx, vld = args[-2], args[-1]
            with jax.named_scope("hash"):
                hash_cols = []
                hidx = 0
                for kind, key in zip(kinds, ks):
                    if kind == "s":
                        hash_cols.append(hh[hidx])
                        hidx += 1
                    else:
                        hash_cols.append(_device_hash32(kind, key))
                buckets = bucket_ids_jnp(hash_cols, num_buckets).astype(jnp.int32)
            with jax.named_scope("exchange"):
                dest = (buckets % n_dev).astype(jnp.int32)
                staged, mask, counts = _stage_for_exchange(
                    [*ks, ridx, buckets], dest, n_dev, capacity, valid=vld
                )
                sent = jnp.minimum(counts, capacity)
                overflow = jnp.sum(counts - sent)
                outs, out_mask = _exchange_packed(staged, mask, axis)
            *out_keys, out_ridx, out_buckets = outs
            with jax.named_scope("sort"):
                order = lex_argsort(
                    [(~out_mask).astype(jnp.int32), out_buckets, *out_keys, out_ridx]
                )
            return (
                out_buckets[order],
                out_ridx[order],
                out_mask[order],
                overflow[None],
            )

        return exchange(*keys, *host_hashes, row_idx, valid)

    return jax.jit(_hlo_lint.named("index-build-exchange", run))


def distributed_bucket_sort_build(
    mesh: Mesh,
    keys: List["jax.Array"],
    host_hashes: List["jax.Array"],
    kinds: Tuple[str, ...],
    row_idx: "jax.Array",
    n_valid: int,
    num_buckets: int,
    capacity: int,
):
    """Run the distributed build step; see ``_build_exchange_program``.

    Inputs must be row-sharded over ``mesh`` and padded to a common length
    divisible by the device count; ``row_idx`` is the global row iota with
    padding rows >= ``n_valid`` (traced, so padding never recompiles).

    Returns device arrays ``(sorted_buckets, sorted_row_idx, valid, overflow)``
    each of per-device length ``n_devices * capacity``. Callers MUST check
    ``overflow.sum() == 0`` and retry with doubled capacity otherwise (the
    skew strategy — SURVEY.md §7 "hard parts").
    """
    ensure_x64()
    import numpy as np

    fn = _build_exchange_program(mesh, tuple(kinds), int(num_buckets), int(capacity))
    # no session conf reaches this layer: maybe_verify(None, ...) consults
    # the process-global default the most recent Session wired
    _hlo_lint.maybe_verify(
        None, "index-build-exchange",
        f"build-exchange[{num_buckets}/{capacity}]@{len(mesh.devices.flat)}",
        fn, (tuple(keys), tuple(host_hashes), row_idx, np.int64(n_valid)),
    )
    return fn(tuple(keys), tuple(host_hashes), row_idx, np.int64(n_valid))


def rebucket_hierarchical(
    mesh: Mesh,
    arrays: Dict[str, "jax.Array"],
    bucket_ids: "jax.Array",
    capacity_ici: int,
    capacity_dcn: int,
) -> Tuple[Dict[str, "jax.Array"], "jax.Array", "jax.Array", "jax.Array"]:
    """Two-phase re-bucketing over a 2-D (dcn, ici) mesh: rows first hop to
    their owner's *local position* within their own slice (all_to_all over
    ICI), then one hop across slices (all_to_all over DCN) — so each row
    crosses the slow inter-slice link exactly once and all position routing
    rides ICI (SURVEY.md §5.8 "cross-slice (DCN) handled by hierarchical
    all-to-all").

    Owner of bucket b on an (S, L) mesh: global device g = b % (S*L),
    slice s = g // L, local position l = g % L.

    Returns (out_arrays, out_buckets, valid_mask, overflow) per global device
    shard, like ``rebucket``; ``overflow`` sums drops from both phases.
    """
    ensure_x64()
    dcn_axis, ici_axis = mesh.axis_names
    S = mesh.shape[dcn_axis]
    L = mesh.shape[ici_axis]
    n_dev = S * L
    names = list(arrays)
    values = [arrays[n] for n in names]
    both = (dcn_axis, ici_axis)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(both),) * (len(values) + 1),
        out_specs=(P(both),) * (len(values) + 3),
    )
    def exchange(*args):
        *vals, buckets = args
        owner = (buckets % n_dev).astype(jnp.int32)

        # -- phase 1 (ICI): route to the owner's local position in this slice
        dest_local = owner % L
        staged, mask, counts = _stage_for_exchange([*vals, buckets], dest_local, L, capacity_ici)
        sent = jnp.minimum(counts, capacity_ici)
        overflow = jnp.sum(counts - sent)
        mid, mid_mask = _exchange_packed(staged, mask, ici_axis)

        # -- phase 2 (DCN): route to the owner slice; local position is kept
        *mid_vals, mid_buckets = mid
        dest_slice = ((mid_buckets % n_dev) // L).astype(jnp.int32)
        staged2, mask2, counts2 = _stage_for_exchange(
            [*mid_vals, mid_buckets], dest_slice, S, capacity_dcn, valid=mid_mask
        )
        sent2 = jnp.minimum(counts2, capacity_dcn)
        overflow = overflow + jnp.sum(counts2 - sent2)
        out, out_mask = _exchange_packed(staged2, mask2, dcn_axis)
        *out_vals, out_buckets = out
        return (*out_vals, out_buckets, out_mask, overflow[None])

    results = _hlo_lint.named("hierarchical-exchange", exchange)(*values, bucket_ids)
    out_arrays = dict(zip(names, results[: len(names)]))
    out_buckets, valid, overflow = results[len(names)], results[len(names) + 1], results[len(names) + 2]
    return out_arrays, out_buckets, valid, overflow
