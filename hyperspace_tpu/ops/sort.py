"""On-device bucketed-sort primitives — the index-build hot path.

Replaces the shuffle + per-partition sort of Spark's bucketed write
(``repartition(numBuckets, cols).sortWithinPartitions``;
ref: HS/index/covering/CoveringIndex.scala:54-69,
HS/index/DataFrameWriterExtensions.scala:50-68) with ONE fused XLA program:

  device hash -> bucket ids -> single multi-operand ``lax.sort``
  (bucket, key..., iota) -> permutation + per-bucket counts
  (counts via the Pallas histogram kernel, ops/kernels.bucket_histogram)

Design notes:
  - every operand is a *key* of the one ``lax.sort`` (iota last), so the
    order is total and no stable-sort or argsort-chaining passes are needed;
  - hash inputs for numeric/date columns are reconstructed ON DEVICE from the
    order-preserving sort keys (bit-exact vs the host ``numeric_hash32``), so
    only the key planes ride host->device; strings ship a host hash plane;
  - callers pad rows to a power of two and pass the true row count as a
    *traced* scalar — one compile serves every build of the same size class;
  - the permutation comes back as int32 and can be fetched asynchronously
    (``copy_to_host_async``) while the host prepares the gather.

int64 keys require x64; enabled lazily at first use via utils.x64.ensure_x64
so importing the library never mutates global JAX state (see
docs/configuration.md).
"""

from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp  # noqa: E402

from hyperspace_tpu.check import hlo_lint as _hlo_lint
from hyperspace_tpu.utils.x64 import ensure_x64
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402


def lex_argsort(keys) -> "jnp.ndarray":
    """Stable argsort by ``keys[0]`` then ``keys[1]`` ... (most-significant
    first), as one multi-operand XLA sort with a trailing iota tiebreak."""
    ensure_x64()
    keys = list(keys)
    n = keys[0].shape[0]
    idx = lax.iota(jnp.int32, n)
    return lax.sort((*keys, idx), num_keys=len(keys) + 1, is_stable=False)[-1]


@partial(jax.jit, static_argnames=("num_buckets",))
def bucket_sort_perm(hash_inputs, sort_keys, num_buckets: int):
    """Assign buckets and produce the permutation that clusters rows by bucket
    and sorts by the indexed columns within each bucket.

    Args:
      hash_inputs: (k, n) uint32 per-column hash inputs of the bucket keys.
      sort_keys:   (k, n) order-preserving keys of the sort columns.
      num_buckets: static bucket count.

    Returns:
      (perm, sorted_buckets): ``perm`` (n,) row permutation; ``sorted_buckets``
      (n,) the bucket id of each permuted row (non-decreasing).
    """
    ensure_x64()
    from hyperspace_tpu.ops.hashing import bucket_ids_jnp

    buckets = bucket_ids_jnp(list(hash_inputs), num_buckets)
    n = buckets.shape[0]
    idx = lax.iota(jnp.int32, n)
    out = lax.sort(
        (buckets, *list(sort_keys), idx),
        num_keys=2 + len(list(sort_keys)),
        is_stable=False,
    )
    return out[-1], out[0]


def _f64_hash_halves(khi, klo):
    """uint32 halves of a float64 ORDER KEY -> halves of the int64 the host
    ``numeric_hash32`` folds for that value: an integral float (|v| < 2^63)
    becomes its int64 value, -0.0 becomes +0.0, every NaN the canonical NaN,
    anything else its raw IEEE-754 bits.

    32-bit integer arithmetic on the bit fields only. The straightforward
    form (bitcast to float64, compare with ``floor``, convert, bitcast back)
    does not compile for the TPU: its 64-bit rewriting implements no
    bitcast-convert between f64 and s64."""
    u32 = jnp.uint32
    # invert the order-preserving transform back to the raw bits
    was_pos = khi >= u32(0x80000000)
    hi = jnp.where(was_pos, khi ^ u32(0x80000000), ~khi)
    lo = jnp.where(was_pos, klo, ~klo)

    neg = (hi >> u32(31)) == u32(1)
    exp = ((hi >> u32(20)) & u32(0x7FF)).astype(jnp.int32)
    frac_hi = hi & u32(0xFFFFF)
    is_zero = (exp == 0) & ((frac_hi | lo) == u32(0))
    is_nan = (exp == 0x7FF) & ((frac_hi | lo) != u32(0))

    # |v| = m * 2^(e - 52) with the 53-bit significand m = (m_hi, lo)
    e = exp - 1023
    m_hi = frac_hi | u32(0x100000)

    def sh(x):  # shift amounts are only used where the selecting branch holds
        return jnp.clip(x, 0, 31).astype(u32)

    # e <= 52: m >> k with k = 52 - e in [0, 52]; integral iff no bit drops
    k = 52 - e
    ones = u32(0xFFFFFFFF)
    lt32_lo = jnp.where(k == 0, lo, (lo >> sh(k)) | (m_hi << sh(32 - k)))
    lt32_hi = m_hi >> sh(k)
    lt32_drop = lo & ~(ones << sh(k))
    ge32_lo = m_hi >> sh(k - 32)
    ge32_drop = lo | (m_hi & ~(ones << sh(k - 32)))
    small = k >= 32
    right_hi = jnp.where(small, u32(0), lt32_hi)
    right_lo = jnp.where(small, ge32_lo, lt32_lo)
    right_drop = jnp.where(small, ge32_drop, lt32_drop)
    # 52 < e <= 62: m << j with j = e - 52 in [1, 10]; always integral
    j = e - 52
    left_hi = (m_hi << sh(j)) | (lo >> sh(32 - j))
    left_lo = lo << sh(j)

    shift_left = e > 52
    mag_hi = jnp.where(shift_left, left_hi, right_hi)
    mag_lo = jnp.where(shift_left, left_lo, right_lo)
    is_int = (e >= 0) & (e <= 62) & (shift_left | (right_drop == u32(0)))
    # int64(-|v|): two's complement across the halves
    neg_lo = ~mag_lo + u32(1)
    neg_hi = ~mag_hi + (mag_lo == u32(0)).astype(u32)
    int_hi = jnp.where(neg, neg_hi, mag_hi)
    int_lo = jnp.where(neg, neg_lo, mag_lo)

    out_hi = jnp.where(is_int, int_hi, jnp.where(is_nan, u32(0x7FF80000), hi))
    out_lo = jnp.where(is_int, int_lo, jnp.where(is_nan, u32(0), lo))
    return jnp.where(is_zero, u32(0), out_hi), jnp.where(is_zero, u32(0), out_lo)


def _device_hash32(kind: str, key):
    """Reconstruct the column's uint32 hash input from its order key —
    bit-exact vs the host ``hashing.numeric_hash32`` on the original values,
    INCLUDING its int/float value normalization (an integral float hashes
    as its int64 value; -0.0 as +0.0; NaN canonically): a nullable int64
    column decodes as float64, and the un-normalized bit-pattern hash once
    bucketed it apart from the int64 side of the same join."""
    bits = lax.bitcast_convert_type(key.astype(jnp.int64), jnp.uint64)
    hi = (bits >> jnp.uint64(32)).astype(jnp.uint32)
    lo = (bits & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    if kind == "f":
        hi, lo = _f64_hash_halves(hi, lo)
    # i / u / b / M — the key IS the value (or its int64 view)
    return hi ^ lo


def _build_sorted(keys, host_hashes, n_valid, num_buckets: int, kinds, interpret: bool):
    from hyperspace_tpu.ops.hashing import bucket_ids_jnp
    from hyperspace_tpu.ops.kernels import _hist_call

    with jax.named_scope("hash"):
        hash_cols = []
        hidx = 0
        for kind, key in zip(kinds, keys):
            if kind == "s":
                hash_cols.append(host_hashes[hidx])
                hidx += 1
            else:
                hash_cols.append(_device_hash32(kind, key))
        buckets = bucket_ids_jnp(hash_cols, num_buckets)

    n = buckets.shape[0]
    idx = lax.iota(jnp.int32, n)
    # padding rows get the sentinel bucket ``num_buckets`` so they cluster
    # after every real bucket and fall outside the returned counts
    buckets = jnp.where(idx < n_valid, buckets, jnp.int32(num_buckets))
    with jax.named_scope("sort"):
        out = lax.sort((buckets, *keys, idx), num_keys=2 + len(keys), is_stable=False)
    sorted_buckets, perm = out[0], out[-1]

    nb_p = -(-(num_buckets + 1) // 128) * 128
    with jax.named_scope("histogram"):
        counts = _hist_call(sorted_buckets[None, :], nb_p, interpret)[:, 0]
    return perm, counts[:num_buckets]


_hlo_lint.register_contract(
    "index-build",
    collectives={},
    description=(
        "single-device index build: device hash, one multi-operand sort by "
        "(bucket, keys), Pallas bucket histogram; permutation and counts out"
    ),
)
# the executable is jit_hs_index_build in the profiler's module line
_build_sorted = jax.jit(
    _hlo_lint.named("index-build", _build_sorted),
    static_argnames=("num_buckets", "kinds", "interpret"),
)


def bucket_sort_build(
    keys: Sequence,
    host_hashes: Sequence,
    kinds: Tuple[str, ...],
    num_buckets: int,
    n_valid: int,
):
    """The full device program of an index build over padded inputs.

    Args:
      keys: per-key-column 1-D device arrays (int32 or int64 order keys),
        all the same power-of-two length, padded past ``n_valid``.
      host_hashes: uint32 hash planes for the ``kinds == 's'`` columns, in
        order of appearance.
      kinds: per-column dtype kind characters (``i u b M f s``), static.
      num_buckets: static bucket count.
      n_valid: true row count (traced — padding amount never recompiles).

    Returns:
      (perm, counts) device arrays: int32 permutation of all padded rows
      (valid rows occupy positions [0, n_valid)) and int32 rows-per-bucket.
    """
    ensure_x64()
    from hyperspace_tpu.ops.kernels import _use_interpret

    return _build_sorted(
        tuple(keys), tuple(host_hashes), np.int32(n_valid), num_buckets, tuple(kinds),
        _use_interpret(),
    )


def padded_size(n: int) -> int:
    """Power-of-two size class for ``n`` rows (min 8)."""
    return max(8, 1 << (max(n - 1, 1)).bit_length())


# --------------------------------------------------------------------------
# streaming device top-k (ORDER BY ... LIMIT k without materialization)
#
# Both programs operate on a (num_keys + 1, P) int64 "plane matrix": one
# signed-order NULLS-LAST plane per ORDER BY key (ops/encode.order_plane)
# plus a trailing global-row-id plane that makes the sort total — equal keys
# resolve by ascending row id, which IS the host stable-sort tie order.
# Padding rows carry ORDER_PLANE_SENTINEL in every plane (including the row
# id), so they cluster after all real rows and the host trims them by
# ``rid < sentinel``. No traced scalars: one compile per (key count,
# capacity, shape-bucket) triple, shared across every chunk of a stream.
# --------------------------------------------------------------------------

_TOPK_SENTINEL = np.int64(np.iinfo(np.int64).max)


def _take_cap(col, cap: int, sentinel):
    """First ``cap`` entries, sentinel-extended when the input is shorter
    (static shapes: the pad amount is a trace-time constant)."""
    p = col.shape[0]
    if p >= cap:
        return col[:cap]
    return jnp.concatenate([col, jnp.full(cap - p, sentinel, dtype=col.dtype)])


def topk_chunk_fn(num_keys: int, cap: int):
    """Builder for the per-chunk select-top-k program: one multi-operand
    ``lax.sort`` over the plane matrix, then the first ``cap`` rows of every
    plane. Returns a (num_keys + 1, cap) candidate matrix."""

    def run(planes):
        ensure_x64()
        ops = tuple(planes[i] for i in range(num_keys + 1))
        out = lax.sort(ops, num_keys=num_keys + 1, is_stable=False)
        return jnp.stack([_take_cap(o, cap, _TOPK_SENTINEL) for o in out])

    return run


def topk_merge_fn(num_keys: int, cap: int):
    """Builder for the pairwise candidate merge: concatenate two capacity-
    sized candidate matrices, sort, keep the first ``cap`` — the device-
    resident fold step of TopKStream (GroupedAggStream._merge analog)."""

    def run(a, b):
        ensure_x64()
        ops = tuple(
            jnp.concatenate([a[i], b[i]]) for i in range(num_keys + 1)
        )
        out = lax.sort(ops, num_keys=num_keys + 1, is_stable=False)
        return jnp.stack([o[:cap] for o in out])

    return run


# --- declared HLO contracts (hyperspace_tpu/check/hlo_lint.py), stated next
# to the program builders like exec/device.py's families ---------------------
_hlo_lint.register_contract(
    "topk-chunk",
    collectives={"all-gather": (0, None)},
    description=(
        "chunk select-top-k: one multi-operand sort over key planes; the "
        "GSPMD partitioner may gather fixed-size planes, never payload rows"
    ),
)
_hlo_lint.register_contract(
    "topk-merge",
    collectives={},
    description="pairwise top-k candidate merge: 2*cap fixed-size inputs, device-local, collective-free",
)
_hlo_lint.register_contract(
    "sharded-topk",
    collectives={"all-gather": (1, 1)},
    description=(
        "shard_map top-k chunk: per-shard select + EXACTLY one fixed-size "
        "all-gather of candidate planes (never rows), replicated final merge"
    ),
)
