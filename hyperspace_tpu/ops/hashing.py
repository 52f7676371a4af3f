"""Bucket hashing — identical on host (numpy) and device (jnp).

The bucket assignment ``bucket = mix(key columns) % num_buckets`` must agree
between index build, query-time bucket pruning (hash the filter literal), and
hybrid-scan re-bucketing of appended rows — these are three call sites of one
function, so both backends share the same 32-bit finalizer arithmetic.

Plays the role of Spark's ``HashPartitioning`` over bucket columns
(ref: HS/index/covering/CoveringIndex.scala:54-69 repartition;
HS/index/covering/CoveringIndexRuleUtils.scala:357-417 on-the-fly re-bucketing).
"""

from __future__ import annotations

import hashlib
from typing import Optional, Sequence

import numpy as np

_C1 = np.uint32(0x85EBCA6B)
_C2 = np.uint32(0xC2B2AE35)
_SEED = np.uint32(0x9747B28C)


def _mix32_np(h):
    h = h ^ (h >> np.uint32(16))
    h = h * _C1
    h = h ^ (h >> np.uint32(13))
    h = h * _C2
    h = h ^ (h >> np.uint32(16))
    return h


def _mix32_jnp(h):
    import jax.numpy as jnp

    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


def combine_hashes_np(cols: Sequence[np.ndarray]) -> np.ndarray:
    """Combine per-column uint32 hash inputs into one row hash."""
    with np.errstate(over="ignore"):
        h = np.full(cols[0].shape, _SEED, dtype=np.uint32)
        for i, c in enumerate(cols):
            h = _mix32_np(h ^ _mix32_np(c.astype(np.uint32) + np.uint32((i * 0x9E3779B9) & 0xFFFFFFFF)))
        return h


def combine_hashes_jnp(cols) -> "jnp.ndarray":  # noqa: F821
    import jax.numpy as jnp

    h = jnp.full(cols[0].shape, jnp.uint32(0x9747B28C), dtype=jnp.uint32)
    for i, c in enumerate(cols):
        h = _mix32_jnp(h ^ _mix32_jnp(c.astype(jnp.uint32) + jnp.uint32((i * 0x9E3779B9) & 0xFFFFFFFF)))
    return h


def bucket_ids_np(hash_inputs: Sequence[np.ndarray], num_buckets: int) -> np.ndarray:
    return (combine_hashes_np(hash_inputs) % np.uint32(num_buckets)).astype(np.int32)


def bucket_ids_jnp(hash_inputs, num_buckets: int):
    import jax.numpy as jnp

    return (combine_hashes_jnp(hash_inputs) % jnp.uint32(num_buckets)).astype(jnp.int32)


def string_hash32(value: str) -> np.uint32:
    """Stable 32-bit hash input for a string value (md5-derived; the per-row
    hash then mixes it like any numeric input)."""
    digest = hashlib.md5(str(value).encode("utf-8")).digest()
    return np.uint32(int.from_bytes(digest[:4], "little"))


_NULL_STRING_SENTINEL = "\x00__hs_null__"


def string_hash32_array(values: np.ndarray) -> np.ndarray:
    """Vectorized over uniques: factorize, hash each unique once, gather.
    Nulls hash via a fixed sentinel so build-time and query-time bucket
    assignment agree."""
    from hyperspace_tpu.ops.encode import factorize_strings

    codes, uniques, null_mask = factorize_strings(values)
    table = np.array([string_hash32(u) for u in uniques], dtype=np.uint32)
    out = np.where(null_mask, string_hash32(_NULL_STRING_SENTINEL), table[np.clip(codes, 0, None)])
    return out.astype(np.uint32)


def numeric_hash32(arr: np.ndarray) -> np.ndarray:
    """uint32 hash input for numeric/datetime columns: fold the int64 bit
    pattern to 32 bits.

    VALUE-consistent across integer and float representations: a float that
    holds an integral value hashes as that int64 (3.0 hashes like 3), -0.0
    normalizes to +0.0, and NaN hashes via the canonical NaN pattern. This
    matters because a nullable int64 parquet column decodes as float64 —
    without normalization the SAME key value lands in different buckets on
    the two sides of a join (or between an int literal and the stored
    column), silently dropping matches. Mirrored bit-exactly on device in
    ops/sort._device_hash32."""
    if arr.dtype.kind == "f":
        with np.errstate(invalid="ignore"):
            v = arr.astype(np.float64) + 0.0  # -0.0 -> +0.0
            # < 2^63 strictly: every such integral float casts to int64
            # exactly (float64 granularity near 2^63 is 1024). Above 2^53
            # the FLOAT side has already rounded the value at decode, so
            # cross-representation consistency is inherently bounded by
            # float64 exactness — the guarantee here covers every integral
            # value float64 can represent.
            isint = np.isfinite(v) & (np.abs(v) < 2.0**63) & (v == np.floor(v))
            int_bits = np.where(isint, v, 0).astype(np.int64).view(np.uint64)
            f_norm = np.where(np.isnan(v), np.float64("nan"), v)
            bits = np.where(isint, int_bits, f_norm.view(np.uint64))
    elif arr.dtype.kind == "M":
        bits = arr.view("int64").astype(np.uint64)
    elif arr.dtype.kind == "b":
        bits = arr.astype(np.uint64)
    else:
        bits = arr.astype(np.int64).view(np.uint64)
    return ((bits ^ (bits >> np.uint64(32))) & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def bucket_of_key_literal(value, kind: str, num_buckets: int) -> Optional[int]:
    """Bucket that holds every row whose single bucket column equals
    ``value``, or None where that cannot be told. The literal is hashed as
    the comparison sees it (``plan/expr._coerce_compare``), which needs the
    column's ``kind``: ``"num"`` (integer, float, boolean: the hash is
    value-consistent across them), ``"str"``, or a ``datetime64[unit]``
    dtype string (a date string or a coarser/finer datetime is cast to the
    column's unit first, as the comparison casts it). A NULL, a NaT, or a
    literal of a kind the column is not compared in as-is gives None
    (ref: FilterIndexRule useBucketSpec, HS/index/covering/FilterIndexRule.scala:162-167)."""
    if value is None:
        return None
    if kind == "str":
        if not isinstance(value, str):
            return None
        h = string_hash32(value)
    elif kind == "num":
        if not isinstance(value, (bool, int, float, np.number, np.bool_)):
            return None
        h = numeric_hash32(np.asarray([value]))[0]
    else:
        try:
            arr = np.asarray([value]).astype(np.dtype(kind))
        except (TypeError, ValueError, OverflowError):
            return None
        if np.isnat(arr[0]):
            return None
        h = numeric_hash32(arr)[0]
    return int(bucket_ids_np([np.asarray([h], dtype=np.uint32)], num_buckets)[0])
