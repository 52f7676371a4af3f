"""Pallas TPU kernels for the framework's hot device ops.

Two kernels back the build paths and one the keyed grouped aggregate (guide:
/opt/skills/guides/pallas_guide.md):

- ``segmented_min_max`` — one-pass fused min+max over a (segments, width)
  matrix, the device program behind MinMaxSketch builds: one row per source
  file, padded to a rectangle, both aggregates in a single VMEM sweep
  (replaces the reference's per-file Spark aggregate jobs,
  ref: HS/index/dataskipping/sketch/MinMaxSketch.scala:33-43).
- ``bucket_histogram`` — rows-per-bucket counts for write planning and skew
  detection in the bucketed index build (the device analogue of counting
  Spark's shuffle partition sizes; ref: HS/index/covering/CoveringIndex.scala:54-69).
- ``copy_blocks`` — the numbered blocks of several one-dimensional arrays,
  copied out by DMA from where the arrays lie: the skip step of
  ``grouped-agg-keyed`` (exec/device.py), which reads only the blocks that
  hold a selected row.

On the ``cpu`` platform (tests, virtual meshes) the kernels run in interpreter
mode with identical numerics; every other platform compiles them through
Mosaic or fails.
"""

from __future__ import annotations

from functools import partial

import jax

from hyperspace_tpu.utils.x64 import ensure_x64


import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUBLANES = 8


def _use_interpret() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# segmented min/max
# ---------------------------------------------------------------------------
#
# Mosaic has no 64-bit types, so the kernel never sees f64: values are encoded
# host-side as order-preserving uint64 keys, split into two bias-corrected
# int32 planes (hi, lo), and the kernel keeps running lexicographic minima and
# maxima of (hi, lo) pairs — exact for the full f64 range. A third int32 plane
# masks padding / SQL nulls.

_I32_MAX = np.int32(2**31 - 1)
_I32_MIN = np.int32(-(2**31))


def _f64_to_orderable_u64(v: np.ndarray) -> np.ndarray:
    """Monotone f64 -> uint64 (NaNs must be excluded by the caller). The
    extreme keys 0 and 2**64-1 are unreachable (they'd require NaN bit
    patterns), so they are safe identity sentinels."""
    bits = np.ascontiguousarray(v, dtype=np.float64).view(np.uint64)
    neg = (bits >> np.uint64(63)).astype(bool)
    return np.where(neg, ~bits, bits | np.uint64(0x8000000000000000))


def _orderable_u64_to_f64(key: np.ndarray) -> np.ndarray:
    was_pos = (key >> np.uint64(63)).astype(bool)
    bits = np.where(was_pos, key & np.uint64(0x7FFFFFFFFFFFFFFF), ~key)
    return bits.view(np.float64)


def _split_hi_lo(key: np.ndarray):
    """uint64 -> (hi, lo) int32 planes whose signed lexicographic order equals
    the unsigned uint64 order (both halves are bias-flipped)."""
    hi = ((key >> np.uint64(32)).astype(np.uint32) ^ np.uint32(0x80000000)).view(np.int32)
    lo = ((key & np.uint64(0xFFFFFFFF)).astype(np.uint32) ^ np.uint32(0x80000000)).view(np.int32)
    return hi, lo


def _join_hi_lo(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    h = (hi.view(np.uint32) ^ np.uint32(0x80000000)).astype(np.uint64)
    l = (lo.view(np.uint32) ^ np.uint32(0x80000000)).astype(np.uint64)
    return (h << np.uint64(32)) | l


def _lex_fold_min(run_h, run_l, cand_h, cand_l):
    """Merge a (rows,1) candidate pair into the running lexicographic min."""
    nh = jnp.minimum(run_h, cand_h)
    l1 = jnp.where(run_h == nh, run_l, _I32_MAX)
    l2 = jnp.where(cand_h == nh, cand_l, _I32_MAX)
    return nh, jnp.minimum(l1, l2)


def _lex_fold_max(run_h, run_l, cand_h, cand_l):
    nh = jnp.maximum(run_h, cand_h)
    l1 = jnp.where(run_h == nh, run_l, _I32_MIN)
    l2 = jnp.where(cand_h == nh, cand_l, _I32_MIN)
    return nh, jnp.maximum(l1, l2)


def _minmax_kernel(h_ref, l_ref, m_ref, minh_ref, minl_ref, maxh_ref, maxl_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        minh_ref[:] = jnp.full_like(minh_ref, _I32_MAX)
        minl_ref[:] = jnp.full_like(minl_ref, _I32_MAX)
        maxh_ref[:] = jnp.full_like(maxh_ref, _I32_MIN)
        maxl_ref[:] = jnp.full_like(maxl_ref, _I32_MIN)

    valid = m_ref[:] != 0
    hi = h_ref[:]
    lo = l_ref[:]

    # -- tile-local lexicographic min over the lane axis --
    hi_mn = jnp.where(valid, hi, _I32_MAX)
    lo_mn = jnp.where(valid, lo, _I32_MAX)
    th = jnp.min(hi_mn, axis=1, keepdims=True)
    tl = jnp.min(jnp.where(hi_mn == th, lo_mn, _I32_MAX), axis=1, keepdims=True)
    nh, nl = _lex_fold_min(minh_ref[:], minl_ref[:], th, tl)
    minh_ref[:] = nh
    minl_ref[:] = nl

    # -- tile-local lexicographic max --
    hi_mx = jnp.where(valid, hi, _I32_MIN)
    lo_mx = jnp.where(valid, lo, _I32_MIN)
    th = jnp.max(hi_mx, axis=1, keepdims=True)
    tl = jnp.max(jnp.where(hi_mx == th, lo_mx, _I32_MIN), axis=1, keepdims=True)
    nh, nl = _lex_fold_max(maxh_ref[:], maxl_ref[:], th, tl)
    maxh_ref[:] = nh
    maxl_ref[:] = nl


@partial(jax.jit, static_argnames=("interpret",))
def _minmax_call(hi, lo, mask, interpret: bool):
    n_seg, width = hi.shape
    row_tile = _SUBLANES
    col_tile = min(width, 512)
    grid = (n_seg // row_tile, width // col_tile)
    blk = pl.BlockSpec((row_tile, col_tile), lambda i, j: (i, j), memory_space=pltpu.VMEM)
    out_blk = pl.BlockSpec((row_tile, 1), lambda i, j: (i, j - j), memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _minmax_kernel,
        grid=grid,
        in_specs=[blk, blk, blk],
        out_specs=[out_blk] * 4,
        out_shape=[jax.ShapeDtypeStruct((n_seg, 1), jnp.int32)] * 4,
        interpret=interpret,
    )(hi, lo, mask)


# Cap on padded (rows x width) elements per device call; segments are split /
# grouped so one huge file can never force a dense n_files x max_rows matrix.
_MINMAX_CALL_ELEMS = 1 << 23


def segmented_min_max(segments):
    """Per-segment (min, max) of variable-length numeric segments.

    ``segments`` is a list of 1-D numpy arrays (one per source file). NaNs
    (SQL nulls) are ignored, matching Min/Max aggregate semantics. Returns
    (mins, maxs) as float64 numpy arrays of length ``len(segments)``;
    all-null/empty segments yield (nan, nan). Exact over the full f64 range
    (the kernel compares order-preserving 2x-int32 keys, not floats).

    Memory-bounded: oversized segments are split into pieces and pieces are
    batched into device calls of at most ``_MINMAX_CALL_ELEMS`` padded
    elements; per-piece results fold together exactly on the host (each piece
    result is already an exact element of the segment).
    """
    ensure_x64()
    n = len(segments)
    if n == 0:
        return np.empty(0), np.empty(0)

    max_piece = _MINMAX_CALL_ELEMS // _SUBLANES
    pieces = []  # (orig_idx, 1-D array)
    for i, s in enumerate(segments):
        s = np.asarray(s)
        if s.shape[0] <= max_piece:
            pieces.append((i, s))
        else:
            for off in range(0, s.shape[0], max_piece):
                pieces.append((i, s[off : off + max_piece]))

    mins = np.full(n, np.nan)
    maxs = np.full(n, np.nan)
    group: list = []
    group_w = 1

    def flush() -> None:
        nonlocal group, group_w
        if not group:
            return
        g_mins, g_maxs = _minmax_rect([p for _, p in group])
        for (idx, _), mn, mx in zip(group, g_mins, g_maxs):
            mins[idx] = np.fmin(mins[idx], mn)
            maxs[idx] = np.fmax(maxs[idx], mx)
        group, group_w = [], 1

    for idx, p in pieces:
        w = max(int(p.shape[0]), 1)
        new_w = max(group_w, w)
        rows = -(-(len(group) + 1) // _SUBLANES) * _SUBLANES
        if group and rows * new_w > _MINMAX_CALL_ELEMS:
            flush()
            new_w = w
        group.append((idx, p))
        group_w = new_w
    flush()
    return mins, maxs


def _minmax_rect(segments):
    """One dense (padded) device call. Internal; see ``segmented_min_max``."""
    n = len(segments)
    width = max(max((s.shape[0] for s in segments), default=1), 1)
    rows = -(-n // _SUBLANES) * _SUBLANES
    col_tile = min(512, -(-width // _LANES) * _LANES)
    width_p = -(-width // col_tile) * col_tile
    hi = np.zeros((rows, width_p), dtype=np.int32)
    lo = np.zeros((rows, width_p), dtype=np.int32)
    mask = np.zeros((rows, width_p), dtype=np.int32)
    for i, s in enumerate(segments):
        v = np.asarray(s, dtype=np.float64)
        ok = ~np.isnan(v)
        v = v[ok]
        if v.shape[0] == 0:
            continue
        h, l = _split_hi_lo(_f64_to_orderable_u64(v))
        hi[i, : v.shape[0]] = h
        lo[i, : v.shape[0]] = l
        mask[i, : v.shape[0]] = 1
    minh, minl, maxh, maxl = _minmax_call(
        jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(mask), _use_interpret()
    )
    minh = np.asarray(minh)[:n, 0]
    minl = np.asarray(minl)[:n, 0]
    maxh = np.asarray(maxh)[:n, 0]
    maxl = np.asarray(maxl)[:n, 0]
    mins = _orderable_u64_to_f64(_join_hi_lo(minh, minl))
    maxs = _orderable_u64_to_f64(_join_hi_lo(maxh, maxl))
    # rows that stayed at the identity sentinels had no valid values at all
    empty = (minh == _I32_MAX) & (minl == _I32_MAX)
    mins = np.where(empty, np.nan, mins)
    maxs = np.where(empty, np.nan, maxs)
    return mins, maxs


# ---------------------------------------------------------------------------
# bucket histogram
# ---------------------------------------------------------------------------


def _hist_kernel(b_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    buckets = b_ref[:]  # (1, tile)
    nb = out_ref.shape[0]
    # one-hot compare against all bucket ids via a 2-D iota whose rows are the
    # ids; (1, tile) broadcasts over rows, the lane-axis reduce yields (nb, 1).
    # (No reindexing/transpose — Mosaic rejects gather-style relayouts.)
    ids = jax.lax.broadcasted_iota(jnp.int32, (nb, buckets.shape[1]), 0)
    eq = (ids == buckets).astype(jnp.int32)  # (nb, tile)
    # dtype pinned: with x64 enabled jnp.sum would promote to (Mosaic-less) i64
    out_ref[:] = out_ref[:] + jnp.sum(eq, axis=1, keepdims=True, dtype=jnp.int32)


@partial(jax.jit, static_argnames=("num_buckets", "interpret"))
def _hist_call(buckets, num_buckets: int, interpret: bool):
    n = buckets.shape[1]
    tile = min(n, 2048)
    grid = (n // tile,)
    return pl.pallas_call(
        _hist_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((1, tile), lambda i: (i - i, i), memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((num_buckets, 1), lambda i: (i - i, i - i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((num_buckets, 1), jnp.int32),
        interpret=interpret,
    )(buckets)


def bucket_histogram(bucket_ids, num_buckets: int):
    """Rows per bucket. ``bucket_ids`` is a 1-D int array (host or device);
    out-of-range ids land in no bucket. Returns int32 numpy array (num_buckets,)."""
    ensure_x64()
    b = np.asarray(bucket_ids, dtype=np.int32)
    n = b.shape[0]
    if n == 0:
        return np.zeros(num_buckets, dtype=np.int32)
    tile = min(max(n, 1), 2048)
    n_p = -(-n // tile) * tile
    padded = np.full((1, n_p), -1, dtype=np.int32)  # -1 matches no bucket id
    padded[0, :n] = b
    nb_p = -(-num_buckets // _LANES) * _LANES
    out = _hist_call(jnp.asarray(padded), nb_p, _use_interpret())
    return np.asarray(out)[:num_buckets, 0]


# ---------------------------------------------------------------------------
# numbered blocks out of one-dimensional arrays
# ---------------------------------------------------------------------------
#
# The arrays stay in HBM as they are, whole and one-dimensional: no slice, no
# reshape, nothing of them is read but the blocks asked for. Block ``i`` of
# every output is block ``numbers[i]`` of its array, one DMA a block and an
# array, HBM to HBM, up to ``_COPY_WINDOW`` blocks in flight (a semaphore a
# copy in flight, and the chip holds about 500 of them). ``block`` is a
# multiple of the 1,024 elements of a one-dimensional 32-bit tile, so every
# copy starts and ends on a tile; Mosaic has no 64-bit types, so an 8-byte
# array goes through the interpreter only (the CPU, where columns stay whole)
# and every index is pinned to int32 (x64 is on: a Python int would be int64).

_COPY_WINDOW = 16
_COPY_SEMAPHORES = 384


def _copy_blocks_kernel(numbers_ref, *refs, n_arrays: int, block: int):
    srcs, dsts, sems = refs[:n_arrays], refs[n_arrays : 2 * n_arrays], refs[2 * n_arrays]
    n = numbers_ref.shape[0]
    in_flight = sems.shape[0]
    window = jnp.int32(in_flight)

    def copies(i):
        start = pl.multiple_of(numbers_ref[i] * jnp.int32(block), block)
        to = pl.multiple_of(i * jnp.int32(block), block)
        return [
            pltpu.make_async_copy(src.at[pl.ds(start, block)], dst.at[pl.ds(to, block)], sems.at[i % window, jnp.int32(a)])
            for a, (src, dst) in enumerate(zip(srcs, dsts))
        ]

    def start(i, carry):
        @pl.when(i >= window)
        def _():
            for c in copies(i - window):  # its slot's semaphores are taken next
                c.wait()

        for c in copies(i):
            c.start()
        return carry

    def drain(i, carry):
        for c in copies(i):
            c.wait()
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(n), start, jnp.int32(0))
    jax.lax.fori_loop(jnp.int32(max(0, n - in_flight)), jnp.int32(n), drain, jnp.int32(0))


def copy_blocks(numbers, arrays, block: int):
    """``[a.reshape(-1, block)[numbers].reshape(-1) for a in arrays]`` for
    one-dimensional device arrays of one length, without laying any of them
    out in blocks: traced, one kernel launch for all of them. ``numbers`` is
    int32, each below ``len(a) // block``."""
    arrays = tuple(arrays)
    in_flight = max(1, min(_COPY_WINDOW, _COPY_SEMAPHORES // len(arrays)))
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        partial(_copy_blocks_kernel, n_arrays=len(arrays), block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[any_space] * len(arrays),
            out_specs=[any_space] * len(arrays),
            scratch_shapes=[pltpu.SemaphoreType.DMA((in_flight, len(arrays)))],
        ),
        out_shape=[jax.ShapeDtypeStruct((numbers.shape[0] * block,), a.dtype) for a in arrays],
        interpret=_use_interpret(),
        name="hs_copy_blocks",
    )(numbers, *arrays)
