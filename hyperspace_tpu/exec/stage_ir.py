"""Whole-plan fusion: stage-IR nodes, the donation-aware stage compiler, and
the fused join→aggregate pipeline stage.

The per-family device programs in ``exec/device.py`` stitch a streamed
chunk's Filter→Project→Join-probe→Agg/TopK chain together with host Python:
every seam pays a dispatch, a device round-trip, and fresh buffers for the
fold state. This module is the composable alternative:

* **Stage IR** — a chunk pipeline is described as a :class:`StagePlan` of
  frozen op nodes (:class:`FilterOp`, :class:`ProjectOp`,
  :class:`JoinProbeOp`, :class:`GroupAggOp`, :class:`TopKOp`). The plan's
  ``skeleton()`` is the program-cache identity: ONE jitted executable per
  (pipeline skeleton, shape bucket, mesh fingerprint), exactly like
  ``device._program_key`` but spanning the whole stage instead of one
  family.

* **Donation-aware program cache** — :func:`compile_stage` is
  ``device._cached_predicate_jit`` plus ``donate_argnums``: streamed fold
  states (the grouped-agg partial table, the top-k candidate matrix, the
  join candidate index buffers) are donated to XLA so the update happens in
  place instead of reallocating every chunk. The donation vector is part of
  the cache key — flipping ``hyperspace.exec.fusion.donation`` never aliases
  executables.

* **Fused join→aggregate stage** — :func:`fused_join_agg_program` compiles
  hash-probe span walk, capacity-bounded pair expansion, exact key
  verification, the post-join predicate, the grouped segment reduction AND
  the running-state merge into one XLA program; :func:`stream_join_aggregate`
  drives it over a broadcast join's probe stream. Capacity overflows (pair
  count or group cardinality beyond the compiled buckets) are detected *in
  program*: the donated state round-trips unchanged (`jnp.where` selects the
  original state into the aliased outputs) and the chunk is redone on the
  per-family path, counted as ``hs_device_fallback_total{op="fusion"}``.

Everything here is gated behind ``hyperspace.exec.fusion.enabled`` and
byte-identical to the per-family path (proved by tests/test_fusion.py); the
per-family path remains both the default and the fallback.
"""

from __future__ import annotations

import sys
from collections import OrderedDict
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from hyperspace_tpu.check import hlo_lint as _hlo_lint
from hyperspace_tpu.obs.metrics import REGISTRY

# --------------------------------------------------------------------------
# conf gates
# --------------------------------------------------------------------------


def fusion_wanted(conf) -> bool:
    """Whole-plan fusion master switch (``hyperspace.exec.fusion.enabled``)."""
    try:
        return bool(conf.fusion_enabled)
    except Exception:
        return False


def donation_wanted(conf) -> bool:
    """Fold-state donation, consulted only when fusion is on."""
    try:
        return bool(conf.fusion_enabled) and bool(conf.fusion_donation)
    except Exception:
        return False


# --------------------------------------------------------------------------
# observability: dispatch counts and the device high-water mark
# --------------------------------------------------------------------------


def count_dispatch(program: str) -> None:
    """Count one jitted device-program dispatch. Called at EVERY jitted call
    site (per-family and fused), so the fusion win is measurable as a
    dispatch-count delta, not just wall clock."""
    from hyperspace_tpu.obs.metrics import REGISTRY

    REGISTRY.counter(
        "hs_device_dispatches_total",
        "Jitted device-program dispatches, by program family",
        program=program,
    ).inc()


def device_peak_bytes() -> Optional[int]:
    """The allocator's own high-water mark: the largest ``peak_bytes_in_use``
    of ``memory_stats()`` over this process's devices. None where the backend
    keeps no such statistic (the CPU backend), and in a process whose JAX
    backend is not up yet — reading a gauge must not be what claims the chip."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


REGISTRY.gauge(
    "hs_device_peak_bytes",
    "High-water bytes in use on a device, from the allocator's memory_stats() "
    "at read time; absent where the backend reports none",
    fn=device_peak_bytes,
)


# --------------------------------------------------------------------------
# stage IR
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class FilterOp:
    """Fused predicate over the chunk (skeleton = structure + column kinds,
    literal-free: same identity discipline as ``predicate_skeleton``)."""

    skeleton: str

    def token(self) -> str:
        return f"F({self.skeleton})"


@dataclass(frozen=True)
class ProjectOp:
    columns: Tuple[str, ...]

    def token(self) -> str:
        return f"P({','.join(self.columns)})"


@dataclass(frozen=True)
class JoinProbeOp:
    """Broadcast hash-probe against a resident build table: span walk +
    bounded pair expansion + exact key verification, ``pair_cap`` pairs."""

    n_keys: int
    pair_cap: int

    def token(self) -> str:
        return f"J(k{self.n_keys}:p{self.pair_cap})"


@dataclass(frozen=True)
class GroupAggOp:
    """Grouped segment reduction folded into a donated running partial."""

    key_specs: Tuple[Tuple[str, str], ...]  # (column, 'i'|'f')
    slot_specs: Tuple[Tuple[str, Optional[str], bool], ...]
    cap: int

    def token(self) -> str:
        k = ",".join(f"{n}:{t}" for n, t in self.key_specs)
        s = ",".join(f"{kind}:{c}:{int(i)}" for kind, c, i in self.slot_specs)
        return f"G[{self.cap}](k:{k}|s:{s})"


@dataclass(frozen=True)
class TopKOp:
    """Chunk top-k select merged into a donated candidate matrix."""

    num_keys: int
    cap: int

    def token(self) -> str:
        return f"T(k{self.num_keys}:c{self.cap})"


@dataclass(frozen=True)
class StagePlan:
    """One streamed pipeline stage: the ordered op chain a chunk flows
    through. ``skeleton()`` is the whole-stage program identity — the string
    ``device._program_key`` combines with the mesh fingerprint, while the
    shape bucket stays the jit cache's own shape signature."""

    ops: Tuple[object, ...]

    def skeleton(self) -> str:
        return "fuse[" + ">".join(op.token() for op in self.ops) + "]"


# --------------------------------------------------------------------------
# donation-aware program cache
# --------------------------------------------------------------------------

_STAGE_CACHE: "OrderedDict[tuple, object]" = OrderedDict()
_STAGE_CACHE_MAX = 256


def compile_stage(skeleton: str, fn, *, donate_argnums: Tuple[int, ...] = (),
                  family: Optional[str] = None):
    """``device._cached_predicate_jit`` with a donation vector: one jitted
    stage program per (skeleton, donate_argnums). Donated positional args
    hand their buffers to XLA for output aliasing — callers MUST NOT touch a
    donated argument after the call (the ``donated-buffer-reuse`` lint rule
    enforces this repo-wide) and rebind their state to the returned arrays
    instead. ``family`` names the executable ``jit_hs_<family>``."""
    import jax

    donate = tuple(int(i) for i in donate_argnums)
    key = (skeleton, donate)
    jitted = _STAGE_CACHE.get(key)
    if jitted is None:
        while len(_STAGE_CACHE) >= _STAGE_CACHE_MAX:
            _STAGE_CACHE.popitem(last=False)
        if family is not None:
            fn = _hlo_lint.named(family, fn)
        jitted = jax.jit(fn, donate_argnums=donate) if donate else jax.jit(fn)
        _STAGE_CACHE[key] = jitted
    else:
        _STAGE_CACHE.move_to_end(key)
    return jitted


def clear_stage_cache() -> None:
    _STAGE_CACHE.clear()


# --------------------------------------------------------------------------
# fused join -> grouped-aggregate stage (the q3 shape)
# --------------------------------------------------------------------------

# Declared HLO contracts: the fused stage is ONE executable (single_fusion),
# host-callback-free and collective-free — any collective means the
# broadcast build side leaked onto the mesh path.
_hlo_lint.register_contract(
    "fused-stage-join-agg",
    collectives={},
    description=(
        "whole-stage probe+verify+filter+group+merge program: one executable, "
        "device-local, donated fold state"
    ),
    single_fusion=True,
)


def fused_join_agg_program(
    vmodes: Tuple[str, ...],
    pred_fn,
    needed: Tuple[str, ...],
    on_probe: Dict[str, bool],
    gkey_specs: Tuple[Tuple[str, str], ...],
    slot_specs,
    cap: int,
    pair_cap: int,
):
    """Build the whole fused stage: hash span walk → bounded pair expansion →
    exact key verify (``vmodes[i]`` = 'i' exact int64 / 'f' float64 with
    NaN-matches-NaN, mirroring ``join_stream._pairs_equal``) → post-join
    predicate → grouped segment reduction over the kept pairs → merge into
    the donated running partial.

    Returns ``(total_pairs, n_chunk_groups, n_merged, n_kept, n_out, fs_out,
    keys_out, slots_out)``. Overflow (``total_pairs > pair_cap`` or a group
    count beyond ``cap``) is detected in-program: the rank-compressed counts
    are exact even above capacity, and every state output selects the
    ORIGINAL state via ``jnp.where`` so the donated buffers round-trip
    unchanged for the host to redo the chunk per-family."""
    import jax.numpy as jnp
    from jax import ops as jops

    from hyperspace_tpu.exec import device as D
    from hyperspace_tpu.ops.hashing import combine_hashes_jnp

    def program(
        state_keys, state_slots, state_fs, state_n,
        table_h, border, n_build, bkenc, pplanes, pkenc, pcols, bcols,
        lits, n_valid, row_base,
    ):
        n_probe = pplanes[0].shape[0]
        t_len = table_h.shape[0]
        b_len = bkenc[0].shape[0]
        # 1. probe spans (the hash-probe family's body)
        h = combine_hashes_jnp(list(pplanes))
        lo = jnp.minimum(jnp.searchsorted(table_h, h, side="left").astype(jnp.int64), n_build)
        hi = jnp.minimum(jnp.searchsorted(table_h, h, side="right").astype(jnp.int64), n_build)
        rvalid = jnp.arange(n_probe, dtype=jnp.int64) < n_valid
        counts = jnp.where(rvalid, hi - lo, jnp.int64(0))
        cum = jnp.cumsum(counts)
        total = cum[-1]
        # 2. capacity-bounded pair expansion (the host repeat/cumsum walk,
        # in-program): pair j belongs to the first probe row whose cumulative
        # count exceeds j
        j = jnp.arange(pair_cap, dtype=jnp.int64)
        pvalid = j < jnp.minimum(total, jnp.int64(pair_cap))
        cand_p = jnp.clip(
            jnp.searchsorted(cum, j, side="right").astype(jnp.int64), 0, n_probe - 1
        )
        start = cum[cand_p] - counts[cand_p]
        slot = jnp.clip(j - start + lo[cand_p], 0, t_len - 1)
        cand_b = jnp.clip(border[slot], 0, b_len - 1)
        # 3. exact key verification (32-bit hash collisions removed)
        keep = pvalid
        for pe, be, mode in zip(pkenc, bkenc, vmodes):
            a = pe[cand_p]
            b = be[cand_b]
            if mode == "i":
                keep = keep & (a == b)
            else:
                af = a.astype(jnp.float64)
                bf = b.astype(jnp.float64)
                keep = keep & ((af == bf) | (jnp.isnan(af) & jnp.isnan(bf)))
        # 4. pair-space column gather + post-join predicate
        cols = {}
        for name in needed:
            src = pcols if on_probe[name] else bcols
            cols[name] = src[name][cand_p if on_probe[name] else cand_b]
        if pred_fn is not None:
            keep = keep & pred_fn(cols, lits)
        rank = jnp.cumsum(keep.astype(jnp.int64)) - 1  # kept-pair position
        n_kept = keep.sum().astype(jnp.int64)
        # 5. grouped segment reduction over the kept pairs (the
        # grouped-agg-chunk family's body, in pair space). fs is the
        # kept-pair position — exactly the row index the per-family path
        # sees after assembling only the kept pairs.
        codes = [D._key_code(cols[name], tag) for name, tag in gkey_specs]
        order, ms, n_chunk, segs = D._segment_ids(codes, keep, cap)
        rep = jops.segment_min(
            jnp.where(ms, order.astype(jnp.int64), jnp.int64(pair_cap)),
            segs, num_segments=cap, indices_are_sorted=True,
        )
        repc = jnp.clip(rep, 0, pair_cap - 1)
        fs_b = jnp.where(rep < pair_cap, rank[repc] + row_base, D._FS_SENTINEL)
        key_b = tuple(cols[name][repc] for name, _ in gkey_specs)
        cols_sorted = {c: cols[c][order] for _, c, _ in slot_specs if c is not None}
        slot_b = D._segment_reduce_slots(cols_sorted, ms, segs, cap, slot_specs)
        # 6. merge into the running partial (the grouped-merge family's body)
        idx = jnp.arange(cap)
        mask = jnp.concatenate([idx < state_n, idx < n_chunk])
        kcat = tuple(jnp.concatenate([a, b]) for a, b in zip(state_keys, key_b))
        scat = tuple(jnp.concatenate([a, b]) for a, b in zip(state_slots, slot_b))
        fs_cat = jnp.concatenate([state_fs, fs_b])
        n_m, fs_m, key_m, slot_m = D._merge_concat_parts(
            gkey_specs, slot_specs, cap, kcat, scat, fs_cat, mask
        )
        # 7. overflow guard: on ANY capacity overflow the (donated,
        # output-aliased) state round-trips unchanged
        ok = (total <= pair_cap) & (n_chunk <= cap) & (n_m <= cap)
        n_out = jnp.where(ok, n_m, state_n)
        fs_out = jnp.where(ok, fs_m, state_fs)
        keys_out = tuple(jnp.where(ok, m, s) for m, s in zip(key_m, state_keys))
        slots_out = tuple(jnp.where(ok, m, s) for m, s in zip(slot_m, state_slots))
        return total, n_chunk, n_m, n_kept, n_out, fs_out, keys_out, slots_out

    return program


def _verify_modes(probe_dtypes, build_dtypes) -> Tuple[str, ...]:
    """Per-key device verification mode, or DeviceUnsupported when the exact
    host semantics (``_pairs_equal``) don't map onto encoded planes: strings
    and objects need the host loop, unsigned ints promote weirdly, and
    mixed-unit datetimes compare at the finest common unit host-side."""
    from hyperspace_tpu.exec.device import DeviceUnsupported

    modes: List[str] = []
    for pd, bd in zip(probe_dtypes, build_dtypes):
        pk, bk = pd.kind, bd.kind
        if pk in "OUS" or bk in "OUS":
            raise DeviceUnsupported("string join keys verify host-side")
        if pk == "u" or bk == "u":
            raise DeviceUnsupported("unsigned join keys verify host-side")
        if pk == "M" or bk == "M":
            if pk != bk or pd != bd:
                raise DeviceUnsupported("mixed datetime join keys verify host-side")
            modes.append("i")
        elif pk in "ib" and bk in "ib":
            modes.append("i")
        else:
            modes.append("f")
    return tuple(modes)


class _JoinAggState:
    """Host-side driver state of one fused join→aggregate stream."""

    __slots__ = ("pair_cap", "bdev", "pred", "refs", "sources", "probe_is_left")

    def __init__(self, pair_cap, bdev, pred, refs, sources, probe_is_left):
        self.pair_cap = pair_cap
        self.bdev = bdev
        self.pred = pred
        self.refs = refs
        self.sources = sources
        self.probe_is_left = probe_is_left


def stream_join_aggregate(executor, join_plan, spec, post_filter, group_keys, aggs):
    """Whole-plan fused execution of a q3-shaped chain — Aggregate over
    (Filter over) an inner broadcast Join: ONE donated XLA program folds each
    probe chunk straight into the device-resident grouped partial.

    Per-family equivalent of one chunk: hash-probe dispatch + host verify +
    fused-postjoin dispatch + grouped-agg-chunk dispatch + grouped-merge
    dispatch. Here: one dispatch, with the fold state donated. Byte-identical
    output (tests/test_fusion.py proves it against the fusion-off path).

    Raises DeviceUnsupported before any fold when the shape doesn't fuse;
    mid-stream capacity overflows redo the offending chunk per-family
    (``hs_device_fallback_total{op="fusion"}``)."""
    import jax

    from hyperspace_tpu.exec import device as D
    from hyperspace_tpu.exec import join_stream as J
    from hyperspace_tpu.exec import trace
    from hyperspace_tpu.exec import batch as B
    from hyperspace_tpu.plan import logical as L
    from hyperspace_tpu.plan.expr import as_bool_mask
    from hyperspace_tpu.utils.x64 import ensure_x64

    ensure_x64()
    session = executor.session
    conf = session.conf
    if join_plan.how != "inner":
        raise D.DeviceUnsupported("fused join-agg stage covers inner joins")

    build_plan = join_plan.left if spec.build_is_left else join_plan.right
    probe_plan = join_plan.right if spec.build_is_left else join_plan.left
    bkeys = spec.lkeys if spec.build_is_left else spec.rkeys
    pkeys = spec.rkeys if spec.build_is_left else spec.lkeys
    probe_is_left = not spec.build_is_left
    lout = join_plan.left.output_columns
    rout = join_plan.right.output_columns

    refs = sorted(post_filter.references()) if post_filter is not None else []
    agg_inputs = sorted({c for _, _, c in aggs if c is not None})
    needed = tuple(dict.fromkeys(refs + list(group_keys) + agg_inputs))
    sources = {name: D._join_column_source(name, lout, rout) for name in needed}
    on_probe = {
        name: (is_left == probe_is_left) for name, (is_left, _) in sources.items()
    }

    bset = set(build_plan.output_columns)
    pset = set(probe_plan.output_columns)
    need_b = {c for (il, c) in sources.values() if il == spec.build_is_left and c in bset}
    need_p = {c for (il, c) in sources.values() if il == probe_is_left and c in pset}
    build_cols = [c for c in build_plan.output_columns if c in need_b or c in bkeys]
    probe_cols = [c for c in probe_plan.output_columns if c in need_p or c in pkeys]

    build = J._shared_build_side(session, build_plan, build_cols, bkeys)
    J._count_broadcast()
    trace.record("join", "broadcast-hash-stream")

    # the grouped fold state + finalization semantics live in
    # GroupedAggStream; this stage drives its device partial directly. The
    # capacity hint keys on the probe leaf files so repeat runs over the same
    # lake start at the settled capacity instead of overflowing chunk one.
    from hyperspace_tpu.exec.executor import _chain_to_scan, _leaf_files

    _, probe_leaf = _chain_to_scan(probe_plan)
    hint_key = (
        ("fused-join-agg",) + tuple(_leaf_files(probe_leaf))
        if probe_leaf is not None else None
    )
    gs = D.GroupedAggStream(
        session, list(group_keys), list(aggs),
        max_groups=conf.agg_max_groups, cap_floor=conf.agg_capacity_floor,
        hint_key=hint_key,
    )
    build_dtype = {name: build.batch[col].dtype for name, (il, col) in sources.items()
                   if il == spec.build_is_left}

    def orient(p_i, b_i):
        return (p_i, b_i) if probe_is_left else (b_i, p_i)

    def classic_chunk(chunk: Dict[str, np.ndarray]) -> None:
        """Per-family fold of one probe chunk (also the overflow redo)."""
        p_i, b_i = J._probe_chunk(session, build, chunk, pkeys, bkeys)
        if post_filter is not None and p_i.shape[0]:
            mask = None
            if conf.device_execution_enabled:
                try:
                    mask = J._device_postjoin_mask(
                        session, post_filter, chunk, build, p_i, b_i,
                        refs, sources, probe_is_left,
                    )
                except D.DeviceUnsupported:
                    trace.fallback("join", "postjoin_device")
            if mask is None:
                lidx, ridx = orient(p_i, b_i)
                lb, rb = (chunk, build.batch) if probe_is_left else (build.batch, chunk)
                refbatch = J._gather_pairs(refs, sources, lb, rb, lidx, ridx, {}, {})
                raw = as_bool_mask(post_filter.eval(refbatch))
                mask = np.broadcast_to(np.asarray(raw, dtype=bool), (p_i.shape[0],))
            p_i, b_i = p_i[mask], b_i[mask]
        if p_i.shape[0] == 0:
            return
        lidx, ridx = orient(p_i, b_i)
        lb, rb = (chunk, build.batch) if probe_is_left else (build.batch, chunk)
        joined = J._gather_pairs(list(needed), sources, lb, rb, lidx, ridx, {}, {})
        gs.update(joined, None)

    # seed the stream's schema from zero-row columns of the joined dtypes
    # (inner join: no null promotion, dtypes pass through the gather)
    probe_exec = probe_plan
    if set(probe_cols) != set(probe_plan.output_columns):
        probe_exec = L.Project(probe_cols, probe_plan)

    from hyperspace_tpu.exec.executor import Executor

    state = _JoinAggState(0, None, None, refs, sources, probe_is_left)
    chunks = 0
    probe_iter = Executor(session).execute_stream(probe_exec)
    try:
        for chunk in probe_iter:
            chunk = {k: np.asarray(v) for k, v in chunk.items()}
            n = B.num_rows(chunk)
            if n == 0:
                continue
            if chunks == 0:
                # fusability gates raise DeviceUnsupported here, before any
                # fold: the caller redoes the query on the materialized path
                sample = {
                    name: np.empty(0, dtype=(
                        build_dtype[name] if not on_probe[name]
                        else chunk[sources[name][1]].dtype
                    ))
                    for name in needed
                }
                gs._check_schema(sample)
                keys_schema, _ = gs._schema
                if any(tag == "s" for tag, _, _ in keys_schema):
                    raise D.DeviceUnsupported("string group keys stay per-family")
                _verify_modes(
                    [np.asarray(chunk[pk]).dtype for pk in pkeys],
                    [build.key_dtypes[bk] for bk in bkeys],
                )
            chunks += 1
            try:
                folded = _fused_fold_chunk(
                    session, gs, build, chunk, pkeys, bkeys, post_filter,
                    needed, on_probe, sources, state,
                )
            except D.DeviceUnsupported:
                folded = False
            if not folded:
                # capacity overflow (or an unfusable chunk dtype): the state
                # round-tripped unchanged, redo this one chunk per-family
                trace.fallback("fusion", "join-agg-overflow")
                classic_chunk(chunk)
            p = gs._partial
            if p is not None and int(p["n"]) > gs.max_groups:
                raise D.DeviceUnsupported(
                    f"group cardinality {int(p['n'])} exceeds "
                    f"maxGroups {gs.max_groups}"
                )
    finally:
        probe_iter.close()
    if not gs.has_data:
        # nothing ever folded (no probe chunks, or every chunk redone
        # per-family with zero kept pairs): punt to the materialized path
        # rather than hand-crafting empty dtypes here
        raise D.DeviceUnsupported("fused join-agg stream folded no groups")
    trace.record("agg", "fused-join-agg-stream")
    return gs.finalize()


def _fused_fold_chunk(session, gs, build, chunk, pkeys, bkeys, post_filter,
                      needed, on_probe, sources, state) -> bool:
    """Fold one probe chunk with the single fused program. Returns False on
    capacity overflow (state preserved; caller redoes the chunk per-family);
    raises DeviceUnsupported when this chunk's dtypes don't fuse."""
    import time as _ptime

    import jax

    from hyperspace_tpu.exec import device as D
    from hyperspace_tpu.exec import batch as B
    from hyperspace_tpu.ops.encode import hash_input_uint32

    conf = session.conf
    n = B.num_rows(chunk)
    vmodes = _verify_modes(
        [np.asarray(chunk[pk]).dtype for pk in pkeys],
        [build.key_dtypes[bk] for bk in bkeys],
    )

    # build-side device encodings (cached on the BuildSide across chunks)
    if state.bdev is None:
        bkenc = []
        for bk in bkeys:
            got = build.enc.get(bk)
            if got is None:
                got = D.encode_column(build.batch[bk])
                build.enc[bk] = got
            bkenc.append(D.put(got[0], "join-mats"))
        bcols = {}
        bcodecs = {}
        for name in needed:
            if on_probe[name]:
                continue
            col = sources[name][1]
            got = build.enc.get(col)
            if got is None:
                got = D.encode_column(build.batch[col])
                build.enc[col] = got
            if got[1].kind == "string" and col in {c for _, _, c in gs.aggs if c}:
                raise D.DeviceUnsupported("string aggregate inputs stay host-side")
            bcols[name] = D.put(got[0], "join-mats")
            bcodecs[name] = got[1]
        border = np.zeros(int(build.table.shape[0]), dtype=np.int64)
        border[: build.n] = build.order
        state.bdev = (tuple(bkenc), bcols, bcodecs, D.put(border, "join-mats"))
    bkenc, bcols, bcodecs, border = state.bdev

    # probe-side per-chunk encodings, padded to the sqrt(2) row bucket
    pplanes = []
    for pk, bk in zip(pkeys, bkeys):
        arr = np.asarray(chunk[pk])
        bdt = build.key_dtypes[bk]
        if arr.dtype.kind == "M" and bdt.kind == "M" and arr.dtype != bdt:
            arr = arr.astype(bdt)
        pplanes.append(D._pad_to_bucket(hash_input_uint32(arr), 1, np.uint32(0)))
    pkenc = []
    for pk in pkeys:
        enc, _ = D.encode_column(np.asarray(chunk[pk]))
        pkenc.append(D._pad_to_bucket(enc, 1, 0 if enc.dtype != np.float64 else np.nan))
    pcols = {}
    codecs = dict(bcodecs)
    for name in needed:
        if not on_probe[name]:
            continue
        col = sources[name][1]
        enc, codec = D.encode_column(np.asarray(chunk[col]))
        if codec.kind == "string" and name in {c for _, _, c in gs.aggs if c}:
            raise D.DeviceUnsupported("string aggregate inputs stay host-side")
        pcols[name] = D._pad_to_bucket(enc, 1, 0 if enc.dtype != np.float64 else np.nan)
        codecs[name] = codec

    if post_filter is not None:
        pred_fn, lits = D.compile_predicate(post_filter, codecs)
        pred_sk = D.predicate_skeleton(post_filter, codecs)
    else:
        pred_fn, lits = None, ()
        pred_sk = "<none>"

    keys_schema, _ = gs._schema
    gkey_specs = tuple(
        (name, "f" if tag == "f" else "i")
        for name, (tag, _, _) in zip(gs.group_keys, keys_schema)
    )

    # capacity buckets: pairs start at one-match-per-row, groups at the hint
    if state.pair_cap <= 0:
        state.pair_cap = D.bucket_rows(n)
    pair_cap = state.pair_cap
    p = gs._partial
    cap = D.group_capacity(max(gs._cap_hint, 1), gs.cap_floor)
    if p is not None:
        cap = max(cap, p["cap"])
    state_keys, state_slots, state_fs, state_n = _ensure_grouped_state(
        gs, gkey_specs, cap
    )

    plan = StagePlan((
        FilterOp(pred_sk),
        JoinProbeOp(len(pkeys), pair_cap),
        GroupAggOp(gkey_specs, tuple(gs._slots), cap),
    ))
    donate = donation_wanted(conf)
    skeleton = plan.skeleton() + f"|v:{','.join(vmodes)}" + ("|don" if donate else "")
    key = D._program_key(skeleton, session.mesh)
    program = fused_join_agg_program(
        vmodes, pred_fn, needed, on_probe, gkey_specs, tuple(gs._slots),
        cap, pair_cap,
    )
    jitted = compile_stage(
        key, program, donate_argnums=(0, 1, 2) if donate else (), family="fused-stage-join-agg"
    )
    shapes = (pplanes[0].shape, int(build.table.shape[0]), cap, pair_cap)
    first = D._note_compile(key, shapes)
    args = (
        state_keys, state_slots, state_fs, np.int64(state_n),
        build.table, border, np.int64(build.n), bkenc,
        tuple(D.put(pl, "join-mats") for pl in pplanes),
        tuple(D.put(k, "join-mats") for k in pkenc),
        {k: D.put(v, "join-mats") for k, v in pcols.items()}, bcols,
        tuple(lits), np.int64(n), np.int64(gs._row_base),
    )
    _hlo_lint.maybe_verify(conf, "fused-stage-join-agg", key, jitted, args)
    t0 = _ptime.perf_counter()
    total_d, n_chunk_d, n_m_d, n_kept_d, n_out_d, fs_out, keys_out, slots_out = jitted(*args)
    count_dispatch("fused-stage-join-agg")
    total, n_chunk, n_m, n_kept, n_out = (
        int(v) for v in D.fetch(
            (total_d, n_chunk_d, n_m_d, n_kept_d, n_out_d), "agg-table", "fused-stage-join-agg"
        )
    )
    D._observe_program("fused-stage-join-agg", first, t0)
    # the donated state is gone: rebind to the returned (aliased) arrays
    # whether the fold took or overflowed (overflow returns the original
    # state values through the same buffers)
    gs._partial = {
        "cap": cap, "n": n_out, "fs": fs_out,
        "keys": list(keys_out), "slots": list(slots_out),
    }
    gs._family = "fused-stage-join-agg"
    if total > pair_cap or n_chunk > cap or n_m > cap:
        state.pair_cap = D.bucket_rows(max(total, 1))
        gs._cap_hint = max(gs._cap_hint, n_chunk, n_m)
        return False
    gs._cap_hint = max(gs._cap_hint, n_m)
    gs._row_base += n_kept
    return True


def _ensure_grouped_state(gs, gkey_specs, cap):
    """The running partial as (keys, slots, fs, n) device arrays padded to
    ``cap`` — zero-filled when the stream is fresh (the fused program's merge
    masks them out via ``state_n == 0``)."""
    import jax
    import jax.numpy as jnp

    from hyperspace_tpu.exec import device as D

    p = gs._partial
    if p is None:
        keys = tuple(
            jnp.zeros(cap, dtype=jnp.float64 if tag == "f" else jnp.int64)
            for _, tag in gkey_specs
        )
        slots = tuple(
            jnp.zeros(cap, dtype=jnp.int64 if (kind in ("cntm", "cnt") or (isint and kind in ("min", "max", "sum"))) else jnp.float64)
            for kind, _, isint in gs._slots
        )
        fs = jnp.full(cap, D._FS_SENTINEL, dtype=jnp.int64)
        return keys, slots, fs, 0
    if p["cap"] < cap:
        p["fs"] = D._dev_pad(p["fs"], cap, D._FS_SENTINEL)
        p["keys"] = [
            D._dev_pad(k, cap, 0 if k.dtype != np.float64 else np.nan) for k in p["keys"]
        ]
        p["slots"] = [D._dev_pad(s, cap, 0) for s in p["slots"]]
        p["cap"] = cap
    return tuple(p["keys"]), tuple(p["slots"]), p["fs"], int(p["n"])
