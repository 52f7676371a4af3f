"""shard_map programs for the mesh-sharded execution path.

``exec/device.py`` compiles every program with plain ``jax.jit`` and lets
GSPMD partition it over the session mesh. The programs here are the explicit
alternative behind ``hyperspace.parallel.enabled``: a ``shard_map`` over the
1-D bucket axis runs the SAME fused filter / grouped-agg program body
per-shard, then merges per-shard partial-aggregate tables ON DEVICE with one
``all_gather`` + the shared segment-reduce merge core
(``device._merge_concat_parts``) — no host loop over shards, O(cap) bytes on
the interconnect instead of O(rows).

Signature parity is deliberate: each builder returns a program with exactly
the call convention of its single-device twin, so ``GroupedAggStream`` and
``device_filter_mask`` swap them in under the same jit cache (keyed by
``device._program_key``'s mode tag) with no other changes.
"""

from __future__ import annotations

from functools import partial

import numpy as np

from hyperspace_tpu.exec import device as D
from jax import shard_map


def sharded_elementwise(mesh, axis, fn):
    """Wrap an elementwise program (predicate mask) in a shard_map over
    ``axis``: each device evaluates its own row block, outputs concatenate
    back to the global row order. No collectives — compiled HLO is
    shuffle-free (tests/test_hlo_collectives.py)."""
    from jax.sharding import PartitionSpec as P

    @partial(shard_map, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(axis))
    def mapped(cols, lits):
        return fn(cols, lits)

    return mapped


def sharded_topk_chunk_program(mesh, axis, num_keys, cap):
    """Sharded twin of ``ops.sort.topk_chunk_fn``: same signature (one
    ``(num_keys + 1, P)`` plane matrix in, one ``(num_keys + 1, cap)``
    candidate matrix out), swapped in under ``device._program_key``'s
    ``shmap`` mode tag by ``TopKStream``.

    Per shard: one multi-operand ``lax.sort`` over the LOCAL plane rows and a
    static take/pad to ``cap`` candidates. Then EXACTLY one fixed-size
    ``all_gather`` of the per-shard candidate matrices — ``n_dev * cap``
    *candidates* on the interconnect, never rows — and a replicated final
    sort down to ``cap``. The trailing row-id plane makes the order total, so
    the result is bit-identical to the single-device program on the same
    matrix (registered HLO contract ``sharded-topk``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from hyperspace_tpu.ops.sort import _TOPK_SENTINEL, _take_cap

    n_dev = mesh.devices.size

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(None, axis),),
        out_specs=P(),
        check_vma=False,
    )
    def program(planes):
        local = lax.sort(
            tuple(planes[i] for i in range(num_keys + 1)),
            num_keys=num_keys + 1,
            is_stable=False,
        )
        mine = jnp.stack([_take_cap(o, cap, _TOPK_SENTINEL) for o in local])
        gathered = jax.lax.all_gather(mine, axis)  # (n_dev, K+1, cap)
        cat = jnp.transpose(gathered, (1, 0, 2)).reshape(num_keys + 1, n_dev * cap)
        merged = lax.sort(
            tuple(cat[i] for i in range(num_keys + 1)),
            num_keys=num_keys + 1,
            is_stable=False,
        )
        return jnp.stack([_take_cap(o, cap, _TOPK_SENTINEL) for o in merged])

    return program


def sharded_grouped_chunk_program(mesh, axis, pred_fn, key_specs, slot_specs, cap):
    """Sharded twin of ``device._grouped_chunk_program``: same signature
    ``program(cols, lits, n_valid, row_base)``, same outputs
    ``(n_groups, first-seen, key reps, state slots)``.

    Per shard: fused predicate + segment reduction over the local row block
    (rows arrive block-sharded by ``NamedSharding(P(axis))``, so device ``d``
    holds global rows ``[d*per, (d+1)*per)``). Then ONE all_gather of the
    per-shard partial tables (``n_dev * cap`` rows — group cardinality, not
    row count) and a replicated ``_merge_concat_parts`` pass; shard-major
    concat order IS ascending global-row order, so first-seen representatives
    match the single-device program bit-for-bit.

    Overflow: a shard whose LOCAL cardinality exceeded ``cap`` dropped groups
    in its own table, which can leave the merged count deceptively <= cap —
    the returned ``n_groups`` is maxed with every shard's local count so the
    caller's right-sizing loop re-runs at a larger capacity.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    n_dev = mesh.devices.size

    def program(cols, lits, n_valid, row_base):
        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(axis), P(), P(), P()),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )
        def per_shard(cols_, lits_, n_valid_, row_base_):
            cols_ = D.join_columns(cols_)
            per = next(iter(cols_.values())).shape[0]
            d = jax.lax.axis_index(axis).astype(jnp.int64)
            gidx = d * per + jnp.arange(per, dtype=jnp.int64)
            valid = gidx < n_valid_
            mask = valid if pred_fn is None else (pred_fn(cols_, lits_) & valid)
            codes = [D._key_code(cols_[name], tag) for name, tag in key_specs]
            order, ms, ng_local, segs = D._segment_ids(codes, mask, cap)
            from jax import ops as jops

            rep = jops.segment_min(
                jnp.where(ms, order.astype(jnp.int64), jnp.int64(per)),
                segs, num_segments=cap, indices_are_sorted=True,
            )
            repc = jnp.clip(rep, 0, per - 1)
            # first-seen is a GLOBAL row index: local rep + shard base + chunk base
            fs_local = jnp.where(rep < per, rep + d * per + row_base_, D._FS_SENTINEL)
            keys_local = tuple(cols_[name][repc] for name, _ in key_specs)
            cols_sorted = {c: cols_[c][order] for _, c, _ in slot_specs if c is not None}
            slots_local = D._segment_reduce_slots(cols_sorted, ms, segs, cap, slot_specs)

            ng_all = jax.lax.all_gather(ng_local, axis)
            fs_all = jax.lax.all_gather(fs_local, axis).reshape(n_dev * cap)
            keys_all = tuple(
                jax.lax.all_gather(k, axis).reshape(n_dev * cap) for k in keys_local
            )
            slots_all = tuple(
                jax.lax.all_gather(s, axis).reshape(n_dev * cap) for s in slots_local
            )
            part_mask = (
                jnp.arange(cap, dtype=jnp.int64)[None, :] < ng_all[:, None]
            ).reshape(n_dev * cap)
            n_g, fs, key_out, slot_out = D._merge_concat_parts(
                key_specs, slot_specs, cap, keys_all, slots_all, fs_all, part_mask
            )
            n_g = jnp.maximum(n_g, jnp.max(ng_all))
            return n_g, fs, key_out, slot_out

        return per_shard(cols, lits, n_valid, row_base)

    return program
