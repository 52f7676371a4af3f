"""The collective story, proven from compiled HLO (VERDICT round-2 item 5).

The architectural claims (SURVEY.md §2.9; ref shuffle-freedom:
HS/index/covering/JoinIndexRule.scala:604-618) are now DECLARED as
:class:`~hyperspace_tpu.check.hlo_lint.ProgramContract`s next to the program
builders (exec/device.py, ops/bucketize.py) and asserted here through the
rule engine (``assert_contract``):

- distributed index build: exactly ONE all-to-all (the packed-plane exchange)
  and no other collective,
- generic re-bucketing (hybrid-scan delta path): exactly ONE all-to-all,
- hierarchical DCN x ICI exchange: exactly TWO all-to-alls (one per phase),
- the bucketed equi-join: NO data-movement collective at all (all-reduce is
  permitted only for a query's own aggregate),
- plane packing is bit-exact for every exchanged dtype.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hyperspace_tpu.check import hlo_lint
from hyperspace_tpu.check.hlo_lint import (
    assert_contract,
    collective_counts,
    hlo_text_of,
    verify_hlo,
)
from hyperspace_tpu.exec import device as _device  # noqa: F401  (registers exec contracts)
from hyperspace_tpu.ops import bucketize as bz

pytestmark = pytest.mark.check

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    devices = np.array(jax.devices()[:N_DEV])
    return Mesh(devices, ("buckets",))


def _sharded(mesh, arr):
    return jax.device_put(arr, NamedSharding(mesh, P("buckets")))


class TestCompiledCollectives:
    def test_build_exchange_is_one_all_to_all(self, mesh):
        """The production distributed-build program (the real code path
        create_index runs on a >1-device session) conforms to its declared
        contract: exactly one all-to-all, nothing else."""
        capacity = 16
        fn = bz._build_exchange_program(mesh, ("i",), 4 * N_DEV, capacity)
        n = N_DEV * 32
        keys = (_sharded(mesh, np.arange(n, dtype=np.int64)),)
        ridx = _sharded(mesh, np.arange(n, dtype=np.int64))
        txt = fn.lower(keys, (), ridx, np.int64(n)).compile().as_text()
        assert_contract("index-build-exchange", txt, "build exchange")

    def test_build_exchange_composite_keys_still_one(self, mesh):
        """Packing is what keeps the count at one: a composite (int, string)
        key staging 4+ buffers still compiles to a single all-to-all."""
        capacity = 16
        fn = bz._build_exchange_program(mesh, ("i", "s"), 4 * N_DEV, capacity)
        n = N_DEV * 32
        keys = (
            _sharded(mesh, np.arange(n, dtype=np.int64)),
            _sharded(mesh, np.arange(n, dtype=np.int64)),
        )
        hh = (_sharded(mesh, np.arange(n, dtype=np.uint32)),)
        ridx = _sharded(mesh, np.arange(n, dtype=np.int64))
        txt = fn.lower(keys, hh, ridx, np.int64(n)).compile().as_text()
        assert_contract("index-build-exchange", txt, "composite-key build exchange")

    def test_rebucket_is_one_all_to_all(self, mesh):
        """The hybrid-scan delta re-bucketing path: one all-to-all."""
        n = N_DEV * 16

        def run(v, b):
            out, ob, valid, ovf = bz.rebucket(mesh, {"v": v}, b, 32)
            return out["v"], ob, valid, ovf

        v = _sharded(mesh, np.arange(n, dtype=np.float64))
        b = _sharded(mesh, (np.arange(n) % (2 * N_DEV)).astype(np.int32))
        txt = jax.jit(hlo_lint.named("index-rebucket", run)).lower(v, b).compile().as_text()
        assert_contract("index-rebucket", txt, "rebucket")

    def test_hierarchical_is_two_all_to_alls(self):
        """DCN x ICI two-phase exchange: exactly two (one per phase)."""
        from hyperspace_tpu.parallel.mesh import make_mesh_2d, sharded_2d

        mesh2d = make_mesh_2d(n_slices=2, per_slice=N_DEV // 2)
        sh2 = sharded_2d(mesh2d)
        n = N_DEV * 16

        def run(v, b):
            out, ob, valid, ovf = bz.rebucket_hierarchical(mesh2d, {"v": v}, b, 32, 32)
            return out["v"], ob, valid, ovf

        v = jax.device_put(np.arange(n, dtype=np.float64), sh2)
        b = jax.device_put((np.arange(n) % (4 * N_DEV)).astype(np.int32), sh2)
        txt = jax.jit(hlo_lint.named("hierarchical-exchange", run)).lower(v, b).compile().as_text()
        assert_contract("hierarchical-exchange", txt, "hierarchical exchange")

    def test_bucketed_join_has_no_data_collectives(self, mesh):
        """Co-sharded bucketed equi-join: no all-to-all / all-gather /
        collective-permute / reduce-scatter anywhere in the compiled program.
        (The final scalar psum is the query's own aggregate — all-reduce — and
        is the ONLY collective present.)"""
        from jax import shard_map

        nk = N_DEV * 32
        sharding = NamedSharding(mesh, P("buckets"))

        @partial(jax.jit, out_shardings=NamedSharding(mesh, P()))
        def join_step(lk, lv, rk, rv):
            @partial(shard_map, mesh=mesh, in_specs=(P("buckets"),) * 4, out_specs=P())
            def per_shard(lk_, lv_, rk_, rv_):
                idx = jnp.searchsorted(rk_, lk_)
                idx = jnp.clip(idx, 0, rk_.shape[0] - 1)
                matched = rk_[idx] == lk_
                contrib = jnp.sum(jnp.where(matched, lv_ * rv_[idx], 0.0))
                return jax.lax.psum(contrib, "buckets")

            return per_shard(lk, lv, rk, rv)

        args = [
            jax.device_put(np.arange(nk, dtype=np.int64), sharding),
            jax.device_put(np.arange(nk, dtype=np.float64), sharding),
            jax.device_put(np.arange(nk, dtype=np.int64), sharding),
            jax.device_put(np.arange(nk, dtype=np.float64), sharding),
        ]
        txt = join_step.lower(*args).compile().as_text()
        counts = collective_counts(txt)
        assert counts["all-to-all"] == 0, counts
        assert counts["all-gather"] == 0, counts
        assert counts["collective-permute"] == 0, counts
        assert counts["reduce-scatter"] == 0, counts
        assert counts["all-reduce"] <= 1, counts  # the aggregate's psum only


class TestPlanePacking:
    @pytest.mark.parametrize(
        "dtype,vals",
        [
            (np.int64, [-(2**62), -1, 0, 1, 2**62]),
            (np.uint64, [0, 1, 2**63, 2**64 - 1]),
            (np.float64, [-1.5, 0.0, np.nan, np.inf, 1e300]),
            (np.int32, [-(2**31), -1, 0, 2**31 - 1]),
            (np.uint32, [0, 1, 2**32 - 1]),
            (np.float32, [-1.5, 0.0, np.nan, 3.4e38]),
            (np.float16, [-1.5, 0.25, np.nan, 65504.0]),
            ("bfloat16", [-1.5, 0.25, float("nan"), 3.0e38]),
            (np.int16, [-(2**15), -1, 0, 2**15 - 1]),
            (np.int8, [-128, -1, 0, 127]),
            (np.bool_, [True, False, True]),
        ],
    )
    def test_roundtrip_bit_exact(self, dtype, vals):
        if dtype == "bfloat16":
            import ml_dtypes

            dtype = ml_dtypes.bfloat16
        v = jnp.asarray(np.array(vals, dtype=dtype))
        planes = bz._to_planes(v)
        back = bz._from_planes(planes, dtype)
        assert back.dtype == jnp.asarray(v).dtype
        np.testing.assert_array_equal(
            np.asarray(back).view(np.uint8), np.asarray(v).view(np.uint8)
        )


class TestShardedExecPrograms:
    """The mesh-sharded execution engine's own programs (PR: parallel
    subsystem), asserted through their declared contracts."""

    def test_bucketed_smj_span_program_is_shuffle_free(self, mesh):
        """The REAL bucketed-SMJ span program (device._bucketed_span_program —
        what device joins execute) conforms to its zero-collective contract:
        co-sharded buckets join device-locally."""
        from hyperspace_tpu.exec import device as D

        prog = D._bucketed_span_program(mesh, "buckets")
        sharding = NamedSharding(mesh, P("buckets"))
        rng = np.random.default_rng(0)
        lm = jax.device_put(np.sort(rng.integers(0, 1000, (N_DEV * 2, 32)).astype(np.int64), axis=1), sharding)
        rm = jax.device_put(np.sort(rng.integers(0, 1000, (N_DEV * 2, 48)).astype(np.int64), axis=1), sharding)
        txt = hlo_text_of(prog, lm, rm)
        assert_contract("bucketed-smj-span", txt, "bucketed SMJ span program")

    def test_sharded_filter_program_is_shuffle_free(self, mesh):
        """The sharded predicate program moves no rows between devices (through
        the ``hyperspace_tpu.parallel`` re-exports of ``check.hlo_lint``)."""
        from hyperspace_tpu.parallel import assert_shuffle_free, hlo_text_of as shim_text_of
        from hyperspace_tpu.parallel import collectives as C

        fn = C.sharded_elementwise(mesh, "buckets", lambda cols, lits: cols["a"] > lits[0])
        dev = jax.device_put(
            np.arange(N_DEV * 16, dtype=np.int64), NamedSharding(mesh, P("buckets"))
        )
        txt = shim_text_of(jax.jit(hlo_lint.named("fused-filter", fn)), {"a": dev}, (np.int64(3),))
        assert_shuffle_free(txt, "sharded filter")
        assert_contract("fused-filter", txt, "sharded filter")

    def test_sharded_grouped_agg_gathers_partials_not_rows(self, mesh):
        """The collective-merged grouped aggregate all-gathers O(cap)
        per-shard partial tables — never an all-to-all row exchange. Its
        contract encodes exactly that (all-gather >= 1, all-to-all = 0)."""
        from hyperspace_tpu.parallel import collectives as C

        prog = C.sharded_grouped_chunk_program(
            mesh, "buckets", None, (("k", "i"),), [("cntm", None, True)], 32
        )
        dev = jax.device_put(
            (np.arange(N_DEV * 64) % 17).astype(np.int64),
            NamedSharding(mesh, P("buckets")),
        )
        txt = hlo_text_of(
            jax.jit(hlo_lint.named("sharded-grouped", prog)),
            {"k": dev}, (), np.int64(N_DEV * 64), np.int64(0),
        )
        got = collective_counts(txt)
        assert got["all-gather"] >= 1, got
        assert not verify_hlo("sharded-grouped", txt, "sharded grouped chunk")
