"""hscheck HLO contract engine: regex edge cases, budget verification,
forbidden-op patterns, the maybe_verify runtime hook, and an end-to-end run
with ``hyperspace.check.hlo.enabled`` on."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.check import hlo_lint
from hyperspace_tpu.check.hlo_lint import (
    assert_contract,
    collective_counts,
    hlo_text_of,
    maybe_verify,
    register_contract,
    reset_runtime_state,
    runtime_violations,
    set_default_enabled,
    verify_hlo,
)
from hyperspace_tpu.exec import device as _device  # noqa: F401  (registers exec contracts)
from hyperspace_tpu.obs.metrics import REGISTRY
from hyperspace_tpu.plan.expr import col

pytestmark = pytest.mark.check


class TestCollectiveCounts:
    def test_plain_instruction(self):
        txt = "  %ag.3 = f32[64]{0} all-gather(f32[8]{0} %p0), dimensions={0}\n"
        assert collective_counts(txt)["all-gather"] == 1

    def test_async_pair_counts_once(self):
        txt = (
            "  %s = (f32[8], f32[64]) all-gather-start(f32[8] %p0)\n"
            "  %d = f32[64] all-gather-done((f32[8], f32[64]) %s)\n"
        )
        got = collective_counts(txt)
        assert got["all-gather"] == 1

    def test_numbered_suffix(self):
        txt = "  %r = f32[] all-reduce.7(f32[] %x), to_apply=%add\n"
        assert collective_counts(txt)["all-reduce"] == 1

    def test_tuple_result_type(self):
        # a tuple result puts a ')' right before the op name — the leading
        # character class must accept it
        txt = "  %a2a = (s32[4], s32[4]) all-to-all(s32[4] %a, s32[4] %b)\n"
        assert collective_counts(txt)["all-to-all"] == 1

    def test_operand_mention_not_counted(self):
        # the op name appearing as an OPERAND (no following paren) is not an
        # application site
        txt = "  %gte = f32[64] get-tuple-element((f32[8], f32[64]) %all-to-all.1), index=1\n"
        assert collective_counts(txt)["all-to-all"] == 0

    def test_metadata_op_names_not_counted(self):
        # metadata uses underscores; dashes only appear at real HLO call sites
        txt = '  %x = f32[8] add(f32[8] %a, f32[8] %b), metadata={op_name="all_to_all"}\n'
        assert all(v == 0 for v in collective_counts(txt).values())


def _hlo(*ops):
    return "".join(f"  %v{i} = f32[8] {op}(f32[8] %p{i})\n" for i, op in enumerate(ops))


@pytest.fixture()
def scratch_contract():
    """A throwaway family: exactly one all-to-all, any number of all-reduce."""
    name = "hscheck-test-family"
    register_contract(
        name,
        {"all-to-all": (1, 1), "all-reduce": (0, None)},
        description="test fixture",
    )
    yield name
    hlo_lint._CONTRACTS.pop(name, None)


class TestVerifyHlo:
    def test_unknown_family_raises(self):
        with pytest.raises(KeyError, match="no contract registered"):
            verify_hlo("never-registered", "")

    def test_conformant(self, scratch_contract):
        txt = _hlo("all-to-all", "all-reduce", "all-reduce")
        assert verify_hlo(scratch_contract, txt) == []
        assert_contract(scratch_contract, txt)  # must not raise

    def test_below_minimum(self, scratch_contract):
        found = verify_hlo(scratch_contract, _hlo("all-reduce"))
        assert [f.rule for f in found] == ["collective-budget:all-to-all"]
        assert "exactly 1" in found[0].message

    def test_above_maximum(self, scratch_contract):
        found = verify_hlo(scratch_contract, _hlo("all-to-all", "all-to-all"))
        assert [f.rule for f in found] == ["collective-budget:all-to-all"]

    def test_unlisted_op_forbidden(self, scratch_contract):
        # a contract says everything it permits: all-gather isn't in the
        # budget, so one occurrence is a violation
        found = verify_hlo(scratch_contract, _hlo("all-to-all", "all-gather"))
        assert [f.rule for f in found] == ["collective-budget:all-gather"]

    def test_program_label(self, scratch_contract):
        found = verify_hlo(scratch_contract, "", program="my-key")
        assert found[0].path == "hlo:my-key"

    def test_assert_contract_raises(self, scratch_contract):
        with pytest.raises(AssertionError, match="collective-budget:all-to-all"):
            assert_contract(scratch_contract, "")


class TestForbiddenPatterns:
    def test_host_callback(self, scratch_contract):
        txt = (
            _hlo("all-to-all")
            + '  %cc = f32[8] custom-call(f32[8] %x), custom_call_target="xla_python_cpu_callback"\n'
        )
        found = verify_hlo(scratch_contract, txt)
        assert [f.rule for f in found] == ["forbidden-op:host-callback"]

    def test_f64_upcast(self, scratch_contract):
        txt = _hlo("all-to-all") + "  %c = f64[1000]{0} convert(f32[1000]{0} %x)\n"
        found = verify_hlo(scratch_contract, txt)
        assert [f.rule for f in found] == ["forbidden-op:f64-upcast"]

    @pytest.mark.parametrize("program, flagged", [
        ("upcast-returned", True), ("upcast-then-arithmetic", True), ("planes-joined-and-returned", True),
        ("planes-joined-inside-a-fusion", False), ("float32-arithmetic", False),
    ])
    def test_f64_upcast_on_compiled_text_tells_the_plane_join_apart(self, scratch_contract, program, flagged):
        # compiled text prints an operand by name alone: the rule reads its
        # type from its definition. A materialised upcast (a fusion's result),
        # f64 arithmetic over f32 data and a joined column written back out
        # are reported; add(convert(head), convert(tail)) consumed inside the
        # fusion that reads the column (exec/device.join_planes) is not.
        _device.ensure_x64()
        planes = _device.ColumnPlanes(jnp.ones(2048, jnp.float32), jnp.full(2048, 1e-9, jnp.float32))
        fn, args = {
            "upcast-returned": (lambda a: a.astype(jnp.float64), (planes.first,)),
            "upcast-then-arithmetic": (lambda a: (a.astype(jnp.float64) * 3.0).sum(), (planes.first,)),
            "planes-joined-and-returned": (_device.join_planes, (planes,)),
            "planes-joined-inside-a-fusion": (
                lambda p: jnp.where(_device.join_planes(p) > 0.5, _device.join_planes(p) * 2.0, 0.0).sum(), (planes,)),
            "float32-arithmetic": (lambda a: (a * 3.0).sum(), (planes.first,)),
        }[program]
        text = hlo_text_of(jax.jit(fn), *args)
        assert ("f64[2048]" in text) == (program != "float32-arithmetic")
        rules = [f.rule for f in verify_hlo(scratch_contract, _hlo("all-to-all") + text)]
        assert rules.count("forbidden-op:f64-upcast") == int(flagged), text

    def test_dynamic_shape(self, scratch_contract):
        txt = _hlo("all-to-all") + "  %p = s32[<=1024] parameter(0)\n"
        found = verify_hlo(scratch_contract, txt)
        assert [f.rule for f in found] == ["forbidden-op:dynamic-shape"]

    def test_opt_out(self):
        register_contract("hscheck-optout", {}, forbid=("host-callback",))
        try:
            txt = "  %p = s32[<=1024] parameter(0)\n"
            assert verify_hlo("hscheck-optout", txt) == []
        finally:
            hlo_lint._CONTRACTS.pop("hscheck-optout", None)

    def test_scalar_f64_convert_allowed(self, scratch_contract):
        # only whole-ARRAY upcasts are flagged; a scalar convert is fine
        txt = _hlo("all-to-all") + "  %c = f64[] convert(f32[] %x)\n"
        assert verify_hlo(scratch_contract, txt) == []


@pytest.fixture()
def runtime_default_on():
    set_default_enabled(True)
    reset_runtime_state()
    yield
    set_default_enabled(False)
    reset_runtime_state()


class TestMaybeVerify:
    def test_disabled_is_noop(self):
        reset_runtime_state()
        set_default_enabled(False)
        calls = []

        class Exploding:
            def lower(self, *a, **k):
                calls.append(1)
                raise RuntimeError("should not be reached")

        maybe_verify(None, "never-registered", "k", Exploding(), (np.zeros(4),))
        assert calls == []
        assert runtime_violations() == []

    def test_verifies_and_dedups(self, scratch_contract, runtime_default_on):
        jitted = jax.jit(hlo_lint.named(scratch_contract, lambda x: x * 2))
        before = REGISTRY.counter(
            "hs_check_programs_verified_total", program=scratch_contract
        ).value
        x = jnp.arange(8, dtype=jnp.float32)
        maybe_verify(None, scratch_contract, "k1", jitted, (x,))
        after = REGISTRY.counter(
            "hs_check_programs_verified_total", program=scratch_contract
        ).value
        assert after == before + 1
        # x*2 has no all-to-all: the budget violation lands in the log + metric
        viol = runtime_violations()
        assert [f.rule for f in viol] == ["collective-budget:all-to-all"]
        assert REGISTRY.counter(
            "hs_check_violations_total",
            rule="collective-budget:all-to-all",
            program=scratch_contract,
        ).value >= 1
        # same key + same shapes: cached executable, not re-verified
        maybe_verify(None, scratch_contract, "k1", jitted, (x,))
        assert REGISTRY.counter(
            "hs_check_programs_verified_total", program=scratch_contract
        ).value == after
        # new shape signature = new executable = verified again
        maybe_verify(
            None, scratch_contract, "k1", jitted, (jnp.arange(16, dtype=jnp.float32),)
        )
        assert REGISTRY.counter(
            "hs_check_programs_verified_total", program=scratch_contract
        ).value == after + 1

    def test_unnamed_program_violates_program_name(self, scratch_contract, runtime_default_on):
        """A family's executable must be jit_hs_<family>: that name is how the
        profiler's module line, HLO dumps and the compile-cache log find it."""
        assert hlo_lint.program_name("grouped-agg-chunk") == "hs_grouped_agg_chunk"
        x = jnp.ones(4, jnp.float32)
        maybe_verify(None, scratch_contract, "unnamed", jax.jit(lambda x: x + 1), (x,))
        rules = [f.rule for f in runtime_violations()]
        assert "program-name" in rules
        reset_runtime_state()
        named = jax.jit(hlo_lint.named(scratch_contract, lambda x: x + 1))
        assert named.lower(x).as_text().startswith("module @jit_hs_hscheck_test_family")
        maybe_verify(None, scratch_contract, "named", named, (x,))
        assert "program-name" not in [f.rule for f in runtime_violations()]
        with pytest.raises(KeyError):
            hlo_lint.named("never-registered-family", lambda x: x)

    def test_violations_warn_never_raise(self, scratch_contract, runtime_default_on):
        jitted = jax.jit(lambda x: x + 1)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            maybe_verify(
                None, scratch_contract, "k2", jitted, (jnp.ones(4, jnp.float32),)
            )
        assert any("contract violation" in str(x.message) for x in w)

    def test_reset_clears_dedup_and_log(self, scratch_contract, runtime_default_on):
        jitted = jax.jit(lambda x: x)
        x = jnp.ones(4, jnp.float32)
        maybe_verify(None, scratch_contract, "k3", jitted, (x,))
        assert runtime_violations()
        reset_runtime_state()
        assert runtime_violations() == []
        before = REGISTRY.counter(
            "hs_check_programs_verified_total", program=scratch_contract
        ).value
        maybe_verify(None, scratch_contract, "k3", jitted, (x,))
        assert REGISTRY.counter(
            "hs_check_programs_verified_total", program=scratch_contract
        ).value == before + 1


class TestEndToEnd:
    def test_device_queries_verified_clean(self, tmp_system_path, sample_parquet):
        """The acceptance run: with the check on, every compiled device
        program is verified and none violates its contract."""
        sess = hst.Session(
            conf={
                hst.keys.SYSTEM_PATH: tmp_system_path,
                hst.keys.CHECK_HLO_ENABLED: True,
                hst.keys.TPU_QUERY_DEVICE_EXECUTION: True,
                hst.keys.TPU_QUERY_DEVICE_MIN_ROWS: 0,
            }
        )
        hst.set_session(sess)
        try:
            reset_runtime_state()
            hs = hst.Hyperspace(sess)
            df = sess.read_parquet(sample_parquet)
            hs.create_index(
                df, hst.CoveringIndexConfig("chkIdx", ["c1"], ["c2", "c3"])
            )
            sess.enable_hyperspace()
            df.filter(col("c1") > 20).select("c2").collect()
            df.filter(col("c1") > 10).group_by("c1").agg(s=("c2", "sum")).collect()
            snap = {
                family: REGISTRY.counter(
                    "hs_check_programs_verified_total", program=family
                ).value
                for family in ("fused-filter", "grouped-agg-chunk")
            }
            assert sum(snap.values()) > 0, snap
            assert runtime_violations() == [], [
                f.render() for f in runtime_violations()
            ]
        finally:
            hst.set_session(None)
            set_default_enabled(False)
            reset_runtime_state()

    def test_exec_contracts_registered(self):
        have = set(hlo_lint.registered_contracts())
        for family in (
            "fused-filter",
            "fused-agg",
            "grouped-agg-chunk",
            "sharded-grouped",
            "grouped-merge",
            "bucketed-smj-span",
        ):
            assert family in have

    def test_parallel_package_reexports_this_module(self):
        import hyperspace_tpu.parallel as par

        assert par.collective_counts is collective_counts
        assert par.hlo_text_of is hlo_text_of
        assert par.assert_shuffle_free is hlo_lint.assert_shuffle_free
