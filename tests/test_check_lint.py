"""hscheck AST lint: per-rule seeded-violation/clean fixture pairs, pragma
suppression, CLI exit codes, and the tree-is-clean acceptance gate."""

import json
import os

import pytest

from hyperspace_tpu.check.__main__ import main
from hyperspace_tpu.check.lint import default_paths, default_root, run_lint
from hyperspace_tpu.check.rules import all_rules

pytestmark = pytest.mark.check

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures", "check")


def fixture(*parts):
    return os.path.join(FIXTURES, *parts)


def lint_one(path, rule):
    return run_lint(paths=[path], rules=[rule])


class TestRulePairs:
    def test_conf_keys_bad(self):
        found = lint_one(fixture("bad_conf_key.py"), "conf-keys")
        assert len(found) == 1
        assert found[0].rule == "conf-keys"
        assert "hyperspace.serving.quueDepth" in found[0].message
        assert found[0].line == 5

    def test_conf_keys_clean(self):
        assert lint_one(fixture("clean_conf_key.py"), "conf-keys") == []

    def test_metric_families_bad(self):
        found = lint_one(fixture("bad_metric.py"), "metric-families")
        assert len(found) == 1
        assert "literal" in found[0].message

    def test_metric_families_clean(self):
        assert lint_one(fixture("clean_metric.py"), "metric-families") == []

    def test_lock_blocking_bad(self):
        found = lint_one(fixture("serving", "bad_lock.py"), "lock-blocking")
        reasons = " | ".join(f.message for f in found)
        assert len(found) == 3
        assert "sleep" in reasons
        assert "file" in reasons
        assert "device" in reasons

    def test_lock_blocking_clean(self):
        # IO after the with-block and inside nested defs must not count.
        assert lint_one(fixture("serving", "clean_lock.py"), "lock-blocking") == []

    def test_lock_blocking_only_fires_under_serving_or_obs(self):
        # Same seeded pattern, but the path filter keeps the rule scoped to
        # the latency-sensitive trees — bad_jit.py lives outside them.
        assert lint_one(fixture("bad_jit.py"), "lock-blocking") == []

    def test_cache_branding_bad(self):
        found = lint_one(fixture("bad_branding.py"), "cache-branding")
        assert [f.line for f in found] == [7, 8, 9]
        assert "'kept'" in found[0].message
        assert "scan_key" in found[1].message

    def test_cache_branding_clean(self):
        # Explicit kwarg, positional past the index, and **kwargs all satisfy.
        assert lint_one(fixture("clean_branding.py"), "cache-branding") == []

    def test_jit_purity_bad(self):
        found = lint_one(fixture("bad_jit.py"), "jit-purity")
        lines = [f.line for f in found]
        assert 12 in lines  # time.time in @jax.jit
        assert 13 in lines  # np.sum in @jax.jit
        assert 17 in lines  # random.random in fn later passed to jax.jit
        assert 28 in lines  # np.mean in fn passed into a *jit*-named wrapper

    def test_jit_purity_clean(self):
        # jnp calls and whitelisted np dtypes/constants inside jit are fine,
        # as is host numpy in a never-jitted helper.
        assert lint_one(fixture("clean_jit.py"), "jit-purity") == []

    def test_snapshot_pin_bad(self):
        found = lint_one(fixture("bad_snapshot_pin.py"), "snapshot-pin")
        assert [f.line for f in found] == [6, 7]
        assert "SnapshotHandle" in found[0].message
        assert "get_latest_log" in found[1].message

    def test_snapshot_pin_clean(self):
        # Pin-aware manager reads, handle reads, and a pragma-suppressed
        # direct resolver all pass.
        assert lint_one(fixture("clean_snapshot_pin.py"), "snapshot-pin") == []

    def test_io_error_swallow_bad(self):
        found = lint_one(fixture("bad_io_swallow.py"), "io-error-swallow")
        assert [f.line for f in found] == [8, 16]
        assert "classify" in found[0].message

    def test_io_error_swallow_clean(self):
        # Narrow handlers, re-raises, count_io_error fallbacks, pragmas,
        # and broad excepts away from lake IO all pass.
        assert lint_one(fixture("clean_io_swallow.py"), "io-error-swallow") == []

    def test_process_local_state_bad(self):
        found = lint_one(fixture("bad_process_local.py"), "process-local-state")
        assert [f.line for f in found] == [6, 7, 8, 9, 10]
        reasons = " | ".join(f.message for f in found)
        assert "'BREAKERS'" in reasons
        assert "defaultdict()" in reasons
        assert "count()" in reasons
        assert "FrontDoorRegistry()" in reasons
        assert "__fabric_published__" in found[0].message

    def test_process_local_state_clean(self):
        # __fabric_published__ listing, a pragma, immutable constants,
        # dunders, and function/class-body mutables all pass.
        assert lint_one(fixture("clean_process_local.py"), "process-local-state") == []

    def test_trace_context_drop_bad(self):
        found = lint_one(fixture("fabric", "bad_trace_drop.py"), "trace-context-drop")
        assert len(found) == 2
        messages = " | ".join(f.message for f in found)
        assert "does not cross thread creation" in messages
        assert "traceparent" in messages
        assert [f.line for f in found] == [16, 22]

    def test_trace_context_drop_clean(self):
        # spans.attach/bind_context on the spawned thread, a traceparent
        # header on the /query hop, and a request-free lifecycle thread
        # all pass.
        assert lint_one(fixture("fabric", "clean_trace_drop.py"), "trace-context-drop") == []

    def test_donated_buffer_reuse_bad(self):
        found = lint_one(fixture("bad_donated_reuse.py"), "donated-buffer-reuse")
        assert len(found) == 2
        messages = " | ".join(f.message for f in found)
        assert "'state'" in messages
        assert "donate_argnums" in messages

    def test_donated_buffer_reuse_clean(self):
        # rebinding to the call's result, reading a non-donated argnum, and
        # starred calls (positions unknowable) all pass.
        assert lint_one(fixture("clean_donated_reuse.py"), "donated-buffer-reuse") == []

    def test_native_fallback_bad(self):
        found = lint_one(fixture("bad_native_fallback.py"), "native-fallback")
        assert [f.line for f in found] == [9, 17, 24]
        assert "hs_native_fallback_total" in found[0].message

    def test_native_fallback_clean(self):
        # Re-raises, classified swallows, counted fallbacks (helper and
        # inline registration), pragmas, and read_columns on a non-native
        # receiver all pass.
        assert lint_one(fixture("clean_native_fallback.py"), "native-fallback") == []

    def test_native_fallback_only_fires_under_exec(self):
        from hyperspace_tpu.check.rules.native_fallback import _in_scope

        assert _in_scope(os.path.join("hyperspace_tpu", "exec", "io.py"))
        assert not _in_scope(os.path.join("hyperspace_tpu", "obs", "x.py"))
        assert not _in_scope("chip_smoke.py")

    def test_donation_compiler_counts_as_jit_for_purity(self):
        # any call that takes fn with donate_argnums=... jits fn — a host
        # numpy call inside fn must fire jit-purity just like jax.jit(fn)
        import ast as _ast

        from hyperspace_tpu.check.rules.jit_purity import scan_tree

        src = (
            "def fold(s, c):\n"
            "    import numpy as np\n"
            "    return np.add(s, c)\n"
            "jitted = compile_stage('fuse[F>G]', fold, donate_argnums=(0,))\n"
        )
        hits = scan_tree(_ast.parse(src))
        assert hits and "np.add" in hits[0][1]

    def test_trace_context_drop_only_fires_under_fabric_or_serving(self):
        from hyperspace_tpu.check.rules.trace_context_drop import _in_scope

        assert _in_scope(os.path.join("hyperspace_tpu", "fabric", "x.py"))
        assert _in_scope(os.path.join("hyperspace_tpu", "serving", "x.py"))
        assert not _in_scope(os.path.join("hyperspace_tpu", "obs", "x.py"))
        assert not _in_scope("chip_smoke.py")

    def test_process_local_state_only_fires_under_serving_or_reliability(self):
        # Full-scope runs keep the rule off layers whose module state the
        # fabric does not reason about — bad_jit.py lives outside them.
        from hyperspace_tpu.check.rules.process_local_state import _in_scope

        assert _in_scope(os.path.join("hyperspace_tpu", "serving", "x.py"))
        assert _in_scope(os.path.join("hyperspace_tpu", "reliability", "x.py"))
        assert not _in_scope(os.path.join("hyperspace_tpu", "obs", "x.py"))
        assert not _in_scope("chip_smoke.py")


class TestSuppression:
    def test_pragma(self):
        found = run_lint(paths=[fixture("suppressed.py")], rules=["conf-keys"])
        # Line 5 (bare disable) and line 6 (disable=conf-keys) are suppressed;
        # line 7 names a different rule, so conf-keys still fires there.
        assert [f.line for f in found] == [7]
        assert "hyperspace.not.registered.c" in found[0].message


class TestRunLint:
    def test_unknown_rule_raises(self):
        with pytest.raises(KeyError, match="no-such-rule"):
            run_lint(rules=["no-such-rule"])

    def test_rule_registry_complete(self):
        assert set(all_rules()) == {
            "cache-branding",
            "conf-keys",
            "jit-purity",
            "lock-blocking",
            "metric-families",
            "snapshot-pin",
            "io-error-swallow",
            "process-local-state",
            "trace-context-drop",
            "donated-buffer-reuse",
            "native-fallback",
        }

    def test_default_scope_excludes_tests(self):
        paths = default_paths(default_root())
        assert paths, "default scope is empty"
        assert not any(os.sep + "tests" + os.sep in p for p in paths)
        assert any(p.endswith("__graft_entry__.py") for p in paths)

    def test_repo_tree_is_clean(self):
        # The acceptance gate: the shipped tree carries zero findings.
        found = run_lint()
        assert found == [], "\n".join(f.render() for f in found)


class TestCli:
    def test_exit_nonzero_on_fixture(self, capsys):
        rc = main([fixture("bad_conf_key.py")])
        out = capsys.readouterr().out
        assert rc == 1
        assert "[conf-keys]" in out
        assert "quueDepth" in out

    def test_exit_zero_on_tree(self, capsys):
        assert main([]) == 0
        assert capsys.readouterr().out == ""

    def test_exit_two_on_unknown_rule(self, capsys):
        rc = main(["--rules", "bogus", fixture("bad_conf_key.py")])
        assert rc == 2
        assert "unknown lint rules" in capsys.readouterr().err

    def test_json_output(self, capsys):
        rc = main(["--json", fixture("bad_branding.py")])
        assert rc == 1
        payload = json.loads(capsys.readouterr().out)
        assert len(payload) == 3
        assert payload[0]["rule"] == "cache-branding"
        assert payload[0]["line"] == 7
        assert payload[0]["path"].endswith("bad_branding.py")

    def test_list_rules(self, capsys):
        assert main(["--list"]) == 0
        out = capsys.readouterr().out
        for name in all_rules():
            assert name in out
