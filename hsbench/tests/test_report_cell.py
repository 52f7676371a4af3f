"""The served cell ``sf10-report`` (PR 37): its files found by name from the
manifest, its new reader over a hand-made trace whose numbers can be worked
out by hand (a call an edge of the window cuts is left out), every new
per-layer metric over an empty run and over the parent's counters, the cell
itself in rehearsal, and its float32 control."""

import json
import os
import subprocess
import sys

import pytest

from hsbench import costs_agg, datagen, deployment, layers, run as hsrun, tracing, traffic
from hsbench.deployment import ROOT
from hsbench.layers import agg_roofline

S = 1e9
M = deployment.manifest()
MINE = [m["name"] for m in M["per_layer"] if m.get("workloads") == ["sf10-report"]]


@pytest.fixture()
def traced(monkeypatch):
    counters = {}
    monkeypatch.setattr(hsrun, "all_counters", lambda: dict(counters))
    run = hsrun.TracedRun("TPU v5 lite")
    run.mark()
    return run, counters


ROWS = 1000


def _ops(at, seconds, columns, tag):
    """The operations of one call: two fusions that both read every column
    (counted once), the second also an output of the first through an
    instruction that has no event, and a reduction over what they wrote."""
    operands = ", ".join(f"{dtype}[{ROWS}]{{0:T(1024)}} %Arg_{i}.{tag}" for i, dtype in enumerate(columns))
    third = seconds * S / 3
    return [(f"%fusion.{tag} = (f64[{ROWS}]{{0}}, pred[{ROWS}]{{0}}) fusion({operands}, s64[] %n_valid.{tag}), kind=kLoop", at, third),
            (f"%fusion.{tag}1 = pred[{ROWS}]{{0}} fusion({operands}, pred[{ROWS}]{{0}} %get-tuple-element.{tag}), kind=kLoop",
             at + third, third),
            (f"%reduce.{tag} = f64[6]{{0}} reduce(f64[{ROWS}]{{0}} %bitcast.{tag}, f64[] %constant.{tag})", at + 2 * third, third)]


Q1_COLUMNS = ["s64", "s64", "f64", "f64", "f64", "s32", "s32"]  # 48 bytes a row
Q6_COLUMNS = ["s64", "s64", "f64", "f64"]                        # 32 bytes a row


def one_plane():
    """A six-second window from the anchor at 10 s. Grouped program: one call
    the window's start cut (clipped onto the edge), two whole ones of 100 and
    300 ms, one its end cut. Fused program: one whole call of 10 ms. A filter
    program that is neither."""
    at = 10 * S
    modules = [("jit_hs_grouped_agg_dense(12)", at, 0.05 * S), ("jit_hs_grouped_agg_dense(12)", at + 1 * S, 0.1 * S),
               ("jit_hs_grouped_agg_dense(12)", at + 2 * S, 0.3 * S), ("jit_hs_fused_agg(3)", at + 3 * S, 0.01 * S),
               ("jit_hs_fused_filter(5)", at + 4 * S, 1 * S), ("jit_hs_grouped_agg_dense(12)", at + 5.9 * S, 0.1 * S)]
    ops = (_ops(at, 0.05, Q1_COLUMNS, 1) + _ops(at + 1 * S, 0.1, Q1_COLUMNS, 2) + _ops(at + 2 * S, 0.3, Q1_COLUMNS, 3)
           + _ops(at + 3 * S, 0.01, Q6_COLUMNS, 4) + _ops(at + 5.9 * S, 0.1, Q1_COLUMNS, 5))
    return {"/host:CPU": {"python": [(tracing.ANCHOR, at, 1000.0)]},
            "/device:TPU:0": {tracing.OPS_LINE: ops, "XLA Modules": modules}}


def _window(run):
    run.planes, run.trace_window_s = one_plane(), 6.0


def test_the_cell_is_found_by_name_and_is_as_issue_37_names_it():
    cell = next(w for w in M["workloads"] if w["name"] == "sf10-report")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("tpch-sf10-report", "report-closed", 1)
    entry = next(c for c in M["configs"] if c["name"] == "tpch-sf10-report")
    assert entry["reduced"] == ["tables"]
    config = deployment.load_config(entry["file"])
    served = deployment.load_config("hsbench/configs/tpch-sf1.json")
    for key in ("limits", "server"):
        assert config[key] == served[key]
    assert {k: v for k, v in config["guarantees"].items() if k != "version"} == served["guarantees"]
    assert "newest ACTIVE version" in config["guarantees"]["version"]
    cap = config["conf"].pop("hyperspace.tpu.query.deviceCacheBytes")
    assert config["conf"] == served["conf"] and 4 << 30 <= cap <= 8 << 30
    assert config["tables"] == ["lineitem"] and config["scale_factor"] == 10.0
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    (index,) = config["indexes"]
    assert index["indexed"] == ["l_shipdate"] and index["included"] == [
        "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice", "l_discount", "l_tax"]
    mix = traffic.load_mix("report-closed")
    assert (mix["loop"], mix["clients"], mix["params_per_template"], mix["param_seed"]) == ("closed", 2, 4, 37)
    assert mix["tenants"] == ["bi", "reports"] and mix["request_timeout_s"] == 120 and mix["trace_seconds"] == 6
    assert [t["name"] for t in mix["templates"]] == ["q1", "q6"] and "rate_per_s" not in mix
    assert "day (3)" in traffic.Template("q1").sql
    for name in ("q1", "q6"):  # templates, their placeholder files and oracles: found by name
        assert os.path.exists(os.path.join(ROOT, "hsbench", "oracles", f"{name}.py"))
    for name in MINE:
        assert os.path.exists(os.path.join(ROOT, "hsbench", "layers", f"{name}.json"))
    assert traffic.Template("q1").ordered and not traffic.Template("q6").ordered
    assert len(MINE) == 17 and all(n.endswith(".report") for n in MINE)
    e2e = next(m for m in M["end_to_end"] if m["name"] == "queries_per_s")
    assert e2e["workloads"] == ["sf1-analytic", "sf10-report"] and e2e["bound"] == 0.2
    assert all(m["moves"] == "queries_per_s" for m in M["per_layer"] if m["name"] in MINE)


def test_the_pool_is_eight_queries_whatever_the_seed():
    mix = traffic.load_mix("report-closed")
    templates = {n: traffic.Template(n) for n in ("q1", "q6")}
    pools = []
    for seed in (1, 2_900_000_123):
        drawers = {n: traffic.ParamDrawer(t, seed, 0.0, None) for n, t in templates.items()}
        pools.append(traffic.pool(mix, templates, drawers))
    assert pools[0] == pools[1] and len(pools[0]) == 8
    assert all(60 <= p["delta"] <= 120 for n, p in pools[0] if n == "q1")


def test_least_bytes_come_from_the_calls_own_operand_shapes():
    ops = [name for name, _, _ in _ops(0.0, 0.3, Q1_COLUMNS, 7)]
    assert sorted(costs_agg.call_inputs(ops)) == [f"%Arg_{i}.7" for i in range(7)] + ["%n_valid.7"]
    assert sorted(costs_agg.resident_columns(ops)) == sorted((d, ROWS) for d in Q1_COLUMNS)
    assert costs_agg.call_least_bytes(ops) == 48 * ROWS  # every column once, whatever reads it how often
    assert costs_agg.call_least_bytes([n for n, _, _ in _ops(0.0, 0.1, Q6_COLUMNS, 8)]) == 32 * ROWS
    assert costs_agg.call_least_bytes(["%copy.1 = f64[6]{0} copy(f64[6]{0} %x)", "no instruction"]) == 48
    assert costs_agg.call_least_bytes([]) == 0 and datagen.rows_of("lineitem", 10.0) == 60_000_000


def test_agg_roofline_over_a_trace_worked_out_by_hand(traced):
    run, _ = traced
    grouped = json.load(open(os.path.join(ROOT, "hsbench/layers/kernels.grouped_agg_roofline.report.json")))["params"]
    fused = json.load(open(os.path.join(ROOT, "hsbench/layers/kernels.fused_agg_roofline.report.json")))["params"]
    assert agg_roofline.read(run, grouped) is None  # no trace
    _window(run)
    # the two whole calls: 2 x 48,000 bytes at 819 GB/s over 0.1 + 0.3 s; the two that an edge cuts are left out
    assert len(agg_roofline.whole_calls(run, grouped)) == 2 and len(agg_roofline.whole_calls(run, fused)) == 1
    assert agg_roofline.read(run, grouped) == pytest.approx(100.0 * (2 * 48 * ROWS / 819e9) / 0.4)
    assert agg_roofline.read(run, fused) == pytest.approx(100.0 * (32 * ROWS / 819e9) / 0.01)
    parent = one_plane()
    parent["/device:TPU:0"]["XLA Modules"] = [("jit_hs_fused_filter(5)", 11 * S, S)]
    run.planes = parent
    assert agg_roofline.read(run, grouped) is None and agg_roofline.read(run, fused) is None  # the parent's trace
    run.planes = {"/device:TPU:0": one_plane()["/device:TPU:0"]}
    assert agg_roofline.read(run, grouped) is None  # no anchor: not this harness's trace
    with pytest.raises(KeyError):
        run.device_kind = "a chip nobody measured"
        _window(run)
        agg_roofline.read(run, grouped)


def test_the_module_readers_tell_the_two_programs_apart(traced):
    run, _ = traced
    _window(run)
    run.traced_work = 4.0
    assert layers.read_metric("device.grouped_agg_ms_per_query.report", run) == pytest.approx(550.0 / 4)
    assert layers.read_metric("device.fused_agg_ms_per_query.report", run) == pytest.approx(2.5)


def test_the_counter_readers(traced):
    run, counters = traced
    run.work = 10.0
    assert layers.read_metric("dispatch.agg_rows_on_device_share.report", run) is None  # the parent: no such counter
    counters.update({"hs_agg_rows_total{path=device}": 540.0, "hs_agg_rows_total{path=host}": 60.0,
                     "hs_device_cache_lookups_total{result=hit}": 55.0,
                     "hs_h2d_bytes_total{site=agg-cols}": 1000.0, "hs_d2h_bytes_total{site=agg-table}": 20.0})
    assert layers.read_metric("dispatch.agg_rows_on_device_share.report", run) == pytest.approx(90.0)
    assert layers.read_metric("device.cache_hit_share.report", run) == pytest.approx(100.0)
    assert layers.read_metric("link.h2d_bytes_per_query.report", run) == pytest.approx(100.0)
    assert layers.read_metric("link.d2h_bytes_per_query.report", run) == pytest.approx(2.0)
    assert layers.read_metric("decode.bytes_per_query.report", run) == 0.0


@pytest.mark.parametrize("name", MINE)
def test_a_new_metric_reads_a_number_or_nothing_and_does_not_raise(traced, name):
    run, _ = traced
    assert layers.read_metric(name, run) in (None, 0.0)
    _window(run)
    run.traced_work, run.work, run.trace_busy_s = 3.0, 3.0, 0.56
    got = layers.read_metric(name, run)
    assert got is None or isinstance(got, float)


def _rehearse(module: str, seed: int, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return subprocess.run(
        [sys.executable, "-m", module, "--workload", "sf10-report", "--seed", str(seed),
         "--seconds", "3", "--rehearse-on-cpu", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_the_cell_runs_in_rehearsal_and_is_correct():
    p = _rehearse("hsbench.run", 3_700_000_127, "--trace", "1")
    assert p.returncode == hsrun.REHEARSAL_EXIT, p.stdout[-2000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 8
    assert last["metrics"] == {}
    assert "oracle: 8 computations" in p.stdout
    for reading in ("serving.latency_p50_ms.report", "serving.plan_cache_hit_rate.report",
                    "plan.ms_per_query.report", "link.h2d_bytes_per_query.report"):
        assert f"not a measurement: {reading} = " in p.stdout


def test_the_float32_control_comes_out_not_correct():
    p = _rehearse("hsbench.control", 3_700_000_128)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = next(l for l in p.stdout.splitlines() if l.startswith("control: "))
    control = json.loads(line.split(": ", 1)[1])
    assert control["sound"] is True and control["float32_aggregates"]["correct"] is False
    assert control["float32_aggregates"]["numbers"]["answer.float_rel_gap"] > 1e-9
