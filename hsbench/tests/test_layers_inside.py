"""The readers of the per-layer metrics that read the program's own stages,
counters, spans and annotations (PR 25), over a synthetic traced run whose
numbers can be worked out by hand."""

import json
import os
from types import SimpleNamespace

import pytest

from hsbench import layers, run as hsrun, tracing
from hsbench.layers import (counter_label_ratio, idle_under_annotation, module_ms_per_work,
                            stage_seconds_per_work)

DEVICE = "/device:TPU:0"
HOST = "/host:CPU"


@pytest.fixture()
def traced(monkeypatch):
    """A TracedRun whose counters are what the test says they are."""
    counters = {}
    monkeypatch.setattr(hsrun, "all_counters", lambda: dict(counters))
    run = hsrun.TracedRun("TPU v5 lite")
    run.mark()
    return run, counters


def span(name, t0, t1, cat="", children=()):
    s = SimpleNamespace(name=name, cat=cat, t0=t0, t1=t1, children=list(children), events=[])
    s.duration_s = t1 - t0
    s.walk = lambda: [s] + [d for c in s.children for d in c.walk()]
    return s


def test_stage_seconds_per_work(traced):
    run, counters = traced
    spec = {"cat": "build", "stages": ["decode-keys", "decode-payload"]}
    assert stage_seconds_per_work.read(run, spec) is None  # no work yet
    run.work = 60.0  # million rows
    assert stage_seconds_per_work.read(run, spec) is None  # a program without the counter
    counters.update({
        "hs_stage_seconds_total{cat=build,stage=decode-keys}": 1.5,
        "hs_stage_seconds_total{cat=build,stage=decode-payload}": 4.5,
        "hs_stage_seconds_total{cat=build,stage=take-write}": 30.0,
        "hs_stage_seconds_total{cat=refresh,stage=decode-keys}": 100.0,
    })
    assert stage_seconds_per_work.read(run, spec) == pytest.approx(6.0 / 60.0)
    assert stage_seconds_per_work.read(run, {"cat": "build", "stages": ["take-write"]}) == pytest.approx(0.5)


def test_counter_label_ratio(traced):
    run, counters = traced
    spec = {"counter": "hs_stage_seconds_total", "label": "stage",
            "numerator": ["write"], "denominator": ["take", "write"]}
    assert counter_label_ratio.read(run, spec) is None
    counters.update({
        "hs_stage_seconds_total{cat=build,stage=take}": 10.0,
        "hs_stage_seconds_total{cat=build,stage=write}": 30.0,
        "hs_stage_seconds_total{cat=build,stage=take-write}": 7.0,  # another stage: not "take"
    })
    assert counter_label_ratio.read(run, spec) == pytest.approx(75.0)


def test_counter_per_work_reads_the_new_counters_as_it_is(traced):
    run, counters = traced
    run.work = 4.0
    counters.update({"hs_d2h_bytes_total{site=filter-mask}": 24e6, "hs_d2h_bytes_total{site=agg-table}": 8e6,
                     "hs_h2d_bytes_total{site=filter-cols}": 1e9})
    spec = json.load(open(os.path.join(layers.HERE, "link.d2h_bytes_per_query.lookup.json")))
    assert spec["reader"] == "counter_per_work"
    assert layers.read_metric("link.d2h_bytes_per_query.lookup", run) == pytest.approx(8e6)


def planes_with(ops, modules=(), host=()):
    return {DEVICE: {tracing.OPS_LINE: list(ops), "XLA Modules": list(modules)},
            HOST: {"python": list(host)}}


def test_idle_under_annotation(traced):
    run, _ = traced
    spec = {"annotation": "hs:build:take-write"}
    assert idle_under_annotation.read(run, spec) is None  # no trace
    s = 1e9
    # a 10 s slice from the anchor at t=100 s; the device works 101-102 and 105-106
    ops = [("%sort", 101 * s, 1 * s), ("%_hist_call", 105 * s, 1 * s)]
    host = [(tracing.ANCHOR, 100 * s, 10.0), ("hs:build:take-write", 101.5 * s, 2.5 * s),
            ("hs:build:take-write", 107 * s, 2 * s), ("hs:build:decode-keys", 100 * s, 1 * s)]
    run.planes = planes_with(ops, host=host)
    run.trace_window_s = 10.0
    # idle: 100-101, 102-105, 106-110 = 8 s; inside the drain's annotations:
    # 102-104 and 107-109 = 4 s
    assert idle_under_annotation.read(run, spec) == pytest.approx(50.0)
    run.planes = planes_with(ops, host=[h for h in host if not h[0].startswith("hs:build:take")])
    assert idle_under_annotation.read(run, spec) is None  # a program without the annotation


def test_module_ms_per_work(traced):
    run, _ = traced
    spec = {"line": "XLA Modules", "contains": ["hs_grouped_agg", "hs_fused_stage_agg"]}
    assert module_ms_per_work.read(run, spec) is None
    modules = [("jit_hs_grouped_agg_chunk(123)", 0.0, 30e6), ("jit_hs_fused_stage_agg(7)", 1e9, 10e6),
               ("jit_hs_bucketed_smj_span(9)", 2e9, 500e6), ("jit_concatenate(1)", 3e9, 1e6)]
    run.planes = planes_with([], modules=modules)
    assert module_ms_per_work.read(run, spec) is None  # nothing finished in the slice
    run.traced_work = 8.0
    assert module_ms_per_work.read(run, spec) == pytest.approx(40.0 / 8.0)
    join = {"line": "XLA Modules", "contains": ["hs_bucketed_smj_span", "hs_fused_stage_join_agg"]}
    assert module_ms_per_work.read(run, join) == pytest.approx(500.0 / 8.0)
    run.planes = planes_with([("%fusion", 0.0, 1e6)])
    assert module_ms_per_work.read(run, spec) is None  # a trace without a module line


def test_span_self_time_reads_the_new_spans_as_it_is(traced):
    run, _ = traced
    wait = span("device-wait", 1.010, 1.030, "device")
    mask = span("filter-mask", 1.000, 1.050, "exec", [wait])
    apply_ = span("filter-apply", 1.050, 1.150, "exec")
    queue = span("queue-wait", 0.990, 1.000, "serving", [span("batch-wait", 0.998, 1.000, "serving")])
    root = span("request", 0.980, 1.200, "query", [queue, span("Filter", 1.0, 1.15, "exec", [mask, apply_])])
    run.outcomes = [SimpleNamespace(root=root, done=1.2), SimpleNamespace(root=None, done=1.3)]
    assert layers.read_metric("dispatch.filter_mask_ms.lookup", run) == pytest.approx(50.0)  # own 30 + wait 20
    assert layers.read_metric("dispatch.filter_apply_ms.lookup", run) == pytest.approx(100.0)
    assert layers.read_metric("serving.queue_wait_ms.lookup", run) == pytest.approx(8.0)  # batch-wait is its child's
    run.outcomes = []
    assert layers.read_metric("serving.queue_wait_ms.analytic", run) is None


NEW_IN_PR_25 = [
    "decode.s_per_mrow.build", "decode.bytes_per_mrow.build", "link.d2h_wait_s_per_mrow.build",
    "link.d2h_bytes_per_mrow.build", "drain.s_per_mrow.build", "drain.write_share.build",
    "device.idle_in_drain_share.build", "serving.queue_wait_ms.lookup", "serving.queue_wait_ms.analytic",
    "dispatch.filter_mask_ms.lookup", "dispatch.filter_apply_ms.lookup", "dispatch.host_join_ms.analytic",
    "dispatch.device_wait_ms.analytic", "link.d2h_bytes_per_query.lookup", "link.d2h_bytes_per_query.analytic",
    "device.grouped_agg_ms_per_query.analytic", "device.span_join_ms_per_query.analytic",
]


@pytest.mark.parametrize("name", NEW_IN_PR_25)
def test_a_new_metric_reads_nothing_from_an_empty_run_and_does_not_raise(traced, name):
    run, _ = traced
    assert layers.read_metric(name, run) in (None, 0.0)
