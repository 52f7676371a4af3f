"""``wait_split`` (PR 39) over hand-built planes: launches and waits as the
program's annotations on host threads, programs as ``XLA Modules`` events of one
device, every time a whole number of nanoseconds that can be worked out by hand."""

import json
import os
from types import SimpleNamespace

import pytest

from hsbench import layers, run as hsrun, tracing
from hsbench.layers import wait_split

DEVICE = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1e6  # ns
LO = 1000 * MS  # the anchor: the traced window opens here
FILTER, AGG = "jit_hs_fused_filter", "jit_hs_fused_agg"


def launch(request, module, t0, t1):
    return (f"hs:device:device-launch module={module} request={request}", t0, t1 - t0)


def wait(request, t0, t1):
    return (f"hs:device:device-wait request={request}", t0, t1 - t0)


def mod(name, t0, t1, fingerprint=7):
    return (f"{name}({fingerprint})", t0, t1 - t0)


def traced(threads: dict, modules, window_ms=100.0, work=2.0, outcomes=()):
    """A TracedRun over ``threads`` ({thread name: events}) and ``modules``,
    clipped to the window as ``run._reduce_trace`` does."""
    whole = {DEVICE: {tracing.OPS_LINE: [], wait_split.MODULES_LINE: list(modules)},
             HOST: {"main": [(tracing.ANCHOR, LO, 10.0)], **{k: list(v) for k, v in threads.items()}}}
    run = hsrun.TracedRun("TPU v5 lite")
    run.trace_window_s = window_ms / 1e3
    run.planes = tracing.clip(whole, tracing.trace_window(whole, run.trace_window_s))
    run.traced_work = work
    run.outcomes = list(outcomes)
    return run


def pieces(run) -> dict:
    return {p: wait_split.read(run, {"piece": p}) for p in wait_split.PIECES}


def ns(run) -> dict:
    """The four pieces in ns, summed over the waits."""
    found = wait_split.split(run)
    return {p: sum(w[p] for _, w in found["waits"]) for p in wait_split.PIECES}


def test_two_requests_interleaved_on_one_device_add_up_to_their_waits():
    t = LO
    # A launches at +1 ms and waits +2..+40; its program runs +5..+20.
    # B launches at +3 ms and waits +4..+45; its program queues behind A's: +20..+38.
    a = [launch("A", FILTER, t + 1 * MS, t + 2 * MS), wait("A", t + 2 * MS, t + 40 * MS)]
    b = [launch("B", AGG, t + 3 * MS, t + 4 * MS), wait("B", t + 4 * MS, t + 45 * MS)]
    modules = [mod(FILTER, t + 5 * MS, t + 20 * MS), mod(AGG, t + 20 * MS, t + 38 * MS)]
    run = traced({"hs-serve-0": a, "hs-serve-1": b}, modules)
    found = wait_split.split(run)
    by_request = dict(found["waits"])
    # A: gap 2..5, own 5..20, then B's program 20..38 (its own result ready), idle 38..40
    assert {p: by_request["A"][p] for p in wait_split.PIECES} == {
        "start_gap": 3 * MS, "run": 15 * MS, "queued": 18 * MS, "tail": 2 * MS}
    assert by_request["A"]["queued_after_own"] == 18 * MS
    # B: idle 4..5 before its program began, A's program 5..20, own 20..38, idle 38..45
    assert {p: by_request["B"][p] for p in wait_split.PIECES} == {
        "start_gap": 1 * MS, "queued": 15 * MS, "run": 18 * MS, "tail": 7 * MS}
    assert by_request["B"]["queued_after_own"] == 0.0
    total = ns(run)
    assert sum(total.values()) == (38 + 41) * MS  # to the nanosecond: the two waits
    assert found["unmatched_ns"] == 0 and len(found["lags"]) == 2 and found["busy_ns"] == 33 * MS
    got = pieces(run)  # ms a query, two queries done in the slice
    assert got == {"run": 16.5, "queued": 16.5, "start_gap": 2.0, "tail": 4.5}


def test_fifo_join_of_two_requests_launching_the_same_module():
    t = LO
    # both launch the filter; the device runs them in launch order, B's well
    # after both launches: the k-th launch is the k-th module event
    a = [launch("A", FILTER, t + 1 * MS, t + 2 * MS), wait("A", t + 2 * MS, t + 30 * MS)]
    b = [launch("B", FILTER, t + 3 * MS, t + 4 * MS), wait("B", t + 4 * MS, t + 52 * MS)]
    modules = [mod(FILTER, t + 10 * MS, t + 30 * MS), mod(FILTER, t + 30 * MS, t + 50 * MS)]
    run = traced({"hs-serve-0": a, "hs-serve-1": b}, modules)
    launches, waits = wait_split.annotations(run.planes)
    owner, lags = wait_split.join(launches, sorted(modules, key=lambda e: e[1]), LO)
    assert owner == ["A", "B"] and lags == [9 * MS, 27 * MS]
    by_request = dict(wait_split.split(run)["waits"])
    assert by_request["A"]["run"] == 20 * MS and by_request["A"]["queued"] == 0.0
    assert by_request["B"]["run"] == 20 * MS and by_request["B"]["queued"] == 20 * MS


def test_a_module_from_before_the_trace_is_matched_to_none_and_does_not_shift_the_join():
    t = LO
    # the first filter event was launched before the profiler started (no
    # annotation): it began before the first launch inside the trace
    a = [launch("A", FILTER, t + 6 * MS, t + 7 * MS), wait("A", t + 7 * MS, t + 30 * MS)]
    modules = [mod(FILTER, t - 5 * MS, t + 8 * MS), mod(FILTER, t + 8 * MS, t + 28 * MS)]
    run = traced({"hs-serve-0": a}, modules)
    found = wait_split.split(run)
    pieces_a = dict(found["waits"])["A"]
    assert pieces_a["queued"] == 1 * MS and pieces_a["run"] == 20 * MS and pieces_a["tail"] == 2 * MS
    assert found["unmatched_ns"] == 8 * MS  # the part of it inside the window
    # a launch from before the window, whose own module lies outside it, does
    # not take the next launch's
    a = [launch("A", FILTER, t - 9 * MS, t - 8 * MS), wait("A", t - 8 * MS, t + 1 * MS),
         launch("A2", FILTER, t + 2 * MS, t + 3 * MS), wait("A2", t + 3 * MS, t + 20 * MS)]
    run = traced({"hs-serve-0": a}, [mod(FILTER, t - 7 * MS, t - 1 * MS), mod(FILTER, t + 4 * MS, t + 19 * MS)])
    by_request = dict(wait_split.split(run)["waits"])
    assert by_request["A"] == {"run": 0.0, "queued": 0.0, "start_gap": 0.0, "tail": 1 * MS, "queued_after_own": 0.0}
    assert by_request["A2"]["run"] == 15 * MS


def test_a_module_recorded_just_before_its_launch_began_is_still_its_own():
    t = LO
    # the device plane's clock runs ahead of the host planes' (1.02 ms at most in
    # PR 39's runs): the module "starts" 1 ms before its launch's annotation
    a = [launch("A", FILTER, t + 10 * MS, t + 11 * MS), wait("A", t + 11 * MS, t + 20 * MS)]
    run = traced({"hs-serve-0": a}, [mod(FILTER, t + 9 * MS, t + 18 * MS)])
    found = wait_split.split(run)
    assert found["lags"] == [-1 * MS] and dict(found["waits"])["A"]["run"] == 7 * MS
    # further back than SKEW_NS it is an earlier launch's
    run = traced({"hs-serve-0": a}, [mod(FILTER, t + 10 * MS - wait_split.SKEW_NS - 1, t + 18 * MS)])
    found = wait_split.split(run)
    assert found["lags"] == [] and dict(found["waits"])["A"]["queued"] == 7 * MS
    # a wrong match shows: the module ends after the last wait of the request it was given to
    b = [launch("B", FILTER, t + 1 * MS, t + 2 * MS), wait("B", t + 2 * MS, t + 5 * MS)]
    assert wait_split.split(traced({"hs-serve-0": b}, [mod(FILTER, t + 30 * MS, t + 40 * MS)]))["late"] == 1
    assert wait_split.split(traced({"hs-serve-0": a}, [mod(FILTER, t + 12 * MS, t + 18 * MS)]))["late"] == 0


def test_a_wait_whose_module_ended_before_it_began_is_all_tail():
    t = LO
    a = [launch("A", AGG, t + 1 * MS, t + 2 * MS), wait("A", t + 20 * MS, t + 26 * MS)]
    run = traced({"hs-serve-0": a}, [mod(AGG, t + 3 * MS, t + 12 * MS)])
    assert ns(run) == {"run": 0.0, "queued": 0.0, "start_gap": 0.0, "tail": 6 * MS}


def test_a_wait_and_a_module_cut_by_either_edge_of_the_window():
    t = LO
    # left edge: the wait began 10 ms before the window, its module 4 ms before;
    # launched before the window, the module is left to no one (the join's rule)
    left = [launch("A", AGG, t - 12 * MS, t - 11 * MS), wait("A", t - 10 * MS, t + 9 * MS)]
    # right edge (window of 100 ms): wait +80..+130, module +85..+120
    right = [launch("B", FILTER, t + 79 * MS, t + 80 * MS), wait("B", t + 80 * MS, t + 130 * MS)]
    modules = [mod(AGG, t - 4 * MS, t + 6 * MS), mod(FILTER, t + 85 * MS, t + 120 * MS)]
    run = traced({"hs-serve-0": left, "hs-serve-1": right}, modules)
    by_request = dict(wait_split.split(run)["waits"])
    assert {p: by_request["A"][p] for p in wait_split.PIECES} == {
        "run": 0.0, "queued": 6 * MS, "start_gap": 0.0, "tail": 3 * MS}  # 9 ms of the wait lie inside
    assert {p: by_request["B"][p] for p in wait_split.PIECES} == {
        "start_gap": 5 * MS, "run": 15 * MS, "queued": 0.0, "tail": 0.0}  # 20 ms inside
    assert sum(ns(run).values()) == 29 * MS
    # a wait wholly outside the window is not counted
    out = [launch("C", AGG, t + 101 * MS, t + 102 * MS), wait("C", t + 102 * MS, t + 110 * MS)]
    run = traced({"hs-serve-0": out}, [mod(AGG, t + 103 * MS, t + 108 * MS)])
    assert wait_split.split(run)["waits"] == [] and pieces(run)["run"] == 0.0


def test_a_helper_thread_s_launch_counts_for_its_request():
    t = LO
    # request A's worker waits; a pool thread it wrapped launched the program
    worker = [wait("A", t + 3 * MS, t + 24 * MS)]
    helper = [launch("A", FILTER, t + 1 * MS, t + 2 * MS)]
    other = [launch("B", FILTER, t + 2 * MS, t + 3 * MS), wait("B", t + 3 * MS, t + 40 * MS)]
    modules = [mod(FILTER, t + 4 * MS, t + 24 * MS), mod(FILTER, t + 24 * MS, t + 39 * MS)]
    run = traced({"hs-serve-0": worker, "decode-pool-3": helper, "hs-serve-1": other}, modules)
    by_request = dict(wait_split.split(run)["waits"])
    assert by_request["A"]["run"] == 20 * MS and by_request["A"]["queued"] == 0.0
    assert by_request["B"]["queued"] == 20 * MS and by_request["B"]["run"] == 15 * MS


def test_none_without_planes_without_work_and_over_a_program_that_names_no_request():
    run = hsrun.TracedRun("TPU v5 lite")
    assert wait_split.read(run, {"piece": "run"}) is None  # no trace
    t = LO
    # the parent's annotations: no suffix, so no request and nothing to read
    bare = [("hs:device:device-wait", t + 2 * MS, 10 * MS), ("hs:exec:filter-mask", t + 1 * MS, 12 * MS)]
    run = traced({"hs-serve-0": bare}, [mod(FILTER, t + 3 * MS, t + 9 * MS)])
    assert pieces(run) == dict.fromkeys(wait_split.PIECES)
    named = [launch("A", FILTER, t + 1 * MS, t + 2 * MS), wait("A", t + 2 * MS, t + 12 * MS)]
    run = traced({"hs-serve-0": named}, [mod(FILTER, t + 3 * MS, t + 9 * MS)], work=0.0)
    assert wait_split.read(run, {"piece": "run"}) is None  # nothing finished in the slice


def test_the_table_by_template_names_the_requests_through_their_roots(capsys):
    t = LO
    a = [launch("r1", AGG, t + 1 * MS, t + 2 * MS), wait("r1", t + 2 * MS, t + 12 * MS)]
    b = [launch("r2", FILTER, t + 3 * MS, t + 4 * MS), wait("r2", t + 4 * MS, t + 20 * MS)]

    def outcome(ident, name):
        root = SimpleNamespace(trace=SimpleNamespace(id=ident))
        return SimpleNamespace(root=root, done=1.0, request=SimpleNamespace(template=SimpleNamespace(name=name)))

    run = traced({"hs-serve-0": a, "hs-serve-1": b},
                 [mod(AGG, t + 3 * MS, t + 11 * MS), mod(FILTER, t + 11 * MS, t + 19 * MS)],
                 outcomes=[outcome("r1", "q1"), outcome("r2", "q6"), SimpleNamespace(root=None, done=None)])
    assert pieces(run)["queued"] == pytest.approx((1 + 7) / 2.0)
    text = capsys.readouterr().out
    assert text.count("wait split:") == 1  # four metrics, one split, one table
    assert "2 device-wait annotations of 2 requests" in text and "2 matched to a launch, 0 to none" in text
    assert "q1 (1): run 8.000, queued 1.000, start_gap 1.000, tail 0.000, queued_after_own 1.000" in text
    assert "q6 (1): run 8.000, queued 7.000, start_gap 0.000, tail 1.000" in text


#: one entry a quantity, listing both served cells (both move queries_per_s), and one
#: for the commit's seconds in both build cells: test_report_cell and test_mesh_cell pin
#: how many metrics list sf10-report or sf10-build-x4 alone
NEW_IN_PR_39 = {
    **{f"dispatch.device_wait_{p}_ms": ("wait_split", ["sf1-analytic", "sf10-report"]) for p in wait_split.PIECES},
    "dispatch.device_launch_ms": ("span_self_time", ["sf1-analytic", "sf10-report"]),
    "dispatch.device_dispatches_per_query": ("counter_per_work", ["sf1-analytic", "sf10-report"]),
    "commit.s_per_mrow": ("stage_seconds_per_work", ["sf10-build", "sf10-build-x4"]),
    "device.idle_in_commit_share.build": ("idle_under_annotation", ["sf10-build"]),
}


@pytest.mark.parametrize("name", sorted(NEW_IN_PR_39))
def test_a_new_metric_is_declared_has_its_reader_and_reads_nothing_from_an_empty_run(name):
    reader, cells = NEW_IN_PR_39[name]
    with open(os.path.join(layers.HERE, f"{name}.json")) as f:
        assert json.load(f)["reader"] == reader
    with open(os.path.join(os.path.dirname(os.path.dirname(layers.HERE)), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = [m for m in manifest["per_layer"] if m["name"] == name]
    assert len(entry) == 1 and entry[0]["workloads"] == cells
    e2e = {m["name"]: m["workloads"] for m in manifest["end_to_end"] if "workloads" in m}
    assert all(c in e2e[entry[0]["moves"]] for c in cells)  # every cell reports the metric it moves
    assert layers.read_metric(name, hsrun.TracedRun("TPU v5 lite")) is None


def test_launch_time_and_dispatch_count_read_the_span_and_the_counter(monkeypatch):
    counters = {}
    monkeypatch.setattr(hsrun, "all_counters", lambda: dict(counters))
    run = hsrun.TracedRun("TPU v5 lite")
    run.mark()

    def span(name, t0, t1, children=()):
        s = SimpleNamespace(name=name, t0=t0, t1=t1, children=list(children), duration_s=t1 - t0)
        s.walk = lambda: [s] + [d for c in s.children for d in c.walk()]
        return s

    mask = span("filter-mask", 1.000, 1.050, [span("device-launch", 1.010, 1.012), span("device-wait", 1.012, 1.040)])
    run.outcomes = [SimpleNamespace(root=span("request", 0.9, 1.1, [mask]), done=1.1)]
    run.work = 4.0
    counters.update({"hs_device_dispatches_total{program=fused-filter}": 6.0,
                     "hs_device_dispatches_total{program=join-expand-gather}": 2.0})
    assert layers.read_metric("dispatch.device_launch_ms", run) == pytest.approx(2.0)
    assert layers.read_metric("dispatch.device_wait_ms.report", run) == pytest.approx(28.0)
    assert layers.read_metric("dispatch.device_dispatches_per_query", run) == pytest.approx(2.0)
    # over the parent: no such span reads 0 ms, the counter what count_dispatch counted
    run.outcomes = [SimpleNamespace(root=span("request", 0.9, 1.1, [span("filter-mask", 1.0, 1.05)]), done=1.1)]
    assert layers.read_metric("dispatch.device_launch_ms", run) == 0.0
