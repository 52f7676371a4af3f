"""The four-chip build cell ``sf10-build-x4`` (PR 27): its readers over a
hand-made trace of two device planes whose numbers can be worked out by hand,
over a one-plane trace (nothing to read), and the cell itself in rehearsal on
four virtual CPU devices, where it has to take the mesh path."""

import json
import os
import subprocess
import sys

import pytest

from hsbench import costs_exchange, deployment, layers, peaks_ici, run as hsrun, tracing
from hsbench.deployment import ROOT
from hsbench.layers import all_to_all_roofline, counter_label_spread, op_share, plane_busy_spread

S = 1e9
# as the v5e's trace names it (my chip run, PR 27): the instruction with underscores, the opcode with dashes
A2A = ("%all_to_all.3 = s32[4,262144,4]{1,2,0:T(4,128)S(1)} all-to-all(s32[4,262144,4]{1,2,0:T(4,128)S(1)} %copy.9), "
       "channel_id=1, replica_groups={{0,1,2,3}}, dimensions={0}")
SORT = "%sort.5 = (s32[1048576]{0}, s32[1048576]{0}) sort(s32[1048576]{0} %a, s32[1048576]{0} %b)"
# a fusion that only names the collective among its operands is not the collective
USER = "%fusion.11 = s32[1048576]{0} fusion(s32[4,262144,4]{2,1,0} %all_to_all.3), kind=kLoop"


@pytest.fixture()
def traced(monkeypatch):
    counters = {}
    monkeypatch.setattr(hsrun, "all_counters", lambda: dict(counters))
    run = hsrun.TracedRun("TPU v5 lite")
    run.mark()
    return run, counters


def two_planes():
    """Chip 0: all-to-all 1 s, then a sort of 3 s; chip 1: all-to-all 2 s (it
    waited), a sort of 1 s, a fusion of 1 s that overlaps nothing."""
    return {
        "/device:TPU:0": {tracing.OPS_LINE: [(A2A, 10 * S, 1 * S), (SORT, 11 * S, 3 * S)],
                          "XLA Modules": [("jit_hs_index_build_exchange(7)", 10 * S, 4 * S)]},
        "/device:TPU:1": {tracing.OPS_LINE: [(A2A, 10 * S, 2 * S), (SORT, 12 * S, 1 * S), (USER, 14 * S, 1 * S)],
                          "XLA Modules": [("jit_hs_index_build_exchange(7)", 10 * S, 5 * S)]},
        "/host:CPU": {"python": [(tracing.ANCHOR, 9 * S, 10.0)]},
    }


def one_plane():
    return {"/device:TPU:0": {tracing.OPS_LINE: [(A2A, 10 * S, 1 * S), (SORT, 11 * S, 3 * S)],
                              "XLA Modules": [("jit_hs_index_build(7)", 10 * S, 4 * S)]}}


def test_plane_busy_spread(traced):
    run, _ = traced
    assert plane_busy_spread.read(run, {}) is None  # no trace
    run.planes = two_planes()
    assert plane_busy_spread.read(run, {}) == pytest.approx(100.0)  # 4 s and 4 s
    run.planes["/device:TPU:1"][tracing.OPS_LINE].pop()  # chip 1 without its fusion: 3 s against 4 s
    assert plane_busy_spread.read(run, {}) == pytest.approx(75.0)
    run.planes = one_plane()
    assert plane_busy_spread.read(run, {}) is None


def test_op_share_counts_the_operation_and_not_its_users(traced):
    run, _ = traced
    spec = {"op": "all-to-all"}
    assert op_share.read(run, spec) is None
    run.planes = two_planes()
    assert op_share.is_op(A2A, "all-to-all") and not op_share.is_op(USER, "all-to-all")
    assert op_share.is_op("%all-to-all-start.1 = (s32[8]) all-to-all-start(s32[8] %x)", "all-to-all")
    assert op_share.is_op("%all_to_all.3", "all-to-all") and not op_share.is_op("%all-reduce.1", "all-to-all")
    assert not op_share.is_op("%sort.5 = s32[8] sort(s32[8] %all_to_all.3)", "all-to-all")
    # 1 + 2 s of all-to-all in 4 + 4 s of operations
    assert op_share.read(run, spec) == pytest.approx(100.0 * 3.0 / 8.0)
    per_plane = op_share.op_seconds_by_plane(run.planes, "all-to-all")
    assert [calls for _, calls in per_plane.values()] == [1, 1]
    run.planes = one_plane()
    assert op_share.read(run, spec) is None  # one chip: a collective means nothing there
    run.planes = {p: {tracing.OPS_LINE: [(SORT, 0.0, S)]} for p in ("/device:TPU:0", "/device:TPU:1")}
    assert op_share.read(run, spec) is None  # a program without the operation


def test_all_to_all_against_the_interconnect(traced):
    run, _ = traced
    spec = {"op": "all-to-all", "key_bytes": 4, "row_index_bytes": 4}
    run.planes = two_planes()
    assert all_to_all_roofline.read(run, spec) is None  # nothing finished in the slice
    run.traced_work = 60.0  # million rows
    # two chips: 30 M rows a chip, half of them leave, 8 bytes each = 120 MB;
    # at 200 GB/s that is 0.6 ms, against a mean of 1.5 s of all-to-all a chip
    least = costs_exchange.least_bytes_leaving_one_chip(60e6, 2, 4, 4)
    assert least == pytest.approx(120e6)
    assert peaks_ici.ici_peaks("TPU v5 lite")["ici_bytes_per_s"] == pytest.approx(200e9)
    assert all_to_all_roofline.read(run, spec) == pytest.approx(100.0 * 0.0006 / 1.5)
    run.planes = one_plane()
    assert all_to_all_roofline.read(run, spec) is None
    assert costs_exchange.least_bytes_leaving_one_chip(60e6, 1, 4, 4) == 0.0
    # four chips, li_sd: 15 M rows a chip, three quarters leave, 8 bytes each
    assert costs_exchange.least_bytes_leaving_one_chip(60e6, 4, 4) == pytest.approx(90e6)
    with pytest.raises(KeyError):
        peaks_ici.ici_peaks("a chip nobody measured")


def test_counter_label_spread(traced):
    run, counters = traced
    spec = {"counter": "hs_build_exchange_rows_total", "label": "device"}
    assert counter_label_spread.read(run, spec) is None  # the parent: no such counter
    counters.update({"hs_build_exchange_rows_total{device=0}": 900.0})
    assert counter_label_spread.read(run, spec) is None  # a mesh of one
    counters.update({"hs_build_exchange_rows_total{device=1}": 1100.0,
                     "hs_build_exchange_rows_total{device=2}": 1000.0,
                     "hs_build_exchange_rows_total{device=3}": 1000.0,
                     "hs_build_exchange_slots_total{kind=valid}": 4000.0})
    assert counter_label_spread.read(run, spec) == pytest.approx(90.0)  # 900 against a mean of 1000


def test_the_existing_readers_read_the_mesh_build(traced):
    run, counters = traced
    run.planes, run.traced_work, run.work = two_planes(), 60.0, 180.0
    run.trace_busy_s, run.trace_window_s = tracing.busy_seconds(run.planes), 10.0
    assert run.trace_busy_s == pytest.approx(4.0)  # the mean of the planes
    assert layers.read_metric("device.idle_share.build-x4", run) == pytest.approx(60.0)
    # chip-milliseconds, summed over the planes: 4 s + 5 s over 60 M rows
    assert layers.read_metric("device.exchange_ms_per_mrow.build-x4", run) == pytest.approx(150.0)
    counters.update({"hs_build_exchange_slots_total{kind=valid}": 2.0e6,
                     "hs_build_exchange_slots_total{kind=shipped}": 4.0e6,
                     "hs_build_exchange_retries_total{}": 9.0,
                     "hs_h2d_bytes_total{site=build-keys}": 360e6})
    assert layers.read_metric("exchange.slot_fill_share.build-x4", run) == pytest.approx(50.0)
    assert layers.read_metric("exchange.retries_per_mrow.build-x4", run) == pytest.approx(0.05)
    assert layers.read_metric("link.h2d_bytes_per_mrow.build-x4", run) == pytest.approx(2e6)
    run.planes = one_plane()  # the one-chip program's module is not the exchange
    assert layers.read_metric("device.exchange_ms_per_mrow.build-x4", run) == pytest.approx(0.0)


M = deployment.manifest()
MINE = [m["name"] for m in M["per_layer"] if m.get("workloads") == ["sf10-build-x4"]]


def test_the_cell_is_as_issue_27_names_it():
    cell = next(w for w in M["workloads"] if w["name"] == "sf10-build-x4")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("tpch-sf10-mesh", "build-li-sd", 4)
    config = deployment.load_config("hsbench/configs/tpch-sf10-mesh.json")
    one_chip = deployment.load_config("hsbench/configs/tpch-sf10.json")
    assert config["conf"] == {"hyperspace.parallel.enabled": True} and config["tables"] == ["lineitem"]
    for key in ("guarantees", "limits", "check_sample_buckets", "scale_factor"):
        assert config[key] == one_chip[key]
    ours = {i["name"]: i for i in config["indexes"]}
    assert ours == {i["name"]: i for i in one_chip["indexes"] if i["name"] in ("li_sd", "li_ok")}
    assert len(MINE) == 13 and all(n.endswith(".build-x4") for n in MINE)
    e2e = next(m for m in M["end_to_end"] if m["name"] == "build_rows_per_s")
    assert e2e["workloads"] == ["sf10-build", "sf10-build-x4"] and e2e["bound"] == 0.22


@pytest.mark.parametrize("name", MINE)
def test_a_new_metric_reads_nothing_from_an_empty_run_and_does_not_raise(traced, name):
    run, _ = traced
    assert layers.read_metric(name, run) in (None, 0.0)
    run.planes, run.traced_work, run.work = one_plane(), 60.0, 60.0  # a one-chip trace, the parent's counters
    run.trace_busy_s, run.trace_window_s = 4.0, 10.0
    # only what a one-chip build has too gives a number there
    one_chip_too = ("device.idle_share", "device.exchange_ms", "exchange.retries", "link.")
    assert layers.read_metric(name, run) is None or name.startswith(one_chip_too)


def test_the_cell_takes_the_mesh_path_in_rehearsal_and_is_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-m", "hsbench.run", "--workload", "sf10-build-x4", "--seed", "2700000127",
         "--seconds", "3", "--trace", "1", "--rehearse-on-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == hsrun.REHEARSAL_EXIT, p.stdout[-2000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 1
    assert last["device"]["count"] == 4 and last["metrics"] == {}
    grew = json.loads(next(l for l in p.stdout.splitlines() if l.startswith("counters that grew"))
                      .split(": ", 1)[1])
    rows = [grew[f"hs_build_exchange_rows_total{{device={d}}}"] for d in range(4)]
    assert all(r > 0 for r in rows)
    assert sum(rows) == grew["hs_build_exchange_slots_total{kind=valid}"] == grew["hs_build_rows_total{}"]
    assert grew["hs_build_exchange_slots_total{kind=shipped}"] >= 2 * sum(rows) * 0.9
    assert "hs_stage_seconds_total{cat=build,stage=exchange-drain}" in grew
    for reading in ("exchange.slot_fill_share.build-x4", "exchange.chip_row_flatness.build-x4",
                    "link.d2h_bytes_per_mrow.build-x4", "drain.s_per_mrow.build-x4"):
        assert f"not a measurement: {reading} = " in p.stdout
