"""The reduction from trace to numbers, on a small trace recorded on the chip:
one create_index over 300,000 rows on one TPU v5 lite."""

import json
import os

import pytest

from hsbench import costs, peaks, tracing
from hsbench.layers import hist_roofline, idle_share, device_ms_per_work

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def planes():
    return tracing.read_planes(os.path.join(DATA, "build_300k_rows.xplane.pb"))


@pytest.fixture(scope="module")
def shape():
    with open(os.path.join(DATA, "build_300k_rows.json")) as f:
        return json.load(f)


def test_device_operations_are_found_and_summed(planes):
    assert tracing.device_planes(planes) == ["/device:TPU:0"]
    ops = tracing.op_seconds(planes)
    top = tracing.top(ops, 3)
    assert top[0][0] == "%sort sort" and top[0][1] == pytest.approx(1.4326e-3, rel=1e-3)
    assert top[1][0] == "%_hist_call.1 custom-call" and top[1][1] == pytest.approx(2.0246e-4, rel=1e-3)
    busy = tracing.busy_seconds(planes)
    assert 0 < busy <= sum(ops.values()) * (1 + 1e-9)
    assert busy == pytest.approx(1.67e-3, rel=0.05)


def test_union_merges_overlaps():
    assert tracing.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_host_spans_go_on_the_profilers_clock_through_the_anchor(planes, shape):
    offset = tracing.anchor_offset_ns(planes, shape["anchor_perf_counter_ns"])
    assert offset == pytest.approx(49372576.0 - shape["anchor_perf_counter_ns"])
    lo, hi = _device_window(planes)
    t0 = (lo - offset) / 1e9
    t1 = (hi - offset) / 1e9
    mid = (t0 + t1) / 2
    gaps = tracing.idle_gaps(planes, [("build:a", t0, mid)], offset, (lo, hi))
    idle = (hi - lo) / 1e9 - tracing.busy_seconds(planes)
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    assert set(gaps) == {"build:a", "(no host span)"}
    with pytest.raises(RuntimeError):
        tracing.anchor_offset_ns({"p": {"l": [("other", 0.0, 1.0)]}}, 0)


def _device_window(planes) -> tuple:
    """First start and last end of anything on the device planes, in trace ns."""
    events = [(s, s + d) for p in tracing.device_planes(planes)
              for line in planes[p].values() for _, s, d in line]
    return min(s for s, _ in events), max(e for _, e in events)


class _Run:
    trace_window_s = 1.0
    traced_work = 0.3

    def __init__(self, planes, kind):
        self.planes, self.device_kind = planes, kind
        self.trace_busy_s = tracing.busy_seconds(planes)


def test_layer_readers_on_the_recorded_trace(planes, shape):
    run = _Run(planes, shape["device"])
    share = hist_roofline.read(run, {"kernel": "_hist_call"})
    least = (524288 + 256) * 4
    assert share == pytest.approx(100 * least / 819e9 / 1.01231e-4, rel=1e-3) and 0 < share < 5
    assert idle_share.read(run, {}) == pytest.approx(100 * (1 - run.trace_busy_s))
    assert device_ms_per_work.read(run, {}) == pytest.approx(1e3 * sum(tracing.op_seconds(planes).values()) / 0.3)
    run.planes = None
    assert hist_roofline.read(run, {"kernel": "_hist_call"}) is None  # nothing to read: left out


def test_an_unknown_device_kind_is_an_error_not_a_default(planes):
    with pytest.raises(KeyError):
        hist_roofline.read(_Run(planes, ""), {"kernel": "_hist_call"})
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_least_bytes_come_from_the_calls_own_shapes():
    text = ('%_hist_call.1 = s32[256,1]{1,0:T(8,128)S(1)} custom-call(s32[1,2097152]{1,0:T(1,128)S(1)} %b), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={s32[1,2097152]{1,0}}')
    assert costs.hist_least_bytes(text) == (2097152 + 256) * 4
    with pytest.raises(ValueError):
        costs.hist_least_bytes("%x = fusion()")
