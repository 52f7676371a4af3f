"""The reduction from trace to numbers, on a small trace recorded on the chip:
one create_index over 300,000 rows on one TPU v5 lite; the traced window, on
synthetic planes and, on the chip alone, on a device that never idles."""

import json
import os
import threading
import time
from types import SimpleNamespace

import pytest

from hsbench import costs, peaks, run as hsrun, tracing
from hsbench.layers import (device_ms_per_work, hist_roofline, idle_share, idle_under_annotation,
                            module_ms_per_work, plane_busy_spread)

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


# -- the traced window -------------------------------------------------------

S = 1e9
MODULES = "XLA Modules"


def _profiler(anchor_perf_ns: int, seconds: float):
    """What ``_reduce_trace`` reads of a ``tracing.Profiler``."""
    started = anchor_perf_ns / 1e9
    return SimpleNamespace(anchor_perf_ns=anchor_perf_ns, started=started, stopped=started + seconds,
                           window_s=seconds)


def _reduce(monkeypatch, planes, profiler, host_spans=()):
    """``run._reduce_trace`` over planes given in place of a file."""
    monkeypatch.setattr(tracing, "read_planes", lambda path: planes)
    run = hsrun.TracedRun("TPU v5 lite")
    return run, hsrun._reduce_trace(run, profiler, "no file", list(host_spans))


def _saturated(*device_planes):
    """A window of 6 s from the anchor at 100 s of the trace's clock; every
    device plane runs operations from before it to after it."""
    planes = {"/host:CPU": {"python": [(tracing.ANCHOR, 100 * S, 10.0), ("hs:build:take-write", 99 * S, 8 * S)]}}
    for n, ops in enumerate(device_planes):
        planes[f"/device:TPU:{n}"] = {tracing.OPS_LINE: ops,
                                      MODULES: [("jit_hs_grouped_agg_chunk(1)", 99.5 * S, 7 * S)]}
    return planes


def test_a_device_that_never_idles_is_busy_for_the_window_and_no_longer(monkeypatch, capsys):
    # starts before the window, tiles it whole, ends after it: 6.7 s of
    # operations in the file, as a saturated chip's trace has them
    ops = [("%fusion.1", 99.7 * S, 0.5 * S), ("%fusion.2", 100.2 * S, 3 * S), ("%fusion.3", 103.2 * S, 2.5 * S),
           ("%fusion.4", 105.7 * S, 0.7 * S)]
    planes = _saturated(ops)
    assert tracing.busy_seconds(planes) == pytest.approx(6.7)  # the old reading: more than the window
    run, breakdown = _reduce(monkeypatch, planes, _profiler(7_000_000_000, 6.0))
    assert run.trace_busy_s == run.trace_window_s == 6.0
    assert idle_share.read(run, {}) == 0.0
    assert "6.700000000 s in the whole file" in capsys.readouterr().out
    # the pieces inside, not the events' whole durations
    assert dict(breakdown["device_ops"]) == pytest.approx(
        {"%fusion.1": 0.2, "%fusion.2": 3.0, "%fusion.3": 2.5, "%fusion.4": 0.3})
    assert breakdown["idle_gaps"] == []
    run.traced_work = 3.0
    assert device_ms_per_work.read(run, {}) == pytest.approx(2000.0)
    assert module_ms_per_work.read(run, {"contains": ["hs_grouped_agg"]}) == pytest.approx(2000.0)  # 6 of 7 s
    # host planes stay whole: the annotation that began before the window is found
    assert run.planes["/host:CPU"] is planes["/host:CPU"]
    assert idle_under_annotation.read(run, {"annotation": "hs:build:take-write"}) is None  # no idle second


def test_an_event_outside_the_window_is_dropped_and_one_across_an_edge_is_cut():
    ops = [("%before", 90 * S, 5 * S), ("%across_lo", 99 * S, 2 * S), ("%inside", 102 * S, 1 * S),
           ("%across_hi", 105.5 * S, 1 * S), ("%after", 106 * S, 3 * S), ("%ends_at_lo", 99 * S, 1 * S)]
    planes = _saturated(ops)
    window = tracing.trace_window(planes, 6.0)
    assert window == (100 * S, 106 * S)
    clipped = tracing.clip(planes, window)
    assert clipped["/device:TPU:0"][tracing.OPS_LINE] == [
        ("%across_lo", 100 * S, 1 * S), ("%inside", 102 * S, 1 * S), ("%across_hi", 105.5 * S, 0.5 * S)]
    assert clipped["/device:TPU:0"][MODULES] == [("jit_hs_grouped_agg_chunk(1)", 100 * S, 6 * S)]
    assert tracing.busy_seconds(clipped) == pytest.approx(2.5)
    assert planes["/device:TPU:0"][tracing.OPS_LINE] == ops  # the file's planes are not changed
    gaps = tracing.idle_gaps(clipped, [], 0.0, window)
    assert sum(gaps.values()) == pytest.approx(6.0 - 2.5)


def test_two_device_planes_are_clipped_each_on_its_own(monkeypatch):
    chip0 = [("%sort", 98 * S, 10 * S)]                                # never idle
    chip1 = [("%sort", 99 * S, 2 * S), ("%fusion", 104 * S, 5 * S)]    # 1 s and 2 s inside
    run, _ = _reduce(monkeypatch, _saturated(chip0, chip1), _profiler(3_000_000_000, 6.0))
    assert plane_busy_spread.plane_busy_seconds(run.planes) == {"/device:TPU:0": 6.0, "/device:TPU:1": 3.0}
    assert run.trace_busy_s == pytest.approx(4.5) and idle_share.read(run, {}) == pytest.approx(25.0)
    assert plane_busy_spread.read(run, {}) == pytest.approx(50.0)
    # a plane whose operations all lie outside ran none in the window
    run, _ = _reduce(monkeypatch, _saturated(chip0, [("%sort", 90 * S, 5 * S)]), _profiler(3_000_000_000, 6.0))
    assert run.trace_busy_s == 6.0 and list(plane_busy_spread.plane_busy_seconds(run.planes)) == ["/device:TPU:0"]


def test_the_recorded_trace_reads_the_same_inside_a_window_that_holds_it(planes, shape, monkeypatch):
    lo, hi = _device_window(planes)
    anchor = tracing.anchor_ns(planes)
    assert anchor <= lo  # the recording's anchor precedes everything its device did
    seconds = (hi - anchor) / 1e9 + 1e-3
    run, breakdown = _reduce(monkeypatch, planes, _profiler(shape["anchor_perf_counter_ns"], seconds))
    assert run.planes["/device:TPU:0"] == planes["/device:TPU:0"]
    assert run.trace_busy_s == tracing.busy_seconds(planes) and run.trace_window_s == pytest.approx(seconds)
    assert breakdown["device_ops"] == tracing.top(tracing.op_seconds(planes))
    assert hist_roofline.read(run, {"kernel": "_hist_call"}) == hist_roofline.read(
        _Run(planes, shape["device"]), {"kernel": "_hist_call"})
    assert sum(v for _, v in breakdown["idle_gaps"]) == pytest.approx(seconds - run.trace_busy_s, rel=1e-6)


def test_on_the_chip_a_full_device_queue_reads_busy_for_the_window_and_no_longer(tmp_path, capsys):
    """Chip only: a second thread keeps the device queue full across
    ``Profiler.start()`` ... ``stop()``; the trace goes through ``_reduce_trace``."""
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        pytest.skip("needs a TPU: JAX_PLATFORMS=tpu python3 -m pytest hsbench/tests/test_tracing.py -k on_the_chip")

    @jax.jit
    def spin(x):
        return jax.lax.fori_loop(0, 64, lambda _, y: (y @ y) * 1e-3 + 1.0, x)

    x = jnp.ones((2048, 2048), jnp.float32)
    spin(x).block_until_ready()  # compiled before the window
    halt = threading.Event()

    def keep_the_queue_full():
        pending = []
        while not halt.is_set():
            pending.append(spin(x))
            if len(pending) >= 8:
                pending.pop(0).block_until_ready()
        for y in pending:
            y.block_until_ready()

    feeder = threading.Thread(target=keep_the_queue_full)
    feeder.start()
    try:
        time.sleep(0.5)
        profiler = tracing.Profiler(str(tmp_path / "profile"))
        profiler.start()
        time.sleep(2.0)
        xplane = profiler.stop()
        time.sleep(0.2)
    finally:
        halt.set()
        feeder.join()
    run = hsrun.TracedRun(jax.devices()[0].device_kind)
    hsrun._reduce_trace(run, profiler, xplane, [])
    line = [l for l in capsys.readouterr().out.splitlines() if l.startswith("trace window:")]
    print(line[0])
    assert 0.98 * run.trace_window_s <= run.trace_busy_s <= run.trace_window_s
    assert 0.0 <= idle_share.read(run, {}) <= 2.0
    assert tracing.busy_seconds(tracing.read_planes(xplane)) >= run.trace_busy_s
