"""A scan-and-aggregate program's share of its HBM roofline: the least bytes
of every call (``hsbench/costs_agg.py``: rows times the item sizes of the
resident columns the call takes, from its own operand shapes) over the chip's
published bandwidth (``hsbench/peaks.py``, by the run's own device kind),
divided by the calls' device time. A call is one event of the device plane's
module line whose name holds one of ``contains``; its operations are the
``XLA Ops`` events inside it. Only calls that lie whole inside the traced
window count: one that an edge cuts is left out with its bytes and its time
(``hsbench/README.md``, "The traced window"). Bounded by bytes: the program
compares, multiplies and adds once per value it reads. Nothing where the
trace has no whole call of such a program. Percent."""

from hsbench import costs_agg, peaks, tracing


def whole_calls(run, params) -> list:
    """``[(start ns, end ns)]`` of the named executables' runs that no edge of
    the traced window cut, over all device planes, with each one's plane."""
    lo = tracing.anchor_ns(run.planes)
    hi = lo + run.trace_window_s * 1e9
    out = []
    for p in tracing.device_planes(run.planes):
        for name, start, dur in run.planes[p].get(params.get("line", "XLA Modules"), []):
            # ``tracing.clip`` moved a cut call's start, or its end, onto the edge
            if any(piece in name for piece in params["contains"]) and start > lo and start + dur < hi:
                out.append((p, start, start + dur))
    return out


def read(run, params):
    if run.planes is None or not run.trace_window_s:
        return None
    try:
        calls = whole_calls(run, params)
    except RuntimeError:  # no anchor: a trace this harness did not take
        return None
    least, seconds = 0, 0.0
    for plane, start, end in calls:
        ops = [name for name, s, d in run.planes[plane].get(tracing.OPS_LINE, []) if s >= start and s + d <= end]
        nbytes = costs_agg.call_least_bytes(ops)
        if nbytes:
            least += nbytes
            seconds += (end - start) / 1e9
    if not seconds:
        return None
    return 100.0 * (least / peaks.peaks(run.device_kind)["hbm_bytes_per_s"]) / seconds
