"""Idle seconds of the device inside the program's own annotations of the
given name (``hs:<cat>:<name>`` events of the trace's host planes), over the
idle seconds of the whole traced slice: both on the profiler's clock. The
slice starts at the harness's anchor annotation and lasts ``trace_window_s``.
Percent."""

from hsbench import tracing


def read(run, params):
    if run.planes is None or not run.trace_window_s:
        return None
    devices = set(tracing.device_planes(run.planes))
    host = [(name, start, dur) for plane, lines in run.planes.items() if plane not in devices
            for events in lines.values() for name, start, dur in events]
    anchors = [start for name, start, _ in host if name == tracing.ANCHOR]
    marks = [(params["annotation"], start / 1e9, (start + dur) / 1e9)
             for name, start, dur in host if name == params["annotation"]]
    if not anchors or not marks:
        return None
    window = (anchors[0], anchors[0] + run.trace_window_s * 1e9)
    gaps = tracing.idle_gaps(run.planes, marks, 0.0, window)
    idle = sum(gaps.values())
    if not idle:
        return None
    return 100.0 * gaps.get(params["annotation"], 0.0) / idle
