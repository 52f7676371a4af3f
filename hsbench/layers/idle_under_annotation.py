"""Idle seconds of the device inside the program's own annotations of the
given name (``hs:<cat>:<name>`` events of the trace's host planes), over the
idle seconds of the whole traced window (``tracing.trace_window``: from the
harness's anchor annotation for ``trace_window_s``), both on the profiler's
clock. Percent."""

from hsbench import tracing


def read(run, params):
    if run.planes is None or not run.trace_window_s:
        return None
    devices = set(tracing.device_planes(run.planes))
    marks = [(name, start / 1e9, (start + dur) / 1e9) for plane, lines in run.planes.items()
             if plane not in devices for events in lines.values()
             for name, start, dur in events if name == params["annotation"]]
    if not marks:
        return None
    window = tracing.trace_window(run.planes, run.trace_window_s)
    gaps = tracing.idle_gaps(run.planes, marks, 0.0, window)
    idle = sum(gaps.values())
    if not idle:
        return None
    return 100.0 * gaps.get(params["annotation"], 0.0) / idle
