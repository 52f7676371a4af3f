"""Summed durations of the device's executables (the events of the device
planes' module line) whose name holds one of the given pieces, per unit of
work completed in the profiler's window. Milliseconds."""

from hsbench import tracing


def read(run, params):
    if run.planes is None or not run.traced_work:
        return None
    modules = [(name, dur) for p in tracing.device_planes(run.planes)
               for name, _, dur in run.planes[p].get(params.get("line", "XLA Modules"), [])]
    if not modules:
        return None
    ns = sum(dur for name, dur in modules if any(piece in name for piece in params["contains"]))
    return ns / 1e6 / run.traced_work
