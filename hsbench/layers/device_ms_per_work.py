"""Summed durations of the device's operations in the profiler's window, per
unit of work completed in that window. Milliseconds."""

from hsbench import tracing


def read(run, params):
    if run.planes is None or not run.traced_work:
        return None
    seconds = sum(tracing.op_seconds(run.planes).values())
    return 1e3 * seconds / run.traced_work
