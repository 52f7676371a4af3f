"""1 - (union of the device's operation intervals / profiler window). Percent."""


def read(run, params):
    if run.planes is None or not run.trace_window_s:
        return None
    return 100.0 * (1.0 - run.trace_busy_s / run.trace_window_s)
