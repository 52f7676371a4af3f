"""The bucket-histogram Pallas kernel's share of its HBM roofline: the least
bytes of every call (``hsbench/costs.py``, from the call's own shapes) over
the chip's published bandwidth (``hsbench/peaks.py``, by the run's own device
kind), divided by the call's device time. Bounded by bytes, not arithmetic.
Percent."""

from hsbench import costs, peaks, tracing


def read(run, params):
    if run.planes is None:
        return None
    calls = tracing.events_named(run.planes, params["kernel"])
    # the call itself, not a fusion that names it among its operands
    calls = [(n, s) for n, s in calls if n.split(" = ")[0].lstrip("%").startswith(params["kernel"])]
    if not calls:
        return None
    bandwidth = peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    least_s = sum(costs.hist_least_bytes(n) for n, _ in calls) / bandwidth
    return 100.0 * least_s / sum(s for _, s in calls)
