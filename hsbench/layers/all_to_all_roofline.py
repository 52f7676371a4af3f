"""The build exchange's ``all-to-all`` against the interconnect: the least
bytes that must leave one chip for the rows of the traced builds
(``hsbench/costs_exchange.py``: the key as Arrow stores it and the row index,
for the rows another chip owns), over the chip's published ICI bandwidth
(``hsbench/peaks_ici.py``, by the run's own device kind), divided by the time
the ``all-to-all`` operations took on one chip (mean over the chips). It
counts least bytes, not the padded slots the program ships, so it can read
far under 100 and never over. Nothing from a trace with fewer than two device
planes or without the operation. Percent."""

from hsbench import costs_exchange, peaks_ici
from hsbench.layers import op_share


def read(run, params):
    if run.planes is None or not run.traced_work:
        return None
    per_plane = op_share.op_seconds_by_plane(run.planes, params["op"])
    seconds = [m for m, _ in per_plane.values()]
    if len(per_plane) < 2 or not sum(seconds):
        return None
    least = costs_exchange.least_bytes_leaving_one_chip(
        run.traced_work * 1e6, len(per_plane), int(params["key_bytes"]), int(params.get("row_index_bytes", 4)))
    least_s = least / peaks_ici.ici_peaks(run.device_kind)["ici_bytes_per_s"]
    return 100.0 * least_s / (sum(seconds) / len(seconds))
