"""How evenly the chips worked in the profiler's window: busy seconds (union
of the ``XLA Ops`` intervals) of the least busy device plane over those of the
busiest. 100 is flat. Nothing from a trace with fewer than two device planes
that ran an operation. Percent."""

from hsbench import tracing


def plane_busy_seconds(planes: dict) -> dict:
    out = {}
    for p in tracing.device_planes(planes):
        ops = planes[p].get(tracing.OPS_LINE, [])
        if ops:
            out[p] = sum(e - s for s, e in tracing.union((s, s + d) for _, s, d in ops)) / 1e9
    return out


def read(run, params):
    if run.planes is None:
        return None
    busy = plane_busy_seconds(run.planes)
    if len(busy) < 2 or not max(busy.values()):
        return None
    return 100.0 * min(busy.values()) / max(busy.values())
