"""From a profiler trace to numbers: device busy time, time per device
operation, idle gaps named by what the host was doing.

``jax.profiler`` writes an ``.xplane.pb``; ``ProfileData`` reads it with
nothing but JAX. Device operations are the events of the ``XLA Ops`` line of a
``/device:TPU:n`` plane. Host spans measured with ``time.perf_counter()`` are
put on the profiler's clock through one ``TraceAnnotation`` (``hsbench:anchor``)
whose perf_counter reading is kept beside the trace.

The traced window is one interval on the trace's clock: from the anchor, which
``Profiler.start`` writes as soon as ``start_trace`` has returned, for
``Profiler.window_s`` seconds, to the reading ``Profiler.stop`` takes before it
calls ``stop_trace``. The profiler records for longer than that on both sides,
so ``clip`` cuts what the device planes hold to the window before anything is
summed: busy time can then not pass the window. Host planes stay whole.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import time

ANCHOR = "hsbench:anchor"
OPS_LINE = "XLA Ops"
_OP_KIND = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")


def null_annotation(name: str):
    return contextlib.nullcontext()


class Profiler:
    """Start and stop one traced window; keeps the anchor's perf_counter time."""

    def __init__(self, directory: str):
        self.directory = directory
        self.anchor_perf_ns = None
        self.started = self.stopped = None

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the Python tracer slows the host it measures
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self.anchor_perf_ns = time.perf_counter_ns()
        self.started = self.anchor_perf_ns / 1e9  # the window opens at the anchor
        with jax.profiler.TraceAnnotation(ANCHOR):
            pass

    def stop(self) -> str:
        import jax

        self.stopped = time.perf_counter()
        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"), recursive=True)
        if not files:
            raise RuntimeError(f"the profiler wrote no .xplane.pb under {self.directory}")
        return max(files, key=os.path.getmtime)

    @property
    def window_s(self) -> float:
        return self.stopped - self.started


def annotation(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def read_planes(path: str) -> dict:
    """``{plane name: {line name: [(event name, start ns, duration ns)]}}``."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for e in line.events:
                events.append((e.name, float(e.start_ns), float(e.duration_ns)))
    return out


def device_planes(planes: dict) -> list:
    return sorted(p for p in planes if p.startswith("/device:TPU:"))


def trace_window(planes: dict, seconds: float) -> tuple:
    """The traced window ``(lo, hi)`` in trace ns: ``seconds`` from the anchor."""
    lo = anchor_ns(planes)
    return lo, lo + seconds * 1e9


def clip(planes: dict, window) -> dict:
    """``planes`` with every event of every line of the device planes cut to
    ``window`` (trace ns): an event outside it is dropped, one across an edge
    keeps the part inside. Host planes are the same objects, whole."""
    lo, hi = window
    out = dict(planes)
    for p in device_planes(planes):
        out[p] = {line: [(name, max(s, lo), min(s + d, hi) - max(s, lo))
                         for name, s, d in events if s + d > lo and s < hi]
                  for line, events in planes[p].items()}
    return out


def union(intervals) -> list:
    """Merged, sorted ``[(start, end)]``."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_seconds(planes: dict) -> float:
    """Seconds in which an operation ran, averaged over the device planes that
    ran any."""
    per_device = []
    for p in device_planes(planes):
        ops = planes[p].get(OPS_LINE, [])
        if ops:
            per_device.append(sum(e - s for s, e in union((s, s + d) for _, s, d in ops)) / 1e9)
    return sum(per_device) / len(per_device) if per_device else 0.0


def op_label(name: str) -> str:
    """``%fusion.26 = f32[...] fusion(...)`` -> ``%fusion.26 fusion``."""
    head, _, rest = name.partition(" = ")
    if not rest:
        return name[:60]
    kind = _OP_KIND.search(rest)  # the first word that opens a call, past the result's shape
    return f"{head} {kind.group(1) if kind else ''}".strip()[:60]


def op_seconds(planes: dict, contains: str = "") -> dict:
    """Summed device seconds per operation label, over all device planes."""
    out = {}
    for p in device_planes(planes):
        for name, _, d in planes[p].get(OPS_LINE, []):
            if contains in name:
                out[op_label(name)] = out.get(op_label(name), 0.0) + d / 1e9
    return out


def events_named(planes: dict, contains: str) -> list:
    """``(name, duration s)`` of the device operations whose name holds ``contains``."""
    return [(name, d / 1e9) for p in device_planes(planes)
            for name, _, d in planes[p].get(OPS_LINE, []) if contains in name]


def top(table: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def anchor_ns(planes: dict) -> float:
    """Where the anchor annotation starts on the trace's clock."""
    for plane in planes.values():
        for events in plane.values():
            for name, start, _ in events:
                if name == ANCHOR:
                    return start
    raise RuntimeError(f"no {ANCHOR!r} annotation in the trace: host spans cannot be placed")


def anchor_offset_ns(planes: dict, anchor_perf_ns: int) -> float:
    """What to add to a perf_counter reading in ns to get the trace's clock."""
    return anchor_ns(planes) - anchor_perf_ns


def idle_gaps(planes: dict, host_spans, offset_ns: float, window) -> dict:
    """Idle seconds of the (first) device inside ``window`` (trace ns), by
    what the host was doing: ``host_spans`` are ``(label, t0 s, t1 s)`` in
    perf_counter seconds; where several overlap a gap their labels are joined
    with ``+``, and where none does the gap is ``(no host span)``."""
    dev = device_planes(planes)
    ops = planes[dev[0]].get(OPS_LINE, []) if dev else []
    lo, hi = window
    busy = union((max(s, lo), min(s + d, hi)) for _, s, d in ops if s + d > lo and s < hi)
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    spans = [(label, t0 * 1e9 + offset_ns, t1 * 1e9 + offset_ns) for label, t0, t1 in host_spans]
    out = {}
    for g0, g1 in gaps:
        inside = [(l, max(a, g0), min(b, g1)) for l, a, b in spans if b > g0 and a < g1]
        cuts = sorted({g0, g1, *(a for _, a, _ in inside), *(b for _, _, b in inside)})
        for a, b in zip(cuts, cuts[1:]):
            labels = sorted({l for l, s, e in inside if s <= a and e >= b})
            key = "+".join(labels) if labels else "(no host span)"
            out[key] = out.get(key, 0.0) + (b - a) / 1e9
    return out
