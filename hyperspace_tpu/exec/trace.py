"""Execution-dispatch trace: which physical path each operator actually took.

The reference approves a *simplified executedPlan* tree per TPC-DS query
(ref: goldstandard/PlanStabilitySuite.scala:83-290), so falling off a fast
path (bucketed SMJ -> generic merge, codegen -> interpreted) is a test
failure. This framework's physical dispatch is decided at runtime (device vs
host by row-count gates, native vs pyarrow decode per file, DeviceUnsupported
fallbacks), so the equivalent pin is a recorded trace: decision points call
:func:`record`, and the golden tests approve the counted summary alongside
the optimized plan.

Recording is off by default (one ``is None`` check per event) and
process-global, NOT thread-local: the parquet decode pool's worker threads
must land their events in the caller's recording. One recording at a time;
list.append is atomic under the GIL. Enable with::

    with trace.recording() as events:
        q.collect()
    print(trace.summarize(events))
"""

from __future__ import annotations

import contextlib
import threading
from collections import Counter
from typing import Iterator, List, Optional

from hyperspace_tpu.obs import spans as _spans

_events: Optional[List] = None

# QueryServers currently running in this process. A process-global recording
# under concurrent serving would interleave events from unrelated requests —
# recording() refuses to start instead (the obs span tracer is the
# per-request surface; see docs/observability.md).
_servers_running = 0
_servers_lock = threading.Lock()


def server_started() -> None:
    global _servers_running
    with _servers_lock:
        _servers_running += 1


def server_stopped() -> None:
    global _servers_running
    with _servers_lock:
        _servers_running = max(0, _servers_running - 1)


def record(kind: str, detail: str) -> None:
    """Append a dispatch event (e.g. ``record("join", "device-smj")``) to the
    active recorder, if any — and annotate the context's current obs span, so
    dispatch decisions land inside the per-request span tree too."""
    events = _events
    if events is not None:
        events.append((kind, detail))
    sp = _spans.current_span()
    if sp is not None:
        sp.event(kind, detail)


def fallback(op: str, reason: str) -> None:
    """Count a device-path fallback in the process metrics registry.

    Dispatch traces already *name* every fallback, but a recording must be
    active to see them; the ``hs_device_fallback_total{op,reason}`` counter
    makes the same decisions visible in Prometheus scrapes and query
    profiles without one.
    """
    from hyperspace_tpu.obs.metrics import REGISTRY

    REGISTRY.counter(
        "hs_device_fallback_total",
        "Device-path fallbacks to host execution, by operator and reason",
        op=op,
        reason=reason,
    ).inc()


_AGG_ROWS: dict = {}


def agg_rows(path: str, n: int) -> None:
    """Count ``n`` rows entering an aggregate's fold on ``path`` (``device``:
    the rows a device program reads, its filter fused in; ``host``: the rows
    handed to pandas) in ``hs_agg_rows_total{path}``."""
    c = _AGG_ROWS.get(path)
    if c is None:
        from hyperspace_tpu.obs.metrics import REGISTRY

        c = _AGG_ROWS[path] = REGISTRY.counter(
            "hs_agg_rows_total",
            "Rows entering an aggregate's fold, by where it was folded",
            path=path,
        )
    c.inc(n)


def active() -> bool:
    return _events is not None


@contextlib.contextmanager
def recording() -> Iterator[List]:
    """Collect dispatch events for the duration of the block.

    Raises ``RuntimeError`` while a ``QueryServer`` is running: this recorder
    is process-global, so it would interleave events from every concurrent
    request. Use span traces (``hyperspace.obs.tracing.enabled`` + per-request
    profiles) under serving instead.
    """
    global _events
    with _servers_lock:
        if _servers_running:
            raise RuntimeError(
                "exec.trace.recording() is process-global and cannot run while "
                f"{_servers_running} QueryServer(s) are serving concurrent "
                "requests; use obs span tracing (hyperspace.obs.tracing.enabled) "
                "for per-request dispatch visibility"
            )
    prev = _events
    _events = []
    try:
        yield _events
    finally:
        _events = prev


def summarize(events: List) -> str:
    """Stable text form for goldens: one ``kind: detail xN`` line per distinct
    event, sorted."""
    counts = Counter(events)
    lines = [f"{kind}: {detail} x{n}" for (kind, detail), n in sorted(counts.items())]
    return "\n".join(lines) if lines else "(no dispatch events)"


def summarize_span_events(root) -> str:
    """Dispatch summary of one finished span tree: the same counted form
    :func:`summarize` produces for a recording, but sourced from the
    per-request events :func:`record` annotated onto obs spans. This is how
    the slow-query flight recorder shows "which physical paths this request
    took" without a process-global recording."""
    events: List = []
    for sp in root.walk():
        events.extend(sp.events)
    return summarize(events)
