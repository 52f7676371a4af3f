"""The arithmetic of the end-to-end metrics: percentiles, latency from the due
time, spreads. Kept apart from the loops so that it can be tested alone."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics; ``nan`` for no values."""
    v = sorted(values)
    if not v:
        return math.nan
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def latencies_ms(due_s, done_s) -> list:
    """Latency of each finished request from the moment it was DUE, not from
    when the generator got round to sending it: a stall in the generator or a
    queue behind a slow request is time the user waited. ``done_s`` holds
    ``None`` for a request that failed; it has no latency."""
    return [(d1 - d0) * 1e3 for d0, d1 in zip(due_s, done_s) if d1 is not None]


def lateness_ms(due_s, sent_s) -> list:
    """How late the generator sent each request."""
    return [max(0.0, (s - d) * 1e3) for d, s in zip(due_s, sent_s)]


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the contract measures it."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
