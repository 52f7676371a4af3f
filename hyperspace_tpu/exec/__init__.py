"""Execution engine: host/device executors, streaming folds.

Everything here loads lazily (PEP 562): lazy attributes keep
``import hyperspace_tpu`` cheap for callers that never execute a query
(jax loads on first use, not at import).

Public surface (mirrors ``parallel/__init__``):

- ``Executor`` — the logical-plan executor (materialized + streaming).
- ``GroupedAggStream``, ``TopKStream``, ``DeviceUnsupported`` — the
  streamed device folds and their fallback signal (``exec.device`` /
  ``exec.topk``).
- ``stream_broadcast_join``, ``BroadcastSpec``, ``broadcast_spec`` — the
  streaming broadcast hash join (``exec.join_stream``).
"""

from __future__ import annotations

__all__ = [
    "BroadcastSpec",
    "DeviceUnsupported",
    "Executor",
    "GroupedAggStream",
    "TopKStream",
    "broadcast_spec",
    "stream_broadcast_join",
]

_HOMES = {
    "Executor": "executor",
    "GroupedAggStream": "device",
    "DeviceUnsupported": "device",
    "TopKStream": "topk",
    "BroadcastSpec": "join_stream",
    "broadcast_spec": "join_stream",
    "stream_broadcast_join": "join_stream",
}


def __getattr__(name):
    home = _HOMES.get(name)
    if home is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(f"{__name__}.{home}"), name)


def __dir__():
    return sorted(set(globals()) | set(__all__))
