"""Per-layer metrics. ``<metric name>.json`` names a reader module of this
directory and its parameters; the reader's ``read(run, params)`` takes the
number from the traced run's spans, counters or profiler trace, and returns
``None`` where it finds nothing to read (the harness then leaves the metric
out). A new metric adds its ``.json`` and, where no reader here fits, a
reader module of its own: no file that exists is edited.
"""

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def read_metric(name: str, run):
    with open(os.path.join(HERE, f"{name}.json")) as f:
        spec = json.load(f)
    reader = importlib.import_module(f"hsbench.layers.{spec['reader']}")
    return reader.read(run, spec.get("params", {}))
