"""hyperspace_tpu — a TPU-native data-lake indexing framework.

A brand-new framework with the capabilities of Microsoft Hyperspace (reference:
``/root/reference``, Scala/Spark): users create *indexes* (derived datasets) over
Parquet/Delta data-lake files, index data plus a versioned operation log live on
storage next to the data, and a query optimizer transparently rewrites filter and
equi-join plans to scan pre-bucketed, pre-sorted index data instead of source files.

Unlike the reference, the execution substrate is JAX/XLA on TPU: hash-bucketing
lowers to on-device hashing + all-to-all over ICI, sorting to ``jax.lax.sort``,
bucketed joins run shuffle-free per device shard, and bucket-union is a
sharding-preserving concatenation.

Layer map (mirrors SURVEY.md §1):
  - ``models/``    metadata model + operation-log persistence   (ref: HS/index/IndexLogEntry.scala)
  - ``sources/``   pluggable source providers                   (ref: HS/index/sources/)
  - ``plan/``      relational IR, expressions, DataFrame API    (ref: Spark Catalyst, subset)
  - ``indexes/``   index implementations (covering, skipping)   (ref: HS/index/covering, dataskipping)
  - ``actions/``   lifecycle actions FSM                        (ref: HS/actions/)
  - ``rules/``     optimizer integration, plan rewriting        (ref: HS/index/rules/)
  - ``ops/``       TPU compute kernels (hash, sort, join, scan)
  - ``parallel/``  device mesh / sharding layer                 (replaces Spark shuffle)
  - ``exec/``      physical execution of (rewritten) plans
  - ``analysis/``  explain / whyNot introspection               (ref: HS/index/plananalysis/)
  - ``telemetry/`` structured event taxonomy                    (ref: HS/telemetry/)
"""

import os as _os

# Persistent XLA compilation cache: index builds re-run the same fused sort
# program per size class across processes; without this every fresh process
# pays a tens-of-seconds TPU compile. JAX_COMPILATION_CACHE_DIR, where set,
# places the cache (jax reads it itself; nothing is configured here).
# Otherwise it lives at one fixed path inside the checkout — the path is part
# of the cache key, so a directory that moves never hits.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    import jax as _jax

    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))), ".jax_cache"),
    )
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

from hyperspace_tpu.version import __version__
from hyperspace_tpu.config import HyperspaceConf, keys
from hyperspace_tpu.session import Session, get_session, set_session
from hyperspace_tpu.plan.expr import col, lit, input_file_name
from hyperspace_tpu.plan.dataframe import DataFrame
from hyperspace_tpu.indexes.covering import CoveringIndexConfig
from hyperspace_tpu.indexes.dataskipping import (
    DataSkippingIndexConfig,
    MinMaxSketch,
    BloomFilterSketch,
    ValueListSketch,
)
from hyperspace_tpu.hyperspace import Hyperspace
from hyperspace_tpu.serving import AdmissionRejected, QueryServer, RequestTimeout

__all__ = [
    "__version__",
    "HyperspaceConf",
    "keys",
    "Session",
    "get_session",
    "set_session",
    "col",
    "lit",
    "input_file_name",
    "DataFrame",
    "CoveringIndexConfig",
    "DataSkippingIndexConfig",
    "MinMaxSketch",
    "BloomFilterSketch",
    "ValueListSketch",
    "Hyperspace",
    "QueryServer",
    "AdmissionRejected",
    "RequestTimeout",
]
