"""Test harness.

Multi-device behavior is exercised on a virtual 8-device CPU mesh, standing in
for the reference's ``local[4]`` in-process Spark
(ref: src/test/scala/com/microsoft/hyperspace/SparkInvolvedSuite.scala:26-56;
SURVEY.md §4 "Implication for the TPU build").

Env vars must be set before jax is imported anywhere.
"""

import os

# Force the 8-device virtual CPU mesh: tests never use a chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest


@pytest.fixture()
def tmp_system_path(tmp_path):
    """Per-test index system path (ref: HyperspaceSuite's per-suite systemPath)."""
    p = tmp_path / "indexes"
    p.mkdir()
    return str(p)


@pytest.fixture()
def sample_parquet(tmp_path):
    """Small sample dataset (ref: test SampleData.scala)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(42)
    n = 1000
    table = pa.table(
        {
            "c1": rng.integers(0, 100, n).astype(np.int64),
            "c2": rng.integers(0, 1000, n).astype(np.int64),
            "c3": rng.standard_normal(n),
            "c4": np.array([f"name_{i % 37}" for i in range(n)]),
        }
    )
    root = tmp_path / "sample_data"
    root.mkdir()
    # several files so file-level diffs are meaningful
    for i in range(4):
        pq.write_table(table.slice(i * 250, 250), root / f"part-{i:05d}.parquet")
    return str(root)


@pytest.fixture()
def session(tmp_system_path):
    import hyperspace_tpu as hst

    sess = hst.Session(conf={hst.keys.SYSTEM_PATH: tmp_system_path})
    hst.set_session(sess)
    yield sess
    hst.set_session(None)


# --- shared E2E helpers (the reference's verifyIndexUsage/checkAnswer) ------


def index_scans(q):
    """IndexScan nodes of the optimized plan (verifyIndexUsage side)."""
    from hyperspace_tpu.plan import logical as L

    return [p for p in L.collect(q.optimized_plan(), lambda x: True) if isinstance(p, L.IndexScan)]


def sorted_rows(batch):
    """Row-set normal form: sorted tuples with NaN made comparable."""

    def norm(v):
        # one totally-ordered domain: NaN == NaN, NULLs sortable, every
        # value stringified (a rollup NULL-filled column mixes types)
        if v is None:
            return "\x00NULL"
        if isinstance(v, float) and v != v:
            return "NaN"
        return str(v)

    cols = sorted(batch.keys())
    if not cols:
        return []
    return sorted(tuple(norm(v) for v in r) for r in zip(*[batch[k].tolist() for k in cols]))


def check_answer(session, q):
    """Full row-set equality with hyperspace on vs off (checkAnswer)."""
    session.enable_hyperspace()
    on = q.collect()
    session.disable_hyperspace()
    try:
        off = q.collect()
    finally:
        session.enable_hyperspace()
    assert sorted(on.keys()) == sorted(off.keys())
    assert sorted_rows(on) == sorted_rows(off)
    return on
