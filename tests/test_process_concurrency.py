"""Cross-process optimistic concurrency on the operation log.

The reference tests concurrent writers at thread level
(IndexLogManagerImplTest races — SURVEY.md §5.2); separate OS processes
exercise the temp-file + atomic-rename protocol with no shared in-process
state at all: exactly one creator wins, losers fail with
ConcurrentModificationException, and the surviving index is consistent.
"""

import os
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

import hyperspace_tpu as hst

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WORKER = r'''
import os, sys
sys.path.insert(0, sys.argv[3])
# a chip belongs to one process: concurrent workers stay on the CPU backend
os.environ["JAX_PLATFORMS"] = "cpu"
import hyperspace_tpu as hst
root, d = sys.argv[1], sys.argv[2]
sess = hst.Session(conf={hst.keys.SYSTEM_PATH: os.path.join(root, "i"), hst.keys.NUM_BUCKETS: 4})
hst.set_session(sess)
hs = hst.Hyperspace(sess)
df = sess.read_parquet(d)
try:
    hs.create_index(df, hst.CoveringIndexConfig("raceIdx", ["k"], ["v"]))
    print("WIN")
except Exception as e:
    print("LOSE", type(e).__name__)
'''


def test_concurrent_creators_single_winner(tmp_path, session):
    d = tmp_path / "data"
    d.mkdir()
    pq.write_table(
        pa.table({"k": np.arange(20_000, dtype=np.int64), "v": np.arange(20_000.0)}),
        d / "p.parquet",
    )
    worker = tmp_path / "worker.py"
    worker.write_text(WORKER)
    sysdir = str(tmp_path)
    procs = [
        subprocess.Popen(
            [sys.executable, str(worker), sysdir, str(d), REPO],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
        )
        for _ in range(4)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=180)
        assert p.returncode == 0, f"worker crashed: stdout={out!r} stderr={err[-2000:]!r}"
        outs.append(out.strip())
    wins = [o for o in outs if o == "WIN"]
    losses = [o for o in outs if o.startswith("LOSE")]
    assert len(wins) == 1, outs
    assert len(losses) == 3, outs
    # a worker losing the log-id race raises ConcurrentModificationException;
    # one starting after the winner committed fails validate() with a plain
    # "already exists" HyperspaceActionException — both are correct outcomes
    assert all(
        "ConcurrentModificationException" in o or "HyperspaceActionException" in o
        for o in losses
    ), outs

    # the surviving index is consistent and usable from a fresh session —
    # in particular no duplicated rows from two builders sharing a data dir
    sess = hst.Session(conf={hst.keys.SYSTEM_PATH: os.path.join(sysdir, "i"), hst.keys.NUM_BUCKETS: 4})
    hs = hst.Hyperspace(sess)
    df = sess.read_parquet(str(d))
    sess.enable_hyperspace()
    q = df.filter(hst.col("k") == 7).select("v")
    assert "IndexScan" in q.optimized_plan().pretty()
    assert len(q.collect()["v"]) == 1


def _write_sample(d, n=5000):
    pq.write_table(
        pa.table({"k": np.arange(n, dtype=np.int64), "v": np.arange(float(n))}),
        os.path.join(str(d), "p.parquet"),
    )


def test_crashed_create_is_recoverable(tmp_path, session):
    """An abandoned CREATING transient (creator died before any stable entry)
    must not brick the index name: a retrying creator wins the next log id
    and builds into its own exclusively-allocated version dir."""
    import hyperspace_tpu.indexes.covering as cov

    d = tmp_path / "data"
    d.mkdir()
    _write_sample(d)
    session.conf.set(hst.keys.NUM_BUCKETS, 2)
    hs = hst.Hyperspace(session)
    df = session.read_parquet(str(d))

    calls = {"n": 0}
    real_write = cov.CoveringIndex.write

    def crashing_write(self, ctx, df_):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("simulated creator crash mid-build")
        return real_write(self, ctx, df_)

    cov.CoveringIndex.write = crashing_write
    try:
        import pytest as _pytest

        with _pytest.raises(RuntimeError):
            hs.create_index(df, hst.CoveringIndexConfig("crashIdx", ["k"], ["v"]))
        # retry succeeds despite the abandoned CREATING transient
        hs.create_index(df, hst.CoveringIndexConfig("crashIdx", ["k"], ["v"]))
    finally:
        cov.CoveringIndex.write = real_write
    session.enable_hyperspace()
    q = df.filter(hst.col("k") == 7).select("v")
    assert "IndexScan" in q.optimized_plan().pretty()
    assert len(q.collect()["v"]) == 1


def test_failed_action_cleans_allocated_version_dir(tmp_path, session):
    """A failed build deletes the version dir it claimed — repeated failures
    must not accumulate orphan v__=N dirs."""
    import hyperspace_tpu.indexes.covering as cov

    d = tmp_path / "data2"
    d.mkdir()
    _write_sample(d)
    session.conf.set(hst.keys.NUM_BUCKETS, 2)
    hs = hst.Hyperspace(session)
    df = session.read_parquet(str(d))

    real_write = cov.CoveringIndex.write

    def failing_write(self, ctx, df_):
        raise RuntimeError("boom")

    cov.CoveringIndex.write = failing_write
    try:
        import pytest as _pytest

        for _ in range(3):
            with _pytest.raises(RuntimeError):
                hs.create_index(df, hst.CoveringIndexConfig("leakIdx", ["k"], ["v"]))
    finally:
        cov.CoveringIndex.write = real_write
    sysp = session.conf.get(hst.keys.SYSTEM_PATH)
    idx_dir = os.path.join(sysp, "leakIdx")
    version_dirs = [n for n in os.listdir(idx_dir) if n.startswith("v__=")] if os.path.isdir(idx_dir) else []
    assert version_dirs == [], version_dirs
    # and the name still works afterwards
    hs.create_index(df, hst.CoveringIndexConfig("leakIdx", ["k"], ["v"]))
