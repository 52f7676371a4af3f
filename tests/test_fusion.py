"""Whole-plan fusion compiler: stage IR, donated fold state, fused join-agg.

Pinned properties:
- fused streamed results are identical to the per-family path with fusion
  off, on q1 (filter→group→agg), q3 (filter→join→group→agg), and top-k
  chains — byte-identical for keys/counts/int aggregates, fp-tolerance for
  float sums (the repo-wide device-vs-host discipline), and byte-identical
  between donation on and off;
- fusion is default-off: a session that never touches the conf dispatches
  zero fused programs;
- one fused executable per (skeleton, shape bucket, mesh fingerprint):
  hs_xla_compiles_total is flat across a chunk-size sweep within warm
  buckets;
- donated fold state really donates: the pre-call state buffer is deleted
  after the fused call (the donated-buffer-reuse regression);
- shapes the fused programs can't run fall back per-family, counted in
  hs_device_fallback_total{op="fusion"}, with unchanged results;
- every fused program satisfies its registered HLO contract (single
  fusion region, zero host callbacks, declared collectives only) when
  verified at program-cache fill under hyperspace.check.hlo.enabled;
- the fused q3 chain folds each chunk in ONE dispatch — a ≥3x
  hs_device_dispatches_total reduction against the per-family
  probe/postjoin/agg-chunk/merge sequence over the same chunks.
"""

import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.exec import trace
from hyperspace_tpu.obs.metrics import REGISTRY

pytestmark = pytest.mark.fusion

FLOAT_RTOL = 1e-9

FUSED_PROGRAMS = (
    "fused-stage-agg",
    "fused-stage-agg-sharded",
    "fused-stage-topk",
    "fused-stage-topk-sharded",
    "fused-stage-join-agg",
)


def _counter(name, **labels) -> float:
    return REGISTRY.counter(name, "", **labels).value


def _fused_dispatches() -> float:
    return sum(_counter("hs_device_dispatches_total", program=p) for p in FUSED_PROGRAMS)


def _fallbacks() -> float:
    snap = REGISTRY.snapshot().get("hs_device_fallback_total")
    if not snap:
        return 0.0
    return sum(s["value"] for s in snap["series"] if s["labels"].get("op") == "fusion")


def _compiles() -> float:
    snap = REGISTRY.snapshot().get("hs_xla_compiles_total")
    if not snap:
        return 0.0
    return sum(s["value"] for s in snap["series"])


def _mk_session(tmp_path, tag="s", fusion=None, donation=True, **conf):
    base = {
        hst.keys.SYSTEM_PATH: str(tmp_path / f"idx_{tag}"),
        hst.keys.TPU_QUERY_DEVICE_EXECUTION: True,
        hst.keys.TPU_QUERY_DEVICE_MIN_ROWS: 0,
        hst.keys.EXEC_STREAM_AGG_MIN_BYTES: 1,
        hst.keys.EXEC_STREAM_CHUNK_BYTES: 1,  # one file per chunk
    }
    base.update(conf)
    if fusion is not None:
        base[hst.keys.EXEC_FUSION_ENABLED] = fusion
        base[hst.keys.EXEC_FUSION_DONATION] = donation
    sess = hst.Session(conf=base)
    hst.set_session(sess)
    return sess


def _write_q1(d, num_files=4, rows=700, seed=7, string_key=False, null_float_key=False):
    os.makedirs(d, exist_ok=True)
    rng = np.random.default_rng(seed)
    for i in range(num_files):
        n = rows + 37 * i  # different shapes exercise the bucket padding
        cols = {
            "g": rng.integers(0, 9, n).astype(np.int64),
            "qty": rng.integers(0, 500, n).astype(np.int64),
            "price": np.round(rng.uniform(0, 1000, n), 3),
        }
        if string_key:
            s = np.array([f"c{v}" for v in rng.integers(0, 5, n)], dtype=object)
            s[rng.random(n) < 0.03] = None
            cols["s"] = s
        if null_float_key:
            f = np.round(rng.uniform(-5, 5, n), 2)
            f[rng.random(n) < 0.05] = np.nan
            f[rng.random(n) < 0.05] = -0.0
            cols["fk"] = f
        pq.write_table(pa.table(cols), os.path.join(d, f"p{i}.parquet"))
    return d


def _write_q3(d, num_files=4, rows=900, build_rows=120, seed=3):
    probe, build = os.path.join(d, "probe"), os.path.join(d, "build")
    os.makedirs(probe, exist_ok=True)
    os.makedirs(build, exist_ok=True)
    rng = np.random.default_rng(seed)
    for i in range(num_files):
        pq.write_table(pa.table({
            "k": rng.integers(0, 80, rows).astype(np.int64),
            "g": rng.integers(0, 12, rows).astype(np.int64),
            "v": np.round(rng.standard_normal(rows), 4),
        }), os.path.join(probe, f"p{i}.parquet"))
    pq.write_table(pa.table({
        "k2": rng.integers(0, 90, build_rows).astype(np.int64),
        "w": np.round(rng.standard_normal(build_rows), 4),
    }), os.path.join(build, "b.parquet"))
    return probe, build


def _q1(df, key="g"):
    return (
        df.filter(hst.col("qty") > 40)
        .group_by(key)
        .agg(
            n=("*", "count"),
            sq=("qty", "sum"),
            sp=("price", "sum"),
            aq=("qty", "avg"),
            lo=("price", "min"),
            hi=("qty", "max"),
            sd=("price", "stddev_samp"),
        )
    )


def _q3(sess, probe_dir, build_dir):
    probe = sess.read_parquet(probe_dir)
    build = sess.read_parquet(build_dir)
    return (
        probe.join(build, on=hst.col("k") == hst.col("k2"), how="inner")
        .filter(hst.col("v") > -0.5)
        .group_by("g")
        .agg(n=("*", "count"), s=("v", "sum"), a=("w", "avg"),
             mn=("v", "min"), mx=("w", "max"))
    )


def _sorted_by(got, *keys):
    arrays = [np.asarray(got[k]) for k in keys]
    order = np.lexsort(tuple(reversed(arrays)))
    return {c: np.asarray(v)[order] for c, v in got.items()}


def assert_results_equal(got, want, float_cols=(), sort_keys=()):
    if sort_keys:
        got, want = _sorted_by(got, *sort_keys), _sorted_by(want, *sort_keys)
    assert sorted(got.keys()) == sorted(want.keys())
    for k in got:
        a, b = np.asarray(got[k]), np.asarray(want[k])
        assert a.shape == b.shape, k
        if k in float_cols:
            np.testing.assert_allclose(a, b, rtol=FLOAT_RTOL, equal_nan=True, err_msg=k)
        elif a.dtype == object or b.dtype == object:
            assert all(
                (not isinstance(x, str) and not isinstance(y, str)) or x == y
                for x, y in zip(a, b)
            ), k
        else:
            assert a.tobytes() == b.tobytes(), k


# --------------------------------------------------------------------------
# q1: fused grouped-agg stream vs the per-family stream
# --------------------------------------------------------------------------


class TestQ1Fused:
    def test_fused_byte_identical_to_per_family_stream(self, tmp_path):
        data = _write_q1(str(tmp_path / "q1"))
        sess = _mk_session(tmp_path, "off", fusion=False)
        with trace.recording() as ev_off:
            want = _q1(sess.read_parquet(data)).collect()
        assert ("agg", "device-grouped-stream") in ev_off
        sess = _mk_session(tmp_path, "on", fusion=True)
        d0, f0 = _fused_dispatches(), _counter(
            "hs_device_dispatches_total", program="grouped-agg-chunk"
        )
        with trace.recording() as ev_on:
            got = _q1(sess.read_parquet(data)).collect()
        assert ("agg", "device-grouped-stream") in ev_on
        assert _fused_dispatches() - d0 >= 4  # one fused dispatch per chunk
        # no per-family grouped-chunk dispatches on the fused stream
        assert _counter("hs_device_dispatches_total", program="grouped-agg-chunk") == f0
        # both are device streamed folds: identical to the byte
        for k in want:
            assert np.asarray(got[k]).tobytes() == np.asarray(want[k]).tobytes(), k

    def test_donation_on_off_byte_identical(self, tmp_path):
        data = _write_q1(str(tmp_path / "q1"))
        sess = _mk_session(tmp_path, "don", fusion=True, donation=True)
        got_d = _q1(sess.read_parquet(data)).collect()
        sess = _mk_session(tmp_path, "nodon", fusion=True, donation=False)
        got_n = _q1(sess.read_parquet(data)).collect()
        for k in got_d:
            assert np.asarray(got_d[k]).tobytes() == np.asarray(got_n[k]).tobytes(), k

    def test_null_and_signed_zero_float_group_keys(self, tmp_path):
        data = _write_q1(str(tmp_path / "q1"), null_float_key=True)
        sess = _mk_session(tmp_path, "off", fusion=False)
        want = _q1(sess.read_parquet(data), key="fk").collect()
        sess = _mk_session(tmp_path, "on", fusion=True)
        d0 = _fused_dispatches()
        got = _q1(sess.read_parquet(data), key="fk").collect()
        assert _fused_dispatches() > d0
        for k in want:
            assert np.asarray(got[k]).tobytes() == np.asarray(want[k]).tobytes(), k

    def test_string_group_keys_stay_per_family(self, tmp_path):
        data = _write_q1(str(tmp_path / "q1"), string_key=True)
        sess = _mk_session(tmp_path, "off", fusion=False)
        want = _q1(sess.read_parquet(data), key="s").collect()
        sess = _mk_session(tmp_path, "on", fusion=True)
        d0 = _fused_dispatches()
        got = _q1(sess.read_parquet(data), key="s").collect()
        assert _fused_dispatches() == d0  # string keys never enter the fused path
        assert_results_equal(got, want)

    def test_default_off_identity(self, tmp_path):
        """An untouched session runs zero fused programs and produces the
        same result as a fused session — flipping the default on can never
        change answers."""
        data = _write_q1(str(tmp_path / "q1"))
        sess = _mk_session(tmp_path, "default")  # fusion conf never touched
        assert sess.conf.fusion_enabled is False
        d0 = _fused_dispatches()
        want = _q1(sess.read_parquet(data)).collect()
        assert _fused_dispatches() == d0
        sess = _mk_session(tmp_path, "on", fusion=True)
        got = _q1(sess.read_parquet(data)).collect()
        for k in want:
            assert np.asarray(got[k]).tobytes() == np.asarray(want[k]).tobytes(), k

    def test_capacity_overflow_falls_back_per_chunk_then_resumes(self, tmp_path):
        """A chunk that discovers more groups than the compiled capacity
        redoes per-family (hs_device_fallback_total{op='fusion'}) and the
        stream resumes fused — results unchanged."""
        data = _write_q1(str(tmp_path / "q1"), rows=1200)
        # fused run FIRST: the process-global capacity-hint memo is cold, so
        # the floor-of-2 capacity undershoots chunk 0's 9 groups → overflow
        sess = _mk_session(
            tmp_path, "on", fusion=True,
            **{hst.keys.EXEC_AGG_CAPACITY_FLOOR: 2},
        )
        fb0, d0 = _fallbacks(), _fused_dispatches()
        got = _q1(sess.read_parquet(data)).collect()
        assert _fallbacks() > fb0
        assert _fused_dispatches() > d0  # later chunks still fused
        sess = _mk_session(tmp_path, "off", fusion=False)
        want = _q1(sess.read_parquet(data)).collect()
        assert_results_equal(
            got, want, float_cols=("sp", "aq", "lo", "sd"), sort_keys=("g",)
        )


# --------------------------------------------------------------------------
# compile-count flatness
# --------------------------------------------------------------------------


class TestCompileFlatness:
    def test_chunk_size_sweep_reuses_fused_programs(self, tmp_path):
        """Chunks padding into warm shape buckets compile nothing new: the
        fused program is keyed on (skeleton, shape bucket, mesh), not row
        count."""
        d1 = _write_q1(str(tmp_path / "a"), num_files=3, rows=700, seed=1)
        sess = _mk_session(tmp_path, "warm", fusion=True)
        _q1(sess.read_parquet(d1)).collect()  # warm the buckets
        c0 = _compiles()
        # same schema, same √2 buckets (rows pad to the same capacities)
        d2 = _write_q1(str(tmp_path / "b"), num_files=3, rows=701, seed=2)
        got = _q1(sess.read_parquet(d2)).collect()
        assert _compiles() == c0, "fused program recompiled inside a warm bucket"
        assert len(np.asarray(got["g"])) > 0


# --------------------------------------------------------------------------
# donation really donates
# --------------------------------------------------------------------------


class TestDonation:
    def test_donated_state_buffer_is_deleted(self):
        import jax
        import jax.numpy as jnp

        from hyperspace_tpu.exec import stage_ir

        jitted = stage_ir.compile_stage(
            "test-donation[regression]", lambda s, c: s + c, donate_argnums=(0,)
        )
        state = jax.device_put(jnp.zeros(64, dtype=jnp.int64))
        out = jitted(state, jnp.ones(64, dtype=jnp.int64))
        assert state.is_deleted(), "donate_argnums did not consume the state"
        assert int(out.sum()) == 64

    def test_stage_cache_reuses_compiled_program(self):
        from hyperspace_tpu.exec import stage_ir

        fn = lambda s, c: s + c  # noqa: E731
        a = stage_ir.compile_stage("test-donation[cache]", fn, donate_argnums=(0,))
        b = stage_ir.compile_stage("test-donation[cache]", fn, donate_argnums=(0,))
        assert a is b
        c = stage_ir.compile_stage("test-donation[cache]", fn)
        assert c is not a  # donation vector is part of the cache key

    def test_peak_bytes_gauge_tracks_high_water(self, tmp_path, monkeypatch):
        """``hs_device_peak_bytes`` is the allocator's own high-water mark,
        read when someone looks — no sampling on the fold path."""
        import jax

        from hyperspace_tpu.exec import stage_ir

        data = _write_q1(str(tmp_path / "q1"))
        sess = _mk_session(tmp_path, "on", fusion=True)
        monkeypatch.setattr(
            jax, "live_arrays", lambda *a, **k: pytest.fail("live_arrays walked on the query path")
        )
        _q1(sess.read_parquet(data)).collect()
        gauge = REGISTRY.gauge("hs_device_peak_bytes", "")
        # the CPU backend keeps no memory statistics: the series is absent
        assert jax.local_devices()[0].memory_stats() is None
        assert gauge.value is None
        assert REGISTRY.snapshot()["hs_device_peak_bytes"]["series"] == []
        assert "hs_device_peak_bytes{" not in REGISTRY.prometheus_text()

        class Dev:
            def __init__(self, peak):
                self.peak = peak

            def memory_stats(self):
                return {"peak_bytes_in_use": self.peak, "bytes_in_use": 1}

        # a backend that reports: the largest peak over the local devices
        monkeypatch.setattr(jax, "local_devices", lambda: [Dev(1 << 20), Dev(3 << 20)])
        assert stage_ir.device_peak_bytes() == 3 << 20
        assert gauge.value == float(3 << 20)
        assert "hs_device_peak_bytes 3.14573e+06" in REGISTRY.prometheus_text()


# --------------------------------------------------------------------------
# q3: whole-plan fused join-agg
# --------------------------------------------------------------------------


class TestQ3Fused:
    def test_fused_matches_classic_and_reduces_dispatches(self, tmp_path):
        probe_dir, build_dir = _write_q3(str(tmp_path / "q3"))
        sess = _mk_session(tmp_path, "off", fusion=False)
        want = _q3(sess, probe_dir, build_dir).collect()

        # per-family baseline over the SAME chunks: the dispatch sequence
        # the fused program replaces — hash-probe + post-join filter via
        # the streaming broadcast join, grouped chunk + merge via the
        # per-family GroupedAggStream
        from hyperspace_tpu.exec import device as D
        from hyperspace_tpu.exec.executor import Executor

        base0 = sum(
            s["value"]
            for s in (REGISTRY.snapshot().get("hs_device_dispatches_total") or {"series": []})["series"]
        )
        gs = D.GroupedAggStream(
            sess, ["g"],
            [("n", "count", None), ("s", "sum", "v"), ("a", "avg", "w"),
             ("mn", "min", "v"), ("mx", "max", "w")],
            max_groups=sess.conf.agg_max_groups,
            cap_floor=sess.conf.agg_capacity_floor,
        )
        probe = sess.read_parquet(probe_dir)
        build = sess.read_parquet(build_dir)
        joined = (
            probe.join(build, on=hst.col("k") == hst.col("k2"), how="inner")
            .filter(hst.col("v") > -0.5)
        )
        for chunk in Executor(sess).execute_stream(joined.plan):
            gs.update({c: np.asarray(v) for c, v in chunk.items()}, None)
        perfam = gs.finalize()
        perfam_dispatches = sum(
            s["value"]
            for s in REGISTRY.snapshot()["hs_device_dispatches_total"]["series"]
        ) - base0

        sess = _mk_session(tmp_path, "on", fusion=True)
        d0 = _counter("hs_device_dispatches_total", program="fused-stage-join-agg")
        base1 = sum(
            s["value"]
            for s in REGISTRY.snapshot()["hs_device_dispatches_total"]["series"]
        )
        with trace.recording() as events:
            got = _q3(sess, probe_dir, build_dir).collect()
        assert ("agg", "fused-join-agg-stream") in events
        fused_total = sum(
            s["value"]
            for s in REGISTRY.snapshot()["hs_device_dispatches_total"]["series"]
        ) - base1
        assert _counter(
            "hs_device_dispatches_total", program="fused-stage-join-agg"
        ) - d0 >= 4  # one per probe chunk

        # ≥3x fewer dispatches than the per-family program sequence
        assert perfam_dispatches >= 3 * fused_total, (perfam_dispatches, fused_total)

        float_cols = ("s", "a", "mn", "mx")
        assert_results_equal(got, want, float_cols=float_cols, sort_keys=("g",))
        assert_results_equal(got, perfam, float_cols=float_cols, sort_keys=("g",))

    def test_donation_on_off_identical(self, tmp_path):
        probe_dir, build_dir = _write_q3(str(tmp_path / "q3"))
        sess = _mk_session(tmp_path, "don", fusion=True, donation=True)
        got_d = _q3(sess, probe_dir, build_dir).collect()
        sess = _mk_session(tmp_path, "nodon", fusion=True, donation=False)
        got_n = _q3(sess, probe_dir, build_dir).collect()
        got_d, got_n = _sorted_by(got_d, "g"), _sorted_by(got_n, "g")
        for k in got_d:
            assert np.asarray(got_d[k]).tobytes() == np.asarray(got_n[k]).tobytes(), k

    def test_string_group_key_falls_back_counted(self, tmp_path):
        """A q3 chain grouped by a string key cannot fuse: the fallback is
        counted in hs_device_fallback_total{op='fusion'} and the classic
        path answers, unchanged."""
        probe_dir, build_dir = _write_q3(str(tmp_path / "q3"))
        # rewrite the probe side with a string group column
        rng = np.random.default_rng(5)
        for i, f in enumerate(sorted(os.listdir(probe_dir))):
            t = pq.read_table(os.path.join(probe_dir, f))
            n = t.num_rows
            t = t.append_column(
                "gs", pa.array([f"s{v}" for v in rng.integers(0, 6, n)])
            )
            pq.write_table(t, os.path.join(probe_dir, f))

        def q(sess):
            probe = sess.read_parquet(probe_dir)
            build = sess.read_parquet(build_dir)
            return (
                probe.join(build, on=hst.col("k") == hst.col("k2"), how="inner")
                .group_by("gs")
                .agg(n=("*", "count"), s=("v", "sum"))
            )

        sess = _mk_session(tmp_path, "off", fusion=False)
        want = q(sess).collect()
        sess = _mk_session(tmp_path, "on", fusion=True)
        fb0, d0 = _fallbacks(), _counter(
            "hs_device_dispatches_total", program="fused-stage-join-agg"
        )
        got = q(sess).collect()
        assert _fallbacks() > fb0
        assert _counter(
            "hs_device_dispatches_total", program="fused-stage-join-agg"
        ) == d0
        assert_results_equal(got, want, float_cols=("s",), sort_keys=("gs",))


# --------------------------------------------------------------------------
# top-k: fused select+merge
# --------------------------------------------------------------------------


def _write_topk(d, num_files=5, rows=600, seed=13):
    os.makedirs(d, exist_ok=True)
    rng = np.random.default_rng(seed)
    for i in range(num_files):
        v = np.round(rng.uniform(-100, 100, rows), 3)
        v[rng.choice(rows, 10, replace=False)] = np.nan
        name = np.array([f"n{j % 17:02d}" for j in range(rows)], dtype=object)
        pq.write_table(pa.table({
            "k": rng.integers(0, 5000, rows).astype(np.int64),
            "v": v,
            "name": name,
        }), os.path.join(d, f"p{i}.parquet"))
    return d


class TestTopkFused:
    def test_fused_byte_identical_multi_key_nan(self, tmp_path):
        data = _write_topk(str(tmp_path / "tk"))
        q = lambda df: df.order_by("v", "k", ascending=[False, True]).limit(25)  # noqa: E731
        sess = _mk_session(tmp_path, "off", fusion=False)
        want = q(sess.read_parquet(data)).collect()
        sess = _mk_session(tmp_path, "on", fusion=True)
        d0 = _counter("hs_device_dispatches_total", program="fused-stage-topk")
        with trace.recording() as events:
            got = q(sess.read_parquet(data)).collect()
        assert ("topk", "device-topk-stream") in events
        # chunk 2..n fold fused (the first chunk has no state to merge into)
        assert _counter(
            "hs_device_dispatches_total", program="fused-stage-topk"
        ) - d0 >= 4
        for k in want:
            assert np.asarray(got[k]).tobytes() == np.asarray(want[k]).tobytes(), k

    def test_donation_on_off_byte_identical(self, tmp_path):
        data = _write_topk(str(tmp_path / "tk"))
        q = lambda df: df.order_by("v", ascending=[False]).limit(40)  # noqa: E731
        sess = _mk_session(tmp_path, "don", fusion=True, donation=True)
        got_d = q(sess.read_parquet(data)).collect()
        sess = _mk_session(tmp_path, "nodon", fusion=True, donation=False)
        got_n = q(sess.read_parquet(data)).collect()
        for k in got_d:
            assert np.asarray(got_d[k]).tobytes() == np.asarray(got_n[k]).tobytes(), k

    def test_string_keys_stay_per_family(self, tmp_path):
        data = _write_topk(str(tmp_path / "tk"))
        q = lambda df: df.order_by("name", "k").limit(20)  # noqa: E731
        sess = _mk_session(tmp_path, "off", fusion=False)
        want = q(sess.read_parquet(data)).collect()
        sess = _mk_session(tmp_path, "on", fusion=True)
        d0 = _counter("hs_device_dispatches_total", program="fused-stage-topk")
        got = q(sess.read_parquet(data)).collect()
        # string keys need the host re-encode between select and merge
        assert _counter(
            "hs_device_dispatches_total", program="fused-stage-topk"
        ) == d0
        assert_results_equal(got, want)


# --------------------------------------------------------------------------
# sharded twins
# --------------------------------------------------------------------------


class TestShardedFused:
    def test_sharded_fused_grouped_agg_matches_per_family_sharded(self, tmp_path):
        """Fused vs per-family on the SAME topology is byte-identical (same
        shard-local fold order); sharded vs single-device floats compare to
        tolerance — the established mesh-exec discipline (shard-local sums
        reassociate float addition)."""
        data = _write_q1(str(tmp_path / "q1"), rows=900)
        shard_conf = {hst.keys.PARALLEL_ENABLED: True, hst.keys.PARALLEL_MIN_ROWS: 0}
        sess = _mk_session(tmp_path, "shoff", fusion=False, **shard_conf)
        want = _q1(sess.read_parquet(data)).collect()
        sess = _mk_session(tmp_path, "shon", fusion=True, **shard_conf)
        d0 = _counter("hs_device_dispatches_total", program="fused-stage-agg-sharded")
        got = _q1(sess.read_parquet(data)).collect()
        assert _counter(
            "hs_device_dispatches_total", program="fused-stage-agg-sharded"
        ) > d0
        for k in want:
            assert np.asarray(got[k]).tobytes() == np.asarray(want[k]).tobytes(), k
        sess = _mk_session(tmp_path, "single", fusion=True)
        single = _q1(sess.read_parquet(data)).collect()
        assert_results_equal(
            got, single, float_cols=("sp", "aq", "lo", "sd"), sort_keys=("g",)
        )

    def test_sharded_fused_topk_matches_single_device(self, tmp_path):
        data = _write_topk(str(tmp_path / "tk"))
        q = lambda df: df.order_by("v", "k", ascending=[False, True]).limit(30)  # noqa: E731
        sess = _mk_session(tmp_path, "single", fusion=True)
        want = q(sess.read_parquet(data)).collect()
        sess = _mk_session(
            tmp_path, "sharded", fusion=True,
            **{hst.keys.PARALLEL_ENABLED: True, hst.keys.PARALLEL_MIN_ROWS: 0},
        )
        d0 = _counter("hs_device_dispatches_total", program="fused-stage-topk-sharded")
        got = q(sess.read_parquet(data)).collect()
        assert _counter(
            "hs_device_dispatches_total", program="fused-stage-topk-sharded"
        ) > d0
        for k in want:
            assert np.asarray(got[k]).tobytes() == np.asarray(want[k]).tobytes(), k


# --------------------------------------------------------------------------
# HLO contracts at program-cache fill
# --------------------------------------------------------------------------


class TestHloContracts:
    def test_fused_programs_verify_clean(self, tmp_path):
        from hyperspace_tpu.check import hlo_lint

        hlo_lint.reset_runtime_state()
        data = _write_q1(str(tmp_path / "q1"), seed=29)
        probe_dir, build_dir = _write_q3(str(tmp_path / "q3"), seed=31)
        tk = _write_topk(str(tmp_path / "tk"), seed=37)
        sess = _mk_session(
            tmp_path, "hlo", fusion=True,
            **{hst.keys.CHECK_HLO_ENABLED: True},
        )
        v0 = _counter("hs_check_programs_verified_total", program="fused-stage-agg")
        _q1(sess.read_parquet(data)).collect()
        _q3(sess, probe_dir, build_dir).collect()
        sess.read_parquet(tk).order_by("v", ascending=[False]).limit(25).collect()
        assert _counter(
            "hs_check_programs_verified_total", program="fused-stage-agg"
        ) > v0
        bad = hlo_lint.runtime_violations()
        assert bad == [], "\n".join(f.render() for f in bad)
