"""The measurement inside the program (ISSUE 25): stages and their counters on
the build path, queue wait and tier spans on the served path, link bytes, and
device programs under stable names. docs/observability.md "Inside the program"
is the table these tests hold the code to."""

import ast
import collections
import functools
import glob
import os
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu import col
from hyperspace_tpu.check import hlo_lint
from hyperspace_tpu.obs import spans
from hyperspace_tpu.obs.metrics import REGISTRY

pytestmark = pytest.mark.obs

PKG = os.path.dirname(hst.__file__)

#: every stage a covering-index build through the public API goes through, cat
#: "build" (docs/observability.md); take and write are the pool's thread-seconds
BUILD_STAGES = {
    "decode-keys", "encode-keys", "h2d-launch", "decode-payload", "combine",
    "d2h-counts", "d2h-perm", "take-write", "take", "write", "log-commit",
}


def stage_values(cat="build"):
    series = REGISTRY.snapshot().get("hs_stage_seconds_total", {"series": []})["series"]
    return {s["labels"]["stage"]: s["value"] for s in series if s["labels"]["cat"] == cat}


def counter(name, **labels):
    return REGISTRY.counter(name, **labels).value


def growth(before, after):
    return {k: after[k] - before.get(k, 0.0) for k in after if after[k] != before.get(k, 0.0)}


class RecordingAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: keeps what was entered."""

    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        RecordingAnnotation.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        RecordingAnnotation.log.append(("exit", self.name))


@pytest.fixture()
def annotations(monkeypatch):
    import jax

    RecordingAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", RecordingAnnotation)
    return RecordingAnnotation.log


# --- one clock: spans and stages ---------------------------------------------


class TestStagePrimitive:
    def test_disabled_span_is_the_shared_null_manager(self, annotations):
        assert spans.current_span() is None
        assert spans.span("anything", cat="exec") is spans._NULL_CM
        with spans.span("anything", cat="exec") as sp:
            assert sp is spans.NULL_SPAN
        assert annotations == []  # the disabled path enters nothing

    def test_enabled_span_enters_a_trace_annotation(self, annotations):
        with spans.trace("t") as root:
            with spans.span("filter-mask", cat="exec"):
                with spans.span("device-wait", cat="device", program="fused-filter"):
                    pass
        assert annotations == [
            ("enter", "hs:exec:filter-mask"), ("enter", "hs:device:device-wait"),
            ("exit", "hs:device:device-wait"), ("exit", "hs:exec:filter-mask"),
        ]
        assert [s.name for s in root.walk()] == ["t", "filter-mask", "device-wait"]

    def test_stage_counts_with_tracing_off_and_annotates(self, annotations):
        before = stage_values("unit").get("alpha", 0.0)
        assert spans.current_span() is None
        with spans.stage("alpha", "unit") as sp:
            time.sleep(0.02)
            assert sp is spans.NULL_SPAN
        assert stage_values("unit")["alpha"] - before >= 0.02
        assert annotations == [("enter", "hs:unit:alpha"), ("exit", "hs:unit:alpha")]
        # the series is held by the module: no registry lookup per entry
        assert spans.stage_seconds("alpha", "unit") is spans.stage_seconds("alpha", "unit")

    def test_stage_opens_a_span_under_a_current_trace(self, annotations):
        before = stage_values("unit").get("beta", 0.0)
        with spans.trace("t") as root:
            with spans.stage("beta", "unit") as sp:
                assert sp is spans.current_span() and sp.name == "beta" and sp.cat == "unit"
        assert [s.name for s in root.walk()] == ["t", "beta"]
        assert root.children[0].t1 is not None
        assert stage_values("unit")["beta"] > before
        assert annotations.count(("enter", "hs:unit:beta")) == 1  # the span's, not a second one

    def test_nested_stage_seconds_are_each_stage_s_own(self):
        before = stage_values("unit")
        t0 = time.perf_counter()
        with spans.stage("outer", "unit"):
            time.sleep(0.01)
            with spans.stage("inner", "unit"):
                time.sleep(0.03)
        wall = time.perf_counter() - t0
        got = growth(before, stage_values("unit"))
        assert got["inner"] >= 0.03 and got["outer"] >= 0.01
        assert got["outer"] < wall - 0.03 + 1e-3  # the inner stage's time is not the outer's too
        assert got["inner"] + got["outer"] == pytest.approx(wall, abs=2e-3)

    def test_a_stage_on_another_thread_does_not_nest(self):
        before = stage_values("unit")

        def other():
            with spans.stage("pooled", "unit"):
                time.sleep(0.02)

        with spans.stage("driver", "unit"):
            t = threading.Thread(target=other)
            t.start()
            t.join()
        got = growth(before, stage_values("unit"))
        assert got["pooled"] >= 0.02 and got["driver"] >= 0.02  # thread-seconds, both whole


# --- the build path ------------------------------------------------------------


def write_source(root, rows=200_000, files=4, seed=0):
    os.makedirs(root, exist_ok=True)
    rng = np.random.default_rng(seed)
    per = rows // files
    for i in range(files):
        pq.write_table(
            pa.table({
                "k": rng.integers(0, 50_000, per),
                "v": rng.standard_normal(per),
                "s": np.array([f"name_{j % 97}" for j in range(per)]),
            }),
            os.path.join(root, f"part-{i:05d}.parquet"),
        )
    return root


class TestBuildStages:
    def test_a_build_emits_exactly_the_documented_stages(self, tmp_path):
        src = write_source(str(tmp_path / "src"))
        sess = hst.Session(conf={
            hst.keys.SYSTEM_PATH: str(tmp_path / "idx"),
            hst.keys.NUM_BUCKETS: 16,
            "hyperspace.tpu.build.batchRows": 60_000,
        })
        hst.set_session(sess)
        try:
            stages0 = stage_values()
            rows0, bytes0 = counter("hs_build_rows_total"), counter("hs_build_source_bytes_total")
            up0 = counter("hs_h2d_bytes_total", site="build-keys")
            perm0 = counter("hs_d2h_bytes_total", site="build-perm")
            df = sess.read_parquet(src)
            hst.Hyperspace(sess).create_index(df, hst.CoveringIndexConfig("inside", ["k"], ["v", "s"]))
        finally:
            hst.set_session(None)
        grown = growth(stages0, stage_values())
        assert set(grown) == BUILD_STAGES
        assert all(v > 0 for v in grown.values())
        assert counter("hs_build_rows_total") - rows0 == 200_000
        # Arrow bytes of what was decoded: keys and payload, every column once
        table = pq.ParquetDataset(src).read()
        assert counter("hs_build_source_bytes_total") - bytes0 == pytest.approx(table.nbytes, rel=0.02)
        # four chunks of 50k rows, each padded to 65,536: one key plane up (the
        # encoder narrows keys below 2**31 to int32), an int32 permutation down
        assert counter("hs_h2d_bytes_total", site="build-keys") - up0 == 4 * 65_536 * 4
        assert counter("hs_d2h_bytes_total", site="build-perm") - perm0 == 4 * 65_536 * 4

    @pytest.mark.parametrize("run", range(3))
    def test_stage_seconds_account_for_the_write_bucketed_wall(self, tmp_path, run):
        """Wall stages are the driver thread's own seconds and nest without
        double counting, so they add up to the call's wall from below."""
        from hyperspace_tpu.indexes.covering import write_bucketed

        rng = np.random.default_rng(run)
        n = 200_000
        keys = pa.table({"k": rng.integers(0, 50_000, n)})
        payload = pa.table({"v": rng.standard_normal(n), "w": rng.integers(0, 9, n)})
        before = stage_values()
        t0 = time.perf_counter()
        files = write_bucketed(
            keys, ["k"], 16, str(tmp_path / "out"), payload_fn=lambda: payload,
            column_order=["k", "v", "w"], batch_rows=50_000,
        )
        wall = time.perf_counter() - t0
        grown = growth(before, stage_values())
        assert set(grown) == BUILD_STAGES - {"decode-keys", "log-commit"}
        staged = sum(v for k, v in grown.items() if k not in ("take", "write"))
        assert staged <= wall * 1.001
        assert staged >= 0.9 * wall, (staged, wall, grown)
        assert len(files) == 4 * 16
        # thread-seconds of the pool, not wall: they may pass the drain's wall
        assert grown["take"] > 0 and grown["write"] > 0


# --- the served path -----------------------------------------------------------


@pytest.fixture()
def indexed(tmp_path):
    """1000 rows behind a covering index, device filter at any size, tracing on."""
    rng = np.random.default_rng(7)
    n = 1000
    root = tmp_path / "data"
    root.mkdir()
    pq.write_table(
        pa.table({"c1": rng.integers(0, 100, n), "c2": rng.integers(0, 1000, n),
                  "c3": rng.standard_normal(n)}),
        root / "part-00000.parquet",
    )
    sess = hst.Session(conf={
        hst.keys.SYSTEM_PATH: str(tmp_path / "idx"),
        hst.keys.NUM_BUCKETS: 4,
        hst.keys.TPU_QUERY_DEVICE_MIN_ROWS: 0,
        hst.keys.OBS_TRACING_ENABLED: True,
    })
    hst.set_session(sess)
    df = sess.read_parquet(str(root))
    hst.Hyperspace(sess).create_index(df, hst.CoveringIndexConfig("insideIdx", ["c1"], ["c2", "c3"]))
    sess.enable_hyperspace()
    yield sess, df
    hst.set_session(None)


class TestServedPath:
    def test_queue_wait_is_at_least_the_block_and_agrees_with_the_histogram(self, indexed, monkeypatch):
        from hyperspace_tpu.serving import QueryServer

        sess, df = indexed
        gate, entered = threading.Event(), threading.Event()
        real = QueryServer._execute_requests

        def held(self, reqs):
            entered.set()
            gate.wait(5)
            return real(self, reqs)

        monkeypatch.setattr(QueryServer, "_execute_requests", held)
        q = df.filter(col("c1") > 20).select("c2")
        with QueryServer(sess, workers=1, name="inside-qw") as server:
            hist = REGISTRY.histogram(
                "hs_admission_wait_seconds", tenant="default", cost_class="unknown", server="inside-qw"
            )
            sum0 = hist.sum
            first = server.submit(q)
            assert entered.wait(5)
            second = server.submit(q)  # the single worker is held: this one queues
            block = 0.25
            time.sleep(block)
            gate.set()
            first.result(10), second.result(10)
            waits = [f.request_root.find("queue-wait") for f in (first, second)]
        assert [len(w) for w in waits] == [1, 1]
        for w in waits:
            assert w[0].cat == "serving" and w[0].t1 is not None
        assert waits[1][0].duration_s >= block
        # the same two waits, once on time.monotonic() and once on the tracer's clock
        in_tree = sum(w[0].duration_s for w in waits)
        assert hist.sum - sum0 == pytest.approx(in_tree, abs=0.01)
        # the wait is the root's child, before the worker's stages
        names = [c.name for c in sorted(second.request_root.children, key=lambda c: c.t0)]
        assert names.index("queue-wait") < names.index("execute")

    def test_filter_tiers_and_link_bytes(self, indexed):
        from hyperspace_tpu.exec import device as D

        sess, df = indexed
        q = df.filter(col("c1") > 20).select("c2")
        n_dev = sess.mesh.devices.size
        padded = D._pad_to_bucket(np.zeros(1000, dtype=np.int64), n_dev, 0)
        D.clear_device_cache()
        up = REGISTRY.counter("hs_h2d_bytes_total", site="filter-cols")
        down = REGISTRY.counter("hs_d2h_bytes_total", site="filter-mask")
        up0, down0 = up.value, down.value
        with spans.trace("first") as root:
            q.collect()
        assert up.value - up0 == padded.nbytes  # the padded int64 column went up
        assert down.value - down0 == padded.shape[0]  # a padded bool mask came down
        flt = root.find("Filter")[0]
        kids = [c.name for c in sorted(flt.children, key=lambda c: c.t0)]
        assert kids[-2:] == ["filter-mask", "filter-apply"]
        mask = root.find("filter-mask")[0]
        assert ("filter", "device") in mask.events
        wait = mask.find("device-wait")
        assert len(wait) == 1 and wait[0].attrs["program"] == "fused-filter"
        # the second query finds its column resident: nothing goes up again
        up1, down1 = up.value, down.value
        with spans.trace("second"):
            q.collect()
        assert up.value == up1
        assert down.value - down1 == padded.shape[0]

    def test_peak_bytes_gauge_reads_the_allocator_high_water(self, indexed, monkeypatch):
        import jax

        from hyperspace_tpu.exec import device as D

        sess, df = indexed
        monkeypatch.setattr(
            jax, "live_arrays", lambda *a, **k: pytest.fail("live_arrays walked on the query path")
        )
        D.clear_device_cache()
        with spans.trace("peak") as root:
            df.filter(col("c1") > 20).select("c2").collect()
        assert root.find("filter-mask")  # a device program ran
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
        assert D.device_peak_bytes() == 3 << 20
        assert gauge.value == float(3 << 20)
        assert "hs_device_peak_bytes 3.14573e+06" in REGISTRY.prometheus_text()

    def test_join_and_aggregate_tiers_are_spans_named_after_the_dispatch(self, tmp_path):
        rng = np.random.default_rng(3)
        left = tmp_path / "l"
        right = tmp_path / "r"
        left.mkdir(), right.mkdir()
        pq.write_table(pa.table({"a": rng.integers(0, 50, 400), "x": rng.standard_normal(400)}),
                       left / "p.parquet")
        pq.write_table(pa.table({"b": np.arange(50), "y": np.arange(50) % 5}), right / "p.parquet")
        sess = hst.Session(conf={hst.keys.SYSTEM_PATH: str(tmp_path / "idx")})
        hst.set_session(sess)
        try:
            l, r = sess.read_parquet(str(left)), sess.read_parquet(str(right))
            q = l.join(r, col("a") == col("b")).group_by("y").agg(s=("x", "sum"))
            with spans.trace("device-tiers") as root:
                out = q.collect()
            sess.conf.set(hst.keys.TPU_QUERY_DEVICE_EXECUTION, False)
            with spans.trace("host-tiers") as host_root:
                host_out = q.collect()
        finally:
            hst.set_session(None)
        assert len(out["y"]) == 5 and len(host_out["y"]) == 5
        # no index: the fused bucketed-join aggregate is tried and refused (the
        # reason rides on its span), the join runs as a broadcast hash stream,
        # the aggregate on the host
        refused = root.find("agg-fused-bucketed-join")
        assert len(refused) == 1 and refused[0].attrs["fallback"] == "join-unsupported"
        tier = root.find("join-broadcast-hash-stream")
        assert len(tier) == 1 and tier[0].cat == "exec" and "fallback" not in tier[0].attrs
        assert ("join", "broadcast-hash-stream") in [e for s in tier[0].walk() for e in s.events]
        assert {w.attrs["program"] for w in tier[0].find("device-wait")} == {"hash-build", "hash-probe"}
        assert len(root.find("agg-host")) == 1
        # device execution off: the generic merge, with the two sides' scans
        # under it, so that its own time is the merge and the gathers
        merge = host_root.find("join-generic-merge")
        assert len(merge) == 1 and ("join", "generic-merge") in merge[0].events
        assert [c.name for c in merge[0].children] == ["Scan", "Scan"]
        assert not host_root.find("device-wait")


# --- a launch is a span with a cause, a wait names its request (ISSUE 39) -----------


def dispatches():
    series = REGISTRY.snapshot().get("hs_device_dispatches_total", {"series": []})["series"]
    return {s["labels"]["program"]: s["value"] for s in series}


def launches_of(root):
    return [(sp.attrs["program"], sp.trace_id) for sp in root.find("device-launch")]


class TestLaunchAndWait:
    def test_with_no_trace_launch_counts_and_is_the_shared_null_manager(self, annotations):
        from hyperspace_tpu.exec import device as D

        assert spans.current_span() is None
        before = dispatches().get("fused-filter", 0.0)
        cm = D.launch("fused-filter")
        assert cm is spans._NULL_CM
        with cm as sp:
            assert sp is spans.NULL_SPAN
        assert dispatches()["fused-filter"] - before == 1.0
        assert spans.request_span("device-wait", cat="device", program="fused-filter") is spans._NULL_CM
        assert annotations == []  # off means off: nothing entered
        # the series and the module's name are held by the module: no registry lookup a launch
        assert D._LAUNCHES["fused-filter"][1] == "jit_" + hlo_lint.program_name("fused-filter")
        assert D._LAUNCHES["fused-filter"][0] is REGISTRY.counter("hs_device_dispatches_total", program="fused-filter")

    def test_the_annotations_name_the_module_and_the_request(self, annotations):
        from hyperspace_tpu.exec import device as D

        with spans.trace("t") as root:
            with spans.span("filter-mask", cat="exec"):
                with D.launch("join-expand-gather") as sp:
                    assert sp.name == "device-launch" and sp.cat == "device"
                    assert sp.attrs == {"program": "join-expand-gather"}
                D.fetch(np.zeros(3), "filter-mask", "join-expand-gather")
        rid = root.trace.id
        assert rid and all(sp.trace_id == rid for sp in root.walk())
        launch = f"hs:device:device-launch module=jit_hs_join_expand_gather request={rid}"
        wait = f"hs:device:device-wait request={rid}"
        assert annotations == [
            ("enter", "hs:exec:filter-mask"), ("enter", launch), ("exit", launch),
            ("enter", wait), ("exit", wait), ("exit", "hs:exec:filter-mask"),
        ]
        # the wait keeps name, category and attr, and has no child
        waits = root.find("device-wait")
        assert [(w.cat, w.attrs, w.children) for w in waits] == [("device", {"program": "join-expand-gather"}, [])]

    def test_a_trace_takes_the_bound_context_s_identifier_else_a_serial(self):
        ctx = spans.TraceContext.new()
        with spans.bind_context(ctx):
            with spans.trace("routed") as routed:
                pass
        assert routed.trace_id == ctx.trace_id
        assert spans.start_trace("given", trace_id="abc").trace_id == "abc"
        a, b = spans.start_trace("a"), spans.start_trace("b")
        assert a.trace_id != b.trace_id and a.trace_id.startswith("r") and len(a.trace_id) <= 9

    def test_a_helper_thread_launches_for_the_request_that_wrapped_it(self, annotations):
        from hyperspace_tpu.exec import device as D

        def helper():
            with D.launch("hash-probe"):
                pass

        with spans.trace("t") as root:
            t = threading.Thread(target=spans.wrap(helper))
            t.start()
            t.join()
        (launch,) = root.find("device-launch")
        assert launch.tid != root.tid and launch.trace_id == root.trace_id
        assert ("enter", f"hs:device:device-launch module=jit_hs_hash_probe request={root.trace_id}") in annotations

    @pytest.mark.parametrize("shape, programs, tier", [
        ("filter", ["fused-filter"], "filter-mask"),
        ("fused-aggregate", ["fused-agg"], "agg-device-fused-scan"),
        ("grouped-aggregate", ["grouped-agg-keyed", "grouped-agg-keyed-probe"], "agg-device-grouped-scan"),
        ("grouped-aggregate-float-key", ["grouped-agg-chunk"], "agg-device-fold"),
    ])
    def test_a_dispatch_is_one_launch_span_and_one_count_on_its_request_s_tree(self, indexed, shape, programs, tier):
        sess, df = indexed
        q = {
            "filter": lambda: df.filter(col("c1") > 20).select("c2"),
            "fused-aggregate": lambda: df.filter(col("c1") > 20).agg(s=("c3", "sum")),
            "grouped-aggregate": lambda: df.filter(col("c1") > 20).group_by("c2").agg(s=("c3", "sum")),
            # a float key is not the keyed program's: the sort-based engine over the host batch
            "grouped-aggregate-float-key": lambda: df.filter(col("c1") > 20).group_by("c3").agg(s=("c2", "sum")),
        }[shape]()
        before = dispatches()
        with spans.trace("one") as root:
            q.collect()
        grew = growth(before, dispatches())
        found = launches_of(root)
        assert sorted({p for p, _ in found}) == programs
        assert grew == dict(collections.Counter(p for p, _ in found))  # one count a span, no other
        assert {rid for _, rid in found} == {root.trace_id}
        for sp in root.find("device-launch"):
            assert sp.cat == "device" and sp.t1 is not None and not sp.children and not sp.events
        # the launch is a child of the tier span that holds the jitted call,
        # beside the wait for it
        holder = root.find(tier)[0]
        kids = [c.name for c in holder.children]
        assert "device-launch" in kids and kids.index("device-launch") < kids.index("device-wait")
        assert not [ev for sp in root.walk() for ev in sp.events if ev[0] == "device-program"]

    def test_a_keyed_launch_counts_its_selected_and_sorted_rows_and_the_tier_says_how_it_skipped(self, indexed):
        sess, df = indexed
        q = df.filter(col("c1") > 20).group_by("c2").agg(s=("c3", "sum"))
        kept = int((df.select("c1").collect()["c1"] > 20).sum())
        before = {k: counter("hs_keyed_rows_total", kind=k) for k in ("selected", "sorted")}
        launched = counter("hs_device_dispatches_total", program="grouped-agg-keyed")
        with spans.trace("keyed") as root:
            q.collect()
        launched = counter("hs_device_dispatches_total", program="grouped-agg-keyed") - launched
        tier = root.find("agg-device-grouped-scan")[0]
        assert tier.attrs["program"] == "grouped-agg-keyed" and tier.attrs["selected_rows"] == kept
        # 1,000 rows lie in the first block of the padded scan: it alone goes on
        from hyperspace_tpu.exec.device import _KEYED_BLOCK_ROWS

        assert tier.attrs["skip"] == ("copy" if sess.mesh.devices.size == 1 else "layout")
        assert tier.attrs["blocks"] == 1 and tier.attrs["rows_on"] == _KEYED_BLOCK_ROWS >= 1000
        assert launched >= 1
        assert counter("hs_keyed_rows_total", kind="selected") - before["selected"] == launched * kept
        assert counter("hs_keyed_rows_total", kind="sorted") - before["sorted"] == launched * tier.attrs["rows_on"]

    def test_a_join_s_programs_launch_on_the_joining_request_s_tree(self, tmp_path):
        rng = np.random.default_rng(3)
        left, right = tmp_path / "l", tmp_path / "r"
        left.mkdir(), right.mkdir()
        pq.write_table(pa.table({"a": rng.integers(0, 50, 400), "x": rng.standard_normal(400)}),
                       left / "p.parquet")
        pq.write_table(pa.table({"b": np.arange(50), "y": np.arange(50) % 5}), right / "p.parquet")
        sess = hst.Session(conf={hst.keys.SYSTEM_PATH: str(tmp_path / "idx")})
        hst.set_session(sess)
        try:
            l, r = sess.read_parquet(str(left)), sess.read_parquet(str(right))
            before = dispatches()
            with spans.trace("join") as root:
                l.join(r, col("a") == col("b")).select("x", "y").collect()
        finally:
            hst.set_session(None)
        found = launches_of(root)
        assert {p for p, _ in found} == {"hash-build", "hash-probe"}
        assert growth(before, dispatches()) == dict(collections.Counter(p for p, _ in found))
        assert {rid for _, rid in found} == {root.trace_id}
        tier = root.find("join-broadcast-hash-stream")[0]
        assert {sp.attrs["program"] for sp in tier.find("device-launch")} == {"hash-build", "hash-probe"}

    def test_two_concurrent_requests_carry_two_identifiers_and_their_own_launches(self, indexed, annotations):
        from hyperspace_tpu.serving import QueryServer

        sess, df = indexed
        q = df.filter(col("c1") > 20).select("c2")
        # two shapes, so that neither request rides on the other's scan
        queries = {"fused_filter": q, "fused_agg": df.filter(col("c1") > 30).agg(s=("c3", "sum"))}
        with QueryServer(sess, workers=2, name="inside-launch") as server:
            futures = {m: server.submit(query) for m, query in queries.items()}
            for f in futures.values():
                f.result(30)
        roots = {m: f.request_root for m, f in futures.items()}
        assert len({r.trace_id for r in roots.values()}) == 2
        for module, root in roots.items():
            rid = root.trace_id
            found = launches_of(root)
            assert found and {i for _, i in found} == {rid}
            assert {sp.trace_id for sp in root.walk()} == {rid}
            assert ("enter", f"hs:device:device-launch module=jit_hs_{module} request={rid}") in annotations
            assert ("enter", f"hs:device:device-wait request={rid}") in annotations
        # a routed request keeps the router's identifier
        ctx = spans.TraceContext.new()
        with QueryServer(sess, workers=1, name="inside-launch-routed") as server:
            f = server.submit(q, trace_context=ctx)
            f.result(30)
        assert f.request_root.trace_id == ctx.trace_id
        assert {rid for _, rid in launches_of(f.request_root)} == {ctx.trace_id}


# --- stable names for device programs ---------------------------------------------


def _jit_sites(tree):
    """(enclosing function, line, named) of every jax.jit call or decorator in
    a module; named = the jitted function goes through hlo_lint.named()."""

    def is_jit(node):
        return (isinstance(node, ast.Attribute) and node.attr == "jit"
                and isinstance(node.value, ast.Name) and node.value.id == "jax")

    def named_call(node):
        return (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                and node.func.attr == "named")

    out = []

    def visit(node, func):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if is_jit(dec) or (isinstance(dec, ast.Call) and any(is_jit(a) for a in dec.args)):
                    out.append((node.name, dec.lineno, False))  # @jax.jit, @partial(jax.jit, ...)
            func = node.name
        elif isinstance(node, ast.Call) and is_jit(node.func):
            out.append((func, node.lineno, bool(node.args) and named_call(node.args[0])))
        for child in ast.iter_child_nodes(node):
            visit(child, func)

    visit(tree, "<module>")
    return out


#: jit sites that name no program of their own: a legacy library entry point
#: and the Pallas kernels' wrappers (a kernel is found in the trace by its own
#: name, e.g. _hist_call)
UNNAMED_OK = {
    ("ops/sort.py", "bucket_sort_perm"),
    ("ops/kernels.py", "_minmax_call"),
    ("ops/kernels.py", "_hist_call"),
}


@functools.lru_cache(maxsize=None)
def _family_literals():
    """(registered, elsewhere): how often each string literal of the package
    is the family argument of a ``register_contract`` call, and how often it
    stands anywhere else (where a program of that family is named, verified,
    counted or dispatched)."""
    registered, everywhere = collections.Counter(), collections.Counter()
    for path in glob.glob(os.path.join(PKG, "**", "*.py"), recursive=True):
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=path)
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                everywhere[node.value] += 1
            elif (
                isinstance(node, ast.Call)
                and getattr(node.func, "attr", getattr(node.func, "id", None)) == "register_contract"
                and node.args
                and isinstance(node.args[0], ast.Constant)
            ):
                registered[node.args[0].value] += 1
    return registered, everywhere - registered


@pytest.mark.parametrize("family", sorted(_family_literals()[0]))
def test_registered_family_is_given_to_a_program(family):
    """Direction two: a contract with no program behind it is drift too. One
    left behind for a deleted program fails here: its family is named nowhere
    in the package but at its registration."""
    registered, elsewhere = _family_literals()
    assert registered[family] == 1, f"{family!r} is registered {registered[family]} times"
    assert elsewhere[family] >= 1, f"{family}: declared, but no program site names it"


def test_the_source_scan_and_the_registry_agree():
    # the parametrisation above reads sources (an empty scan would run no
    # case); the registry is the truth, scratch families of the tests aside
    import hyperspace_tpu.exec.join_stream  # noqa: F401
    import hyperspace_tpu.exec.lineage  # noqa: F401
    import hyperspace_tpu.ops.bucketize  # noqa: F401
    import hyperspace_tpu.ops.sort  # noqa: F401

    scanned = set(_family_literals()[0])
    assert scanned and scanned <= set(hlo_lint.registered_contracts())


class TestProgramNames:
    def test_every_family_has_its_program_name(self):
        for family in hlo_lint.registered_contracts():
            name = hlo_lint.program_name(family)
            assert name == "hs_" + family.replace("-", "_") and name.isidentifier()

    def test_named_renames_and_refuses_an_undeclared_family(self):
        def program(x):
            return x

        assert hlo_lint.named("fused-filter", program).__name__ == "hs_fused_filter"
        with pytest.raises(KeyError):
            hlo_lint.named("no-such-family", program)

    def test_every_jit_in_the_package_names_its_program(self):
        """Direction one: no program is compiled under a name the profiler's
        readers do not know."""
        unnamed = []
        for path in glob.glob(os.path.join(PKG, "**", "*.py"), recursive=True):
            rel = os.path.relpath(path, PKG)
            if rel.startswith("check" + os.sep):
                continue
            for func, lineno, named in _jit_sites(ast.parse(open(path).read())):
                if not named and (rel, func) not in UNNAMED_OK:
                    unnamed.append(f"{rel}:{lineno} in {func}")
        assert unnamed == []

    def test_programs_lower_to_modules_named_after_their_family(self, tmp_path):
        """With the check on, every program a workload compiles is verified
        against its contract, the module name included (rule program-name)."""
        import jax.numpy as jnp

        from hyperspace_tpu.ops import sort
        from hyperspace_tpu.utils.x64 import ensure_x64

        ensure_x64()
        # the build program, which no query compiles
        keys = (jnp.zeros(8, dtype=jnp.int64),)
        lowered = sort._build_sorted.lower(keys, (), np.int32(8), 4, ("i",), True)
        assert lowered.as_text().startswith("module @jit_hs_index_build")
        scoped = lowered.as_text(debug_info=True)  # the phases' named scopes, on every op
        assert all(f"jit(hs_index_build)/{phase}" in scoped for phase in ("hash", "sort", "histogram"))

        rng = np.random.default_rng(5)
        n = 4000
        root = tmp_path / "d"
        root.mkdir()
        for i in range(2):
            pq.write_table(pa.table({
                "k": rng.integers(0, 40, n), "g": rng.integers(0, 7, n),
                "v": rng.standard_normal(n), "q": rng.integers(0, 100, n),
            }), root / f"part-{i}.parquet")
        sess = hst.Session(conf={
            hst.keys.SYSTEM_PATH: str(tmp_path / "idx"),
            hst.keys.NUM_BUCKETS: 4,
            hst.keys.CHECK_HLO_ENABLED: True,
            hst.keys.TPU_QUERY_DEVICE_MIN_ROWS: 0,
        })
        hst.set_session(sess)
        verified = {}
        try:
            hlo_lint.reset_runtime_state()
            df = sess.read_parquet(str(root))
            hs = hst.Hyperspace(sess)
            hs.create_index(df, hst.CoveringIndexConfig("nmA", ["k"], ["g", "v", "q"]))
            hs.create_index(df, hst.CoveringIndexConfig("nmB", ["k"], ["v"]))
            sess.enable_hyperspace()
            before = {f: counter("hs_check_programs_verified_total", program=f)
                      for f in hlo_lint.registered_contracts()}
            df.filter(col("k") > 3).select("v").collect()
            df.filter(col("k") > 3).agg(s=("v", "sum")).collect()
            df.filter(col("k") > 3).group_by("g").agg(s=("q", "sum")).collect()
            df.filter(col("k") > 3).group_by("v").agg(s=("q", "sum")).collect()  # a float key: the chunk family
            a = df.select("k", "v")
            b = df.select("k", "g")
            a.join(b, "k").select("v", "g").collect()
            df.select("k", "v").sort("v").limit(5).collect()
            verified = {f for f, v in before.items()
                        if counter("hs_check_programs_verified_total", program=f) > v}
            violations = [f.render() for f in hlo_lint.runtime_violations()]
        finally:
            hst.set_session(None)
            hlo_lint.set_default_enabled(False)
            hlo_lint.reset_runtime_state()
        assert violations == []
        assert {"fused-filter", "fused-agg"} <= verified, verified
        assert {"grouped-agg-keyed", "grouped-agg-chunk"} <= verified, verified
