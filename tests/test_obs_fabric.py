"""Distributed observability across the serving fabric: trace-context
propagation over FrontDoor hops, cross-process span stitching (one
end-to-end tree per routed request, per-process attribution, valid Chrome
export), byte-identical wire format when disabled, federated profile/SLO
merging with its documented error model, per-node staleness gauges, build
identity in every exposition, and device-program timing hooks."""

import json
import os
import threading
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import hyperspace_tpu as hst
from hyperspace_tpu.fabric import FrontDoor
from hyperspace_tpu.fabric.frontdoor import (
    WorkerEndpoint,
    WorkerError,
    merge_prometheus_texts,
)
from hyperspace_tpu.obs import spans
from hyperspace_tpu.obs.history import ProfileHistory, merge_history_snapshots
from hyperspace_tpu.obs.metrics import REGISTRY
from hyperspace_tpu.serving import QueryServer
from hyperspace_tpu.version import __version__
from test_obs import _validate_chrome

pytestmark = [pytest.mark.obs, pytest.mark.fabric]

N_THREADS = 8
REQS_PER_THREAD = 3


@pytest.fixture()
def traced_sess(tmp_path):
    """A small table + a session with tracing AND fabric stitching on."""
    n = 400
    d = tmp_path / "t"
    d.mkdir()
    pq.write_table(
        pa.table(
            {
                "c1": np.arange(n, dtype=np.int64),
                "m": np.arange(n, dtype=np.int64) % 3,
            }
        ),
        str(d / "part-0.parquet"),
    )
    sysp = tmp_path / "_indexes"
    sysp.mkdir()
    sess = hst.Session(
        conf={
            hst.keys.SYSTEM_PATH: str(sysp),
            hst.keys.NUM_BUCKETS: 4,
            hst.keys.OBS_TRACING_ENABLED: True,
            hst.keys.OBS_FABRIC_STITCH_ENABLED: True,
            hst.keys.OBS_PROFILE_HISTORY: 64,
        }
    )
    sess.enable_hyperspace()
    df = sess.read_parquet(str(d))
    df.create_or_replace_temp_view("t")
    sess.test_dataframe = df  # for tests that need to index the table
    return sess


# --- trace context (wire-format units) ---------------------------------------


class TestTraceContext:
    def test_traceparent_round_trip(self):
        ctx = spans.TraceContext.new()
        assert len(ctx.trace_id) == 32 and len(ctx.span_id) == 16
        back = spans.parse_traceparent(ctx.to_traceparent())
        assert back is not None
        assert back.trace_id == ctx.trace_id and back.span_id == ctx.span_id
        assert back.sampled

    def test_child_keeps_trace_id_fresh_span_id(self):
        ctx = spans.TraceContext.new()
        hop = ctx.child()
        assert hop.trace_id == ctx.trace_id
        assert hop.span_id != ctx.span_id

    @pytest.mark.parametrize(
        "header",
        [
            None,
            "",
            "garbage",
            "00-deadbeef-cafe-01",  # bad lengths
            "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # all-zero trace id
            "00-" + "1" * 32 + "-" + "0" * 16 + "-01",  # all-zero span id
            "00-" + "x" * 32 + "-" + "1" * 16 + "-01",  # non-hex
        ],
    )
    def test_malformed_traceparent_degrades_to_none(self, header):
        assert spans.parse_traceparent(header) is None

    def test_wire_round_trip_and_budgets(self):
        root = spans.start_trace("request", cat="query", max_spans=1000)
        with spans.attach(root):
            for i in range(6):
                with spans.span(f"step-{i}", cat="exec"):
                    pass
        root.finish()

        wire = spans.to_wire(root)
        rebuilt = spans.from_wire(wire, pid=4242)
        names = {sp.name for sp in rebuilt.walk()}
        assert names == {"request"} | {f"step-{i}" for i in range(6)}
        assert all(sp.pid == 4242 for sp in rebuilt.walk())

        # span budget: tree-prefix truncation, dropped count reported
        small = spans.to_wire(root, max_spans=3)
        assert small["droppedSpans"] == 4
        assert sum(1 for _ in spans.from_wire(small).walk()) == 3

        # byte budget: degrade to root-only, flagged
        tiny = spans.to_wire(root, max_bytes=10)
        assert tiny["truncated"] is True
        assert sum(1 for _ in spans.from_wire(tiny).walk()) == 1


# --- stitched routing --------------------------------------------------------


class TestStitchedRouting:
    def test_single_request_yields_one_stitched_tree(self, traced_sess):
        with QueryServer(traced_sess, workers=1, name="qsA") as a, QueryServer(
            traced_sess, workers=1, name="qsB"
        ) as b:
            with WorkerEndpoint(a) as ea, WorkerEndpoint(b) as eb:
                fd = FrontDoor([ea.url, eb.url], conf=traced_sess.conf)
                res = fd.query("SELECT m FROM t WHERE c1 >= 0", tenant="alice")
                assert sorted(np.unique(res["m"]).tolist()) == [0, 1, 2]
                prof = fd.last_query_profile()

        root = prof.root
        assert root.name == "frontdoor-request"
        assert root.attrs["worker"] is not None
        assert root.attrs["retries"] == 0 and root.attrs["hedged"] is False
        routes = [c for c in root.children if c.name == "route"]
        assert len(routes) == 1
        assert routes[0].attrs["outcome"] == "ok"

        # the worker's whole tree hangs under the route attempt, parented by
        # the hop context: route.span_id == worker root.parent_span_id, one
        # trace id end to end
        grafted = [c for c in routes[0].children if c.name == "request"]
        assert len(grafted) == 1
        wroot = grafted[0]
        assert wroot.attrs["trace_id"] == root.attrs["trace_id"]
        assert wroot.attrs["parent_span_id"] == routes[0].attrs["span_id"]
        assert wroot.pid == os.getpid()  # in-process endpoint: same pid
        names = {sp.name for sp in wroot.walk()}
        assert names & {"resolve-plan", "resolve", "parse"}
        assert names & {"execute", "execute-shared-scan"}
        # the stitched copy lives in the ROUTER's trace budget
        assert all(sp.trace is root.trace for sp in root.walk())

        _validate_chrome(prof.chrome_trace())

    def test_concurrent_storm_one_disjoint_stitched_tree_each(self, traced_sess):
        with QueryServer(traced_sess, workers=4, name="qsA") as a, QueryServer(
            traced_sess, workers=4, name="qsB"
        ) as b:
            with WorkerEndpoint(a) as ea, WorkerEndpoint(b) as eb:
                fd = FrontDoor([ea.url, eb.url], conf=traced_sess.conf)
                errors = []
                start = threading.Barrier(N_THREADS)

                def client(k):
                    try:
                        start.wait()
                        for j in range(REQS_PER_THREAD):
                            fd.query(
                                f"SELECT m FROM t WHERE c1 >= {k + j}",
                                tenant=f"tenant-{k}",
                            )
                    except Exception as exc:
                        errors.append(exc)

                threads = [
                    threading.Thread(target=client, args=(k,))
                    for k in range(N_THREADS)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                assert not errors
                profiles = fd.last_profiles()

        assert len(profiles) == N_THREADS * REQS_PER_THREAD
        trace_ids = set()
        seen_spans = set()
        for prof in profiles:
            root = prof.root
            assert root.name == "frontdoor-request"
            grafted = [
                c for r in root.children if r.name == "route"
                for c in r.children if c.name == "request"
            ]
            # exactly one stitched worker tree per routed request
            assert len(grafted) == 1
            assert grafted[0].attrs["trace_id"] == root.attrs["trace_id"]
            trace_ids.add(root.attrs["trace_id"])
            ids = {id(sp) for sp in root.walk()}
            assert not (ids & seen_spans)  # no cross-request span leakage
            seen_spans |= ids
        assert len(trace_ids) == len(profiles)  # disjoint trace ids

    def test_worker_failure_yields_router_error_span_no_leak(self, traced_sess):
        with QueryServer(traced_sess, workers=1, name="qsA") as a:
            with WorkerEndpoint(a) as ea:
                fd = FrontDoor([ea.url], conf=traced_sess.conf)
                with pytest.raises(WorkerError):
                    fd.query("SELECT nope FROM missing_table")
                assert spans.current_span() is None  # nothing left attached
                prof = fd.last_query_profile()

        assert prof.error == "WorkerError"
        routes = [c for c in prof.root.children if c.name == "route"]
        assert len(routes) == 1
        assert routes[0].attrs["outcome"] == "error"
        assert routes[0].attrs["error"] == "WorkerError"
        # no attempt succeeded, so no worker is credited with the answer
        assert prof.root.attrs["worker"] is None

    def test_chrome_export_attributes_remote_pids(self):
        root = spans.start_trace("frontdoor-request", cat="fabric")
        with spans.attach(root):
            with spans.span("route", cat="fabric") as att:
                remote = spans.start_trace("request", cat="query", server="qsZ")
                with spans.attach(remote):
                    with spans.span("execute", cat="serving"):
                        pass
                remote.finish()
                wire = spans.to_wire(remote)
                wire["pid"] = 99_999
                wire["server"] = "qsZ"
                spans.graft_remote(att, wire, pid=99_999)
        root.finish()

        doc = spans.to_chrome_trace(root)
        _validate_chrome(doc)
        pids = {e["pid"] for e in doc["traceEvents"] if e.get("ph") == "X"}
        assert os.getpid() in pids and 99_999 in pids
        process_names = {
            e["pid"]: e["args"]["name"]
            for e in doc["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"
        }
        assert process_names[99_999] == "hyperspace_tpu worker qsZ"


# --- byte-identical wire when disabled ---------------------------------------


class _RecordingWorker:
    """A stub /query HTTP server that records request headers verbatim."""

    def __init__(self):
        self.headers = []
        recorder = self

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def do_GET(self):
                recorder.headers.append(dict(self.headers))
                body = json.dumps({"columns": {"m": [0, 1, 2]}}).encode("utf-8")
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), _Handler)
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        )
        self._thread.start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        self._thread.join(timeout=5)

    def header_names(self, i=-1):
        return {k.lower() for k in self.headers[i]}


class TestDisabledIsByteIdentical:
    def test_untraced_frontdoor_sends_no_trace_headers(self, session):
        stub = _RecordingWorker()
        try:
            fd = FrontDoor([stub.url])  # no conf: untraced legacy router
            fd.query("SELECT 1")
            assert "traceparent" not in stub.header_names()
            assert "x-hs-stitch" not in stub.header_names()
        finally:
            stub.close()

    def test_propagate_off_sends_no_trace_headers(self, session):
        session.conf.set(hst.keys.OBS_TRACING_ENABLED, True)
        session.conf.set(hst.keys.OBS_FABRIC_PROPAGATE, False)
        stub = _RecordingWorker()
        try:
            fd = FrontDoor([stub.url], conf=session.conf)
            fd.query("SELECT 1")
            assert "traceparent" not in stub.header_names()
            assert "x-hs-stitch" not in stub.header_names()
        finally:
            stub.close()
            session.conf.set(hst.keys.OBS_TRACING_ENABLED, False)
            session.conf.set(hst.keys.OBS_FABRIC_PROPAGATE, True)

    def test_propagation_on_stitch_off_sends_only_traceparent(self, session):
        session.conf.set(hst.keys.OBS_TRACING_ENABLED, True)
        stub = _RecordingWorker()
        try:
            fd = FrontDoor([stub.url], conf=session.conf)
            fd.query("SELECT 1")
            assert "traceparent" in stub.header_names()
            assert "x-hs-stitch" not in stub.header_names()
        finally:
            stub.close()
            session.conf.set(hst.keys.OBS_TRACING_ENABLED, False)

    def test_response_without_header_carries_no_trace_key(self, traced_sess):
        # even on a tracing+stitching worker, a request without the
        # x-hs-stitch header gets the exact legacy body shape
        with QueryServer(traced_sess, workers=1, name="qsA") as srv:
            with WorkerEndpoint(srv) as ep:
                with urllib.request.urlopen(
                    f"{ep.url}/query?sql=SELECT%20m%20FROM%20t%20WHERE%20c1%20%3E%3D%200",
                    timeout=30,
                ) as resp:
                    body = json.loads(resp.read().decode("utf-8"))
        assert set(body) == {"columns"}


# --- federation --------------------------------------------------------------


class TestFederation:
    def test_merge_history_snapshots_error_model(self):
        a, b = ProfileHistory(), ProfileHistory()
        for _ in range(100):
            a.record("fp1", 0.010, rows=10)
            b.record("fp1", 0.030, rows=30)
        b.record("fp2", 0.5)
        merged = merge_history_snapshots([a.snapshot(), b.snapshot()])

        assert merged["federated"] is True
        assert merged["fingerprints"] == 2
        by_fp = {e["fingerprint"]: e for e in merged["entries"]}
        lat = by_fp["fp1"]["latencySeconds"]
        # exact: counts, extrema; n-weighted exact: mean
        assert by_fp["fp1"]["count"] == 200
        assert lat["min"] == pytest.approx(0.010)
        assert lat["max"] == pytest.approx(0.030)
        assert lat["mean"] == pytest.approx(0.020, rel=0.05)
        # approximate: federated p50 is the n-weighted average of per-node
        # P² estimates — bounded by the cross-node spread
        assert 0.010 <= lat["p50"] <= 0.030
        assert by_fp["fp2"]["count"] == 1

    def test_frontdoor_profilez_and_statusz_federation(self, traced_sess):
        with QueryServer(traced_sess, workers=1, name="qsA") as a, QueryServer(
            traced_sess, workers=1, name="qsB"
        ) as b:
            with WorkerEndpoint(a) as ea, WorkerEndpoint(b) as eb:
                fd = FrontDoor([ea.url, eb.url], conf=traced_sess.conf)
                for t in range(6):
                    fd.query("SELECT m FROM t WHERE c1 >= 0", tenant=f"t-{t}")
                fed = fd.profilez()
                statusz = fd.federated_statusz()

        assert fed["federated"] is True and fed["fingerprints"] >= 1
        assert sum(e["count"] for e in fed["entries"]) == 6
        assert set(fed["workers"]) == set(fd.worker_ids)
        assert all(w is not None for w in fed["workers"].values())

        assert set(statusz["workers"]) == set(fd.worker_ids)
        tenants = statusz["slo"]["tenants"]
        assert sum(t["good"] + t["bad"] for t in tenants.values()) == 6
        assert all(t["compliance"] is not None for t in tenants.values())


# --- identity, staleness gauges, flight route info ---------------------------


class TestFleetIdentity:
    def test_build_info_and_commit_seq_in_exposition(self, session):
        with QueryServer(session, workers=1, name="qsBld") as srv:
            text = srv.prometheus_text()
        assert "hs_build_info" in text
        # the registry is shared, so pick THIS server's line
        line = next(
            l
            for l in text.splitlines()
            if l.startswith("hs_build_info{") and 'server="qsBld"' in l
        )
        assert f'version="{__version__}"' in line
        assert 'node="' in line
        assert line.endswith(" 1.0") or line.endswith(" 1")

    def test_commit_seq_exported_only_when_fabric_on(self, tmp_system_path):
        sess = hst.Session(
            conf={
                hst.keys.SYSTEM_PATH: tmp_system_path,
                hst.keys.FABRIC_ENABLED: True,
                hst.keys.FABRIC_NODE_ID: "nodeSeq",
                hst.keys.FABRIC_WATCHER_ENABLED: False,
            }
        )
        with QueryServer(sess, workers=1, name="qsSeq") as srv:
            text = srv.prometheus_text()
        line = next(
            l
            for l in text.splitlines()
            if l.startswith("hs_fabric_commit_seq{") and 'server="qsSeq"' in l
        )
        assert 'node="nodeSeq"' in line

    def test_merged_exposition_one_header_per_family(self, session):
        with QueryServer(session, workers=1, name="qsM1") as s1, QueryServer(
            session, workers=1, name="qsM2"
        ) as s2:
            merged = merge_prometheus_texts(
                [s1.prometheus_text(), s2.prometheus_text()]
            )
        assert merged.count("# HELP hs_build_info ") == 1
        assert merged.count("# TYPE hs_build_info ") == 1
        assert merged.count('server="qsM1"') > 0
        assert merged.count('server="qsM2"') > 0

    def test_watcher_staleness_gauges(self, tmp_system_path):
        from hyperspace_tpu.fabric.watcher import CommitWatcher

        sess = hst.Session(conf={hst.keys.SYSTEM_PATH: tmp_system_path})
        w = CommitWatcher(sess, node_id="nodeT", interval=3600.0)
        poll_ts = REGISTRY.gauge(
            "hs_fabric_watcher_last_poll_seconds", server="nodeT"
        )
        assert poll_ts.value == -1.0  # never polled
        w.poll_once()
        # a stable unixtime (age is computed scraper-side), not a live age
        import time

        assert abs(time.time() - poll_ts.value) < 60.0
        lag = REGISTRY.gauge("hs_fabric_commit_lag_seconds", server="nodeT")
        assert lag.value == 0.0  # nothing left to replay == caught up

    def test_flight_recorder_captures_route_outcomes(self, traced_sess):
        traced_sess.conf.set(hst.keys.OBS_SLOW_QUERY_MS, 0.001)
        with QueryServer(traced_sess, workers=1, name="qsA") as a:
            with WorkerEndpoint(a) as ea:
                fd = FrontDoor([ea.url], conf=traced_sess.conf)
                fd.query("SELECT m FROM t WHERE c1 >= 0")
                entries = fd.last_slow_queries()
        assert entries, "every request is slower than 1 microsecond"
        j = entries[-1].to_json()
        assert j["route"] == {
            "retries": 0,
            "hedged": False,
            "worker": fd.worker_ids[0],
        }
        # the captured profile is the stitched end-to-end tree
        assert entries[-1].profile is not None
        assert any(
            sp.name == "request" for sp in entries[-1].profile.root.walk()
        )


# --- device-program hooks: compile seconds, the launch span, device-wait -------


class TestDeviceProgramTiming:
    def test_observe_program_metrics_and_span_event(self):
        import time

        from hyperspace_tpu.exec.device import _note_compile, _observe_program, launch

        family = f"test-family-{os.getpid()}"
        sig = ("unit", (7, 3))
        assert _note_compile(family, sig) is True  # first sight compiles
        assert _note_compile(family, sig) is False

        root = spans.start_trace("request", cat="query")
        with spans.attach(root):
            t0 = time.perf_counter()
            for first_seen in (True, False):
                with launch(family):
                    pass
                _observe_program(family, first_seen, t0)
        root.finish()

        total = REGISTRY.counter("hs_device_compile_seconds_total", program=family)
        first = total.value
        assert first > 0.0  # the first-seen call's wall, compile-dominated
        with spans.attach(root):
            with launch(family):
                pass
            _observe_program(family, False, t0)
        assert total.value == first  # a cached signature adds nothing
        # the device-program event went (ISSUE 39): the device-launch span
        # says the same, with the host time of the dispatch call
        assert not [ev for sp in root.walk() for ev in (sp.events or [])]
        launches = root.find("device-launch")
        assert [sp.attrs["program"] for sp in launches] == [family] * 3
        assert all(sp.cat == "device" and sp.t1 is not None for sp in launches)
        # the host-clock histogram this hook used to feed is gone
        assert "hs_device_program_seconds" not in REGISTRY.snapshot()

    def test_fused_programs_observed_end_to_end(self, traced_sess):
        # the device filter only engages over index/file scans — give the
        # optimizer a covering index so the predicate runs as a device program
        hst.Hyperspace(traced_sess).create_index(
            traced_sess.test_dataframe, hst.CoveringIndexConfig("obsFab", ["c1"], ["m"])
        )
        down = REGISTRY.counter("hs_d2h_bytes_total", site="filter-mask")
        base = down.value
        traced_sess.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 0)
        try:
            with spans.trace("q") as root:
                res = traced_sess.sql(
                    "SELECT m FROM t WHERE c1 > 10 AND c1 < 300"
                ).collect()
        finally:
            traced_sess.conf.set(hst.keys.TPU_QUERY_DEVICE_MIN_ROWS, 1 << 40)
        assert len(res["m"]) > 0
        # what replaced the histogram: the host's wait for the program is a
        # device-wait span naming the family, under the tier that asked for it
        waits = [sp for sp in root.walk() if sp.name == "device-wait"]
        assert [sp.attrs["program"] for sp in waits] == ["fused-filter"]
        assert waits[0].cat == "device" and waits[0].t1 is not None
        mask_spans = root.find("filter-mask")
        assert len(mask_spans) == 1 and waits[0] in list(mask_spans[0].walk())
        assert down.value > base  # and the mask's bytes crossed the link
