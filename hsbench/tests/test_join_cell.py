"""The served cell ``sf10-join`` (PR 43): its files found by name from the
manifest, the pool of eight (mode pair, year) sets whatever the seed, the Q12
oracle over a hand-made frame and its bite (one matching ``orders`` row
dropped is an exact mismatch), every metric of the cell over an empty run, and
the cell itself in rehearsal."""

import json
import os
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

from hsbench import check, deployment, layers, run as hsrun, traffic
from hsbench.deployment import ROOT
from hsbench.oracles import q12

M = deployment.manifest()
MINE = [m["name"] for m in M["per_layer"] if m.get("workloads") == ["sf10-join"]]
SHARED = ["dispatch.device_wait_run_ms", "dispatch.device_wait_queued_ms", "dispatch.device_wait_start_gap_ms",
          "dispatch.device_wait_tail_ms", "dispatch.device_launch_ms", "dispatch.device_dispatches_per_query",
          "device.columns_as_planes_share"]


def test_the_cell_is_found_by_name_and_is_as_issue_43_names_it():
    cell = next(w for w in M["workloads"] if w["name"] == "sf10-join")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("tpch-sf10-join", "join-closed", 1)
    assert M["workloads"][-1] == cell and len(cell["why"]) <= 200
    entry = next(c for c in M["configs"] if c["name"] == "tpch-sf10-join")
    assert entry["reduced"] == ["tables"] and M["configs"][-1] == entry
    config = deployment.load_config(entry["file"])
    rollup = deployment.load_config("hsbench/configs/tpch-sf10-rollup.json")
    for key in ("conf", "limits"):
        assert config[key] == rollup[key]
    # the server is sf10-rollup's, and asserts the program family the deployment is sized for: a
    # build without it (this PR's parent) ends at QueryServer() where it would take 7 minutes a run
    assert config["server"] == dict(rollup["server"], requires=["join-agg-resident"])
    assert {k: v for k, v in config["guarantees"].items() if k not in ("answers", "version")} == {
        k: v for k, v in rollup["guarantees"].items() if k not in ("answers", "version", "equality")}
    assert "table" in config["guarantees"]["version"] and "equality" not in config["guarantees"]
    assert config["tables"] == ["orders", "lineitem"] and config["scale_factor"] == 10.0 and config["architecture"] is None
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200 and "2.4.12" in entry["source"]
    by_name = {i["name"]: i for i in config["indexes"]}
    assert (by_name["li_ok_ship"]["table"], by_name["li_ok_ship"]["indexed"]) == ("lineitem", ["l_orderkey"])
    assert by_name["li_ok_ship"]["included"] == ["l_shipmode", "l_shipdate", "l_commitdate", "l_receiptdate"]
    assert (by_name["o_ok_pri"]["table"], by_name["o_ok_pri"]["indexed"], by_name["o_ok_pri"]["included"]) == (
        "orders", ["o_orderkey"], ["o_orderpriority"])
    assert config["conf"]["hyperspace.index.numBuckets"] == 200, "equal numBuckets for both: JoinIndexRule's shape"
    mix = traffic.load_mix("join-closed")
    assert (mix["loop"], mix["clients"], mix["params_per_template"], mix["param_seed"]) == ("closed", 2, 8, 43)
    assert mix["tenants"] == ["bi", "reports"] and mix["request_timeout_s"] == 120 and mix["trace_seconds"] == 6
    assert [t["name"] for t in mix["templates"]] == ["q12"] and "rate_per_s" not in mix and mix["key_skew_zipf_s"] == 0.0
    template = traffic.Template("q12")
    assert template.ordered and "o_orderkey = l_orderkey" in template.sql and "case when o_orderpriority" in template.sql
    for name in MINE:
        assert os.path.exists(os.path.join(ROOT, "hsbench", "layers", f"{name}.json"))
    assert len(MINE) == 17 and all(n.endswith(".join") for n in MINE)
    for name in ("device.join_agg_ms_per_query.join", "kernels.join_agg_roofline.join", "join.probe_matched_share.join",
                 "join.build_table_hit_share.join"):
        assert name in MINE
    assert all(m["moves"] == "queries_per_s" for m in M["per_layer"] if m["name"] in MINE)
    e2e = next(m for m in M["end_to_end"] if m["name"] == "queries_per_s")
    assert e2e["workloads"][-1] == "sf10-join" and e2e["bound"] == 0.2
    for name in SHARED:
        assert next(m for m in M["per_layer"] if m["name"] == name)["workloads"][-1] == "sf10-join"


def test_the_pool_is_the_same_eight_sets_whatever_the_seed():
    mix = traffic.load_mix("join-closed")
    templates = {"q12": traffic.Template("q12")}
    pools = []
    for seed in (1, 2_900_000_123):
        drawers = {n: traffic.ParamDrawer(t, seed, 0.0, None) for n, t in templates.items()}
        pools.append(traffic.pool(mix, templates, drawers))
    assert pools[0] == pools[1] and len(pools[0]) == 8
    for _, p in pools[0]:
        assert p["mode1"] != p["mode2"] and p["date"].endswith("-01-01") and 1993 <= int(p["date"][:4]) <= 1997


def _frames():
    """Five orders, two of them urgent or high; line items in and out of the
    year, late and not, of the two modes and of a third; one order (5) with no
    line, one line (order 9) with no order."""
    day = lambda text: np.datetime64(text, "D")
    orders = pd.DataFrame({"o_orderkey": np.array([1, 2, 3, 4, 5], dtype=np.int64),
                           "o_orderpriority": ["1-URGENT", "3-MEDIUM", "2-HIGH", "5-LOW", "1-URGENT"]})
    lines = [  # order, mode, ship, commit, receipt
        (1, "MAIL", "1994-02-01", "1994-02-10", "1994-02-12"),  # counts: high
        (2, "MAIL", "1994-03-01", "1994-03-10", "1994-03-15"),  # counts: low
        (3, "SHIP", "1994-05-01", "1994-05-09", "1994-05-10"),  # counts: high
        (4, "SHIP", "1994-06-01", "1994-06-09", "1994-06-11"),  # counts: low
        (4, "SHIP", "1994-06-01", "1994-06-09", "1994-06-08"),  # received before the commit date
        (2, "MAIL", "1994-03-12", "1994-03-10", "1994-03-15"),  # shipped after the commit date
        (1, "MAIL", "1995-02-01", "1995-02-10", "1995-02-12"),  # another year
        (3, "AIR", "1994-05-01", "1994-05-09", "1994-05-10"),  # another mode
        (9, "MAIL", "1994-02-01", "1994-02-10", "1994-02-12"),  # no such order
    ]
    lineitem = pd.DataFrame({
        "l_orderkey": np.array([l[0] for l in lines], dtype=np.int64), "l_shipmode": [l[1] for l in lines],
        "l_shipdate": [day(l[2]) for l in lines], "l_commitdate": [day(l[3]) for l in lines],
        "l_receiptdate": [day(l[4]) for l in lines]})
    return {"orders": orders, "lineitem": lineitem}


PARAMS = {"mode1": "MAIL", "mode2": "SHIP", "date": "1994-01-01"}


def test_the_oracle_over_a_hand_made_frame():
    got = q12.answer(_frames(), PARAMS)
    assert list(got) == ["l_shipmode", "high_line_count", "low_line_count"]
    assert list(got["l_shipmode"]) == ["MAIL", "SHIP"]
    assert list(got["high_line_count"]) == [1, 1] and list(got["low_line_count"]) == [1, 1]
    assert set(q12.COLUMNS) == {"orders", "lineitem"}


def test_the_oracle_bites_one_matching_orders_row_dropped_is_an_exact_mismatch():
    """A join that loses a build row (a table that misses a key, a match
    dropped) answers with a smaller count: the check's exact compare says so."""
    frames = _frames()
    want = q12.answer(frames, PARAMS)
    assert check.compare_answer(q12.answer(frames, PARAMS), want, ordered=True) == (0, 0.0)
    lossy = dict(frames, orders=frames["orders"][frames["orders"].o_orderkey != 2])
    wrong, gap = check.compare_answer(q12.answer(lossy, PARAMS), want, ordered=True)
    assert wrong >= 1 and gap == 0.0, "integers and strings: a mismatch is exact, no float gap stands in for it"
    unmatched = dict(frames, orders=frames["orders"][frames["orders"].o_orderkey != 5])  # order 5 has no line
    assert check.compare_answer(q12.answer(unmatched, PARAMS), want, ordered=True) == (0, 0.0)


@pytest.mark.parametrize("name", MINE + SHARED)
def test_every_metric_of_the_cell_reads_nothing_from_an_empty_run(name, monkeypatch):
    monkeypatch.setattr(hsrun, "all_counters", lambda: {})
    run = hsrun.TracedRun("TPU v5 lite")
    run.mark()
    got = layers.read_metric(name, run)
    assert got is None or isinstance(got, float)


def test_the_cell_runs_in_rehearsal_and_is_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    p = subprocess.run(
        [sys.executable, "-m", "hsbench.run", "--workload", "sf10-join", "--seed", "4300000127",
         "--seconds", "3", "--rehearse-on-cpu", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == hsrun.REHEARSAL_EXIT, p.stdout[-2000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 8
    assert last["metrics"] == {}
    growth = json.loads(next(l for l in p.stdout.splitlines() if l.startswith("counters that grew")).split(": ", 1)[1])
    launches = growth["hs_device_dispatches_total{program=join-agg-resident}"]
    assert launches == growth["hs_serving_completed_total{}"], "one run of the program a request"
    assert growth["hs_join_build_table_total{result=hit}"] == launches and "hs_join_build_table_total{result=built}" not in growth
    assert growth["hs_join_probe_rows_total{kind=matched}"] == growth["hs_join_probe_rows_total{kind=selected}"] > 0
    assert not [k for k in growth if k.startswith("hs_device_fallback_total")]
    assert not [k for k in growth if k.startswith(("hs_h2d_bytes_total", "hs_native_decode_bytes_total", "hs_agg_rows_total{path=host"))]
    assert not [k for k in growth if "program=" in k and "join-agg-resident" not in k], "no other program in the window"
    for reading in ("join.probe_matched_share.join", "join.build_table_hit_share.join", "dispatch.agg_rows_on_device_share.join",
                    "link.d2h_bytes_per_query.join", "serving.latency_p50_ms.join"):
        assert f"not a measurement: {reading} = " in p.stdout
