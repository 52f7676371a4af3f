"""The served cell ``sf10-rollup`` (PR 41): its files found by name from the
manifest, the pool of eight quarters whatever
the seed, the Q15 oracle over a hand-made frame, every new per-layer metric
over an empty run, the cell itself in rehearsal, and its float32 control."""

import json
import os
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

from hsbench import deployment, layers, run as hsrun, traffic
from hsbench.deployment import ROOT
from hsbench.oracles import q15

M = deployment.manifest()
MINE = [m["name"] for m in M["per_layer"] if m.get("workloads") == ["sf10-rollup"]]
SHARED = ["dispatch.device_wait_run_ms", "dispatch.device_wait_queued_ms", "dispatch.device_wait_start_gap_ms",
          "dispatch.device_wait_tail_ms", "dispatch.device_launch_ms", "dispatch.device_dispatches_per_query",
          "device.columns_as_planes_share"]


def test_the_cell_is_found_by_name_and_is_as_issue_41_names_it():
    cell = next(w for w in M["workloads"] if w["name"] == "sf10-rollup")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("tpch-sf10-rollup", "rollup-closed", 1)
    entry = next(c for c in M["configs"] if c["name"] == "tpch-sf10-rollup")
    assert entry["reduced"] == ["tables"]
    config = deployment.load_config(entry["file"])
    report = deployment.load_config("hsbench/configs/tpch-sf10-report.json")
    for key in ("conf", "server", "limits"):
        assert config[key] == report[key]
    assert {k: v for k, v in config["guarantees"].items() if k not in ("answers", "equality")} == {
        k: v for k, v in report["guarantees"].items() if k != "answers"}
    assert "one evaluation" in config["guarantees"]["equality"]
    assert config["tables"] == ["lineitem", "supplier"] and config["scale_factor"] == 10.0
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    (index,) = config["indexes"]
    assert (index["table"], index["name"], index["indexed"]) == ("lineitem", "li_sd_sup", ["l_shipdate"])
    assert index["included"] == ["l_suppkey", "l_extendedprice", "l_discount"]
    mix = traffic.load_mix("rollup-closed")
    assert (mix["loop"], mix["clients"], mix["params_per_template"], mix["param_seed"]) == ("closed", 2, 8, 41)
    assert mix["tenants"] == ["bi", "reports"] and mix["request_timeout_s"] == 120 and mix["trace_seconds"] == 6
    assert [t["name"] for t in mix["templates"]] == ["q15"] and "rate_per_s" not in mix
    template = traffic.Template("q15")
    assert template.ordered and "with revenue0 as" in template.sql and "select max(total_revenue) from revenue0" in template.sql
    assert os.path.exists(os.path.join(ROOT, "hsbench", "oracles", "q15.py"))
    for name in MINE:
        assert os.path.exists(os.path.join(ROOT, "hsbench", "layers", f"{name}.json"))
    assert len(MINE) == 17 and all(n.endswith(".rollup") for n in MINE)
    assert "kernels.keyed_agg_roofline.rollup" in MINE and "device.keyed_agg_ms_per_query.rollup" in MINE
    assert all(m["moves"] == "queries_per_s" for m in M["per_layer"] if m["name"] in MINE)
    e2e = next(m for m in M["end_to_end"] if m["name"] == "queries_per_s")
    assert e2e["workloads"][-1] == "sf10-rollup" and e2e["bound"] == 0.2
    for name in SHARED:
        assert "sf10-rollup" in next(m for m in M["per_layer"] if m["name"] == name)["workloads"]


def test_the_pool_is_eight_quarters_whatever_the_seed():
    mix = traffic.load_mix("rollup-closed")
    templates = {"q15": traffic.Template("q15")}
    pools = []
    for seed in (1, 2_900_000_123):
        drawers = {n: traffic.ParamDrawer(t, seed, 0.0, None) for n, t in templates.items()}
        pools.append(traffic.pool(mix, templates, drawers))
    assert pools[0] == pools[1] and len(pools[0]) == 8
    for _, p in pools[0]:
        year, month, day = (int(x) for x in p["date"].split("-"))
        assert day == 1 and (1993, 1) <= (year, month) <= (1997, 10)


def test_the_oracle_over_a_hand_made_frame():
    """Three suppliers; two tie for the greatest revenue in the quarter, one row
    lies outside it, one supplier has no row at all."""
    day = lambda text: np.datetime64(text, "D")
    lineitem = pd.DataFrame({
        "l_shipdate": [day("1996-01-01"), day("1996-02-15"), day("1996-03-31"), day("1996-04-01"), day("1996-01-20")],
        "l_suppkey": np.array([2, 2, 3, 1, 1], dtype=np.int64),
        "l_extendedprice": [100.0, 300.0, 500.0, 9000.0, 10.0],
        "l_discount": [0.0, 0.5, 0.5, 0.0, 0.0],
    })
    supplier = pd.DataFrame({"s_suppkey": np.array([1, 2, 3, 4], dtype=np.int64), "s_name": ["a", "b", "c", "d"],
                             "s_address": ["w", "x", "y", "z"], "s_phone": ["1", "2", "3", "4"]})
    got = q15.answer({"lineitem": lineitem, "supplier": supplier}, {"date": "1996-01-01"})
    assert list(got) == ["s_suppkey", "s_name", "s_address", "s_phone", "total_revenue"]
    assert list(got["s_suppkey"]) == [2, 3] and list(got["s_name"]) == ["b", "c"]
    assert list(got["total_revenue"]) == [250.0, 250.0]
    later = q15.answer({"lineitem": lineitem, "supplier": supplier}, {"date": "1996-04-01"})
    assert list(later["s_suppkey"]) == [1] and list(later["total_revenue"]) == [9000.0]
    assert set(q15.COLUMNS) == {"lineitem", "supplier"}


@pytest.mark.parametrize("name", MINE + SHARED)
def test_every_metric_of_the_cell_reads_nothing_from_an_empty_run(name, monkeypatch):
    monkeypatch.setattr(hsrun, "all_counters", lambda: {})
    run = hsrun.TracedRun("TPU v5 lite")
    run.mark()
    got = layers.read_metric(name, run)
    assert got is None or isinstance(got, float)


def _rehearse(module: str, seed: int, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return subprocess.run(
        [sys.executable, "-m", module, "--workload", "sf10-rollup", "--seed", str(seed),
         "--seconds", "3", "--rehearse-on-cpu", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_the_cell_runs_in_rehearsal_and_is_correct():
    p = _rehearse("hsbench.run", 4_100_000_127, "--trace", "1")
    assert p.returncode == hsrun.REHEARSAL_EXIT, p.stdout[-2000:] + p.stderr[-2000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 8
    assert last["metrics"] == {}
    growth = json.loads(next(l for l in p.stdout.splitlines() if l.startswith("counters that grew")).split(": ", 1)[1])
    keyed = growth["hs_device_dispatches_total{program=grouped-agg-keyed}"]
    assert keyed == growth["hs_serving_completed_total{}"], "one run of the keyed program a request"
    assert not [k for k in growth if k.startswith("hs_device_fallback_total{op=agg")]
    assert not [k for k in growth if k.startswith(("hs_h2d_bytes_total", "hs_native_decode_bytes_total"))]
    for reading in ("agg.groups_per_query.rollup", "dispatch.agg_rows_on_device_share.rollup",
                    "dispatch.host_join_ms.rollup", "link.d2h_bytes_per_query.rollup", "serving.latency_p50_ms.rollup"):
        assert f"not a measurement: {reading} = " in p.stdout


def test_the_float32_control_comes_out_not_correct():
    p = _rehearse("hsbench.control", 4_100_000_128)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    line = next(l for l in p.stdout.splitlines() if l.startswith("control: "))
    control = json.loads(line.split(": ", 1)[1])
    assert control["sound"] is True and control["float32_aggregates"]["correct"] is False
