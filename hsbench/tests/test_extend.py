"""A later PR adds a cell, a configuration, a mix, a template with its oracle
and a per-layer metric with NEW FILES and one list entry each: done here in a
temporary copy, and the new cell run in rehearsal. And: a run whose timed path
is broken underneath comes out ``correct: false``."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np

from hsbench import run
from hsbench.deployment import ROOT


def _write(path, text):
    with open(path, "w") as f:
        f.write(text)


def test_a_cell_is_added_by_new_files_and_one_list_entry_each(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "hsbench"), copy / "hsbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (copy / "hsbench").rglob("*") if p.is_file()}
    hb = copy / "hsbench"

    with open(os.path.join(ROOT, "hsbench", "configs", "tpch-sf1.json")) as f:
        config = json.load(f)
    config.update(name="tpch-tiny", source="a throwaway for this test", scale_factor=0.01,
                  indexes=[i for i in config["indexes"] if i["name"] in ("o_ck", "o_ok")])
    _write(hb / "configs" / "tpch-tiny.json", json.dumps(config))
    _write(hb / "traffic" / "cust-burst.json", json.dumps({
        "loop": "open", "arrivals": "poisson", "param_seed": 7, "key_skew_zipf_s": 0.8, "tenants": ["t"],
        "burst": {"rate_per_s": 30.0, "on_s": 0.5, "off_s": 0.5},
        "templates": [{"name": "cust_total", "share": 3}, {"name": "lk_o_orderkey", "share": 1}],
        "trace_seconds": 1.0, "trace_lead_s": 0.2}))
    _write(hb / "templates" / "cust_total.sql",
           "SELECT SUM(o_totalprice) AS total, COUNT(*) AS n FROM orders WHERE o_custkey = {key}")
    _write(hb / "templates" / "cust_total.json", json.dumps(
        {"params": {"key": {"kind": "key", "table": "orders", "column": "o_custkey"}}, "ordered": False}))
    _write(hb / "oracles" / "cust_total.py", (
        "import numpy as np\n\nCOLUMNS = {'orders': ['o_custkey', 'o_totalprice']}\n\n\n"
        "def answer(t, p):\n    o = t['orders']\n    m = o.o_custkey.to_numpy() == p['key']\n"
        "    return {'total': np.array([o.o_totalprice.to_numpy()[m].sum()]), 'n': np.array([int(m.sum())])}\n"))
    _write(hb / "layers" / "serving.requests_traced.tiny.json", json.dumps({"reader": "requests_traced"}))
    _write(hb / "layers" / "requests_traced.py",
           "def read(run, params):\n    return float(len([o for o in run.outcomes if o.root is not None])) or None\n")

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "tpch-tiny", "source": "a throwaway for this test",
                                "file": "hsbench/configs/tpch-tiny.json", "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "tiny-burst", "config": "tpch-tiny", "traffic": "cust-burst",
                                  "chips": 1, "why": "test"})
    for m in manifest["end_to_end"]:
        if m["name"] == "query_p50_ms":
            m["workloads"].append("tiny-burst")
    manifest["per_layer"].append({"name": "serving.requests_traced.tiny", "unit": "requests", "better": "higher",
                                  "source": "program_span", "layer": "serving", "moves": "query_p50_ms",
                                  "workloads": ["tiny-burst"]})
    _write(copy / "BENCHMARK.json", json.dumps(manifest))

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    for trace in ("0", "1"):
        p = subprocess.run(
            [sys.executable, "-m", "hsbench.run", "--workload", "tiny-burst", "--seed", "2400000950",
             "--seconds", "3", "--trace", trace, "--rehearse-on-cpu"],
            cwd=copy, env=env, capture_output=True, text=True, timeout=300)
        assert p.returncode == run.REHEARSAL_EXIT, p.stdout[-2000:] + p.stderr[-2000:]
        last = json.loads(p.stdout.strip().splitlines()[-1])
        assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 10
        assert last["metrics"] == {} and last["device"]["platform"] == "cpu"
        reading = "serving.requests_traced.tiny" if trace == "1" else "query_p50_ms"
        assert f"not a measurement: {reading} = " in p.stdout
    # nothing that was there has been edited
    assert all(p.read_bytes() == b for p, b in before.items())


def test_a_run_without_a_tpu_fails_and_prints_no_result():
    p = subprocess.run([sys.executable, "-m", "hsbench.run", "--workload", "sf1-lookup", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)
    assert p.returncode != 0 and "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def _args(cell):
    return run.parse(["--workload", cell, "--seed", "2400000960", "--seconds", "2",
                      "--rehearse-on-cpu", "--rehearse-sf", "0.01"])


def test_an_answer_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from hyperspace_tpu.exec.executor import Executor

    sound = Executor.execute

    def altered(self, *a, **kw):
        batch = sound(self, *a, **kw)
        for c, v in batch.items():
            v = np.asarray(v)
            if v.dtype.kind == "f" and len(v):
                batch[c] = np.concatenate([v[:1] * (1 + 1e-6), v[1:]])  # one value, one part in a million
                break
        return batch

    monkeypatch.setattr(Executor, "execute", altered)
    result, _ = run.execute(_args("sf1-lookup"))
    assert result["correct"] is False and result["failed"] == 0


def test_a_build_that_loses_a_chunk_of_rows_is_not_correct(monkeypatch):
    import pyarrow.parquet as pq

    sound = pq.write_table
    calls = {"n": 0}

    def lossy(table, where, *a, **kw):
        calls["n"] += 1
        if "indexes" in str(where) and calls["n"] % 50 == 0:
            table = table.slice(0, max(0, table.num_rows - 1))
        return sound(table, where, *a, **kw)

    monkeypatch.setattr(pq, "write_table", lossy)
    result, _ = run.execute(_args("sf10-build"))
    assert result["correct"] is False
