import json
import os
import re

from hsbench import deployment, layers, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
M = deployment.manifest()


def test_names_and_units_keep_to_the_allowed_characters():
    names = ([c["name"] for c in M["configs"]] + [w["name"] for w in M["workloads"]]
             + [w["traffic"] for w in M["workloads"]]
             + [m["name"] for m in M["end_to_end"] + M["per_layer"]])
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    metrics = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    assert all(UNIT.match(m["unit"]) for m in M["end_to_end"] + M["per_layer"])
    assert all(m["better"] in ("lower", "higher") for m in M["end_to_end"] + M["per_layer"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in M["end_to_end"])
    assert all(len(w["why"]) <= 200 for w in M["workloads"])
    assert 1 <= M["run_seconds"] <= 51
    assert len(json.dumps(M)) < 64 * 1024


def _reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_every_layer_metric_moves_a_metric_each_of_its_cells_reports():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    cells = [w["name"] for w in M["workloads"]]
    for m in M["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        for cell in m.get("workloads", cells):
            assert cell in cells and _reports(e2e[m["moves"]], cell), (m["name"], cell)
    for cell in cells:
        assert _reports(e2e["setup_s"], cell)
        assert sum(1 for m in M["end_to_end"] if _reports(m, cell)) >= 2
        assert any(_reports(m, cell) for m in M["per_layer"])


def test_every_configuration_has_a_cell_and_its_own_file_under_paths():
    used = {w["config"] for w in M["workloads"]}
    files = [c["file"] for c in M["configs"]]
    assert {c["name"] for c in M["configs"]} == used and len(set(files)) == len(files)
    for c in M["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in M["paths"]))
        conf = deployment.load_config(c["file"])
        assert conf["reduced"] == c["reduced"] and conf["source"] == c["source"]
        assert conf["guarantees"] and conf["limits"] and "assumed" in conf


def test_at_most_half_of_the_cells_ask_for_four_chips():
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert all(w["chips"] in (1, 4) for w in M["workloads"])
    assert four <= max(1, len(M["workloads"]) // 2)


def test_every_name_finds_its_files():
    for w in M["workloads"]:
        mix = traffic.load_mix(w["traffic"])
        assert mix["loop"] in ("open", "closed", "build")
        assert mix["loop"] == "build" or "param_seed" in mix  # every seed asks a served cell the same requests
        for t in mix.get("templates", []):
            traffic.Template(t["name"])
            assert os.path.exists(os.path.join(deployment.HERE, "oracles", f"{t['name']}.py"))
    for m in M["per_layer"]:
        with open(os.path.join(layers.HERE, f"{m['name']}.json")) as f:
            reader = json.load(f)["reader"]
        assert os.path.exists(os.path.join(layers.HERE, f"{reader}.py"))
