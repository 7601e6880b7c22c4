"""Every cell's configuration, traffic mix and store tier is a file of its
own, found by name, and the configuration states the DAG the program
runs."""
import json
import re

import pytest

from bench import cells

BENCH = cells.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_names_and_units_keep_to_the_contract():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_are_found_by_name(name):
    cell = cells.load_cell(name)
    config = cell["config"]
    entry = {c["name"]: c for c in BENCH["configs"]}[config["name"]]
    assert all(k in config for k in entry["reduced"])
    assert cell["traffic"]["name"] == cell["workload"]["traffic"]
    assert cell["store"]["name"] == config["store"]
    assert len(cell["workload"]["why"]) <= 200
    assert {m["name"] for m in cell["end_to_end"]} >= {"setup_s"}


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_configuration_states_the_program_dag(name):
    from repro.mv import paper_workloads
    from repro.mv.workloads import filter_threshold

    entry = {c["name"]: c for c in BENCH["configs"]}[name]
    config = json.loads((cells.ROOT / entry["file"]).read_text())
    named = {w.name.split("@")[0]: w
             for w in paper_workloads(config["scale_gb"])}
    w = named[config["program_workload"]]
    assert [n.op for n in w.nodes] == [n["op"] for n in config["nodes"]]
    assert [list(n.parents) for n in w.nodes] == \
        [n["parents"] for n in config["nodes"]]
    for i, n in enumerate(config["nodes"]):
        if n["op"] == "FILTER":
            assert n["threshold"] == filter_threshold(i)
