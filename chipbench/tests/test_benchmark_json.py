"""BENCHMARK.json holds to the format the harness and its checker read:
names, units, cross references, and the files found by name."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert bench["command"][1].startswith("chipbench/")
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_and_units(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [x["name"] for x in metrics + bench["workloads"]
             + bench["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "layer", "moves", "workloads"}
    for c in bench["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200


def test_files_found_by_name(bench):
    used = {w["config"] for w in bench["workloads"]}
    files = {}
    for c in bench["configs"]:
        assert c["file"].startswith("chipbench/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            files[c["name"]] = json.load(f)
    # A composite configuration names its parts, each a configuration of
    # its own with a file of its own and no parts.
    parts = {n: cfg.get("parts", [n]) for n, cfg in files.items()}
    for name, names in parts.items():
        assert all(p in files and "parts" not in files[p] for p in names), \
            name
    assert used == set(files)
    for w in bench["workloads"]:
        with open(os.path.join(ROOT, "chipbench", "traffic",
                               w["traffic"] + ".json")) as f:
            mix = json.load(f)
        assert sorted(mix.get("parts", parts[w["config"]])) == \
            sorted(parts[w["config"]]), w["name"]
        assert w["chips"] == 1
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "chipbench", "metrics", m["name"] + ".py"))


def test_every_cell_reports_what_its_metrics_move(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e["setup_s"]
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m["workloads"]) <= set(moved), m["name"]
    for c in cells:
        reported = [n for n, m in e2e.items() if c in m.get("workloads", cells)]
        assert "setup_s" in reported and len(reported) >= 2, c
        assert any(c in m["workloads"] for m in bench["per_layer"]), c
    layers = {}
    for m in bench["per_layer"]:
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        layers.setdefault(m["layer"], []).append(m["name"])
    assert "kernels" in layers and any("mfu" in n for n in
                                       layers["model inference"])
