"""BENCHMARK.json and the data files against the benchmark's contract."""

import json
import os
import re

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    B = json.load(f)


def one_line(s, limit=200):
    return 1 <= len(s) <= limit and "\n" not in s and "\t" not in s


def test_top_level_keys_and_limits():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert B["paths"] == ["benchmark"] and B["command"][-1].startswith("benchmark/")
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert sum(w["chips"] == 4 for w in B["workloads"]) <= max(1, len(B["workloads"]) // 2)


def test_names_units_and_whys():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in B[group]]
        assert len(seen) == len(set(seen)), group
        names += seen
    metric_names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for n in names + [w["traffic"] for w in B["workloads"]]:
        assert NAME.match(n), n
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in B["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert one_line(m["layer"])
    for e in B["configs"] + B["workloads"]:
        assert one_line(e["why"]), e["name"]
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert one_line(c["source"]) and len(c["reduced"]) <= 16
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)


def test_every_cell_finds_its_files_and_reports_enough():
    configs = {c["name"]: c for c in B["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert {w["config"] for w in B["workloads"]} == set(configs)
    e2e = {m["name"]: m for m in B["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in B["workloads"]:
        c = configs[w["config"]]
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(ROOT, c["file"])) as f:
            doc = json.load(f)
        for key in c["reduced"]:
            assert key in doc and key in doc["reduced"], (c["name"], key)
        assert doc["source"] and doc["assumed"] and doc["guarantees"]
        assert os.path.isfile(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        mine = [m for m in e2e.values() if w["name"] in m.get("workloads", [w["name"]])]
        assert len(mine) >= 2, w["name"]
        assert any(w["name"] in m.get("workloads", [w["name"]]) for m in B["per_layer"])


def test_per_layer_metrics_have_readers_and_move_what_their_cells_report():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    cells = {w["name"] for w in B["workloads"]}
    for m in B["per_layer"]:
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
            assert json.load(f)["reader"]["type"]
        target = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert cell in target.get("workloads", cells), (m["name"], cell)
    # no reader without an entry: a file that nothing evaluates is dead
    listed = {m["name"] + ".json" for m in B["per_layer"]}
    assert set(os.listdir(os.path.join(BENCH, "metrics"))) == listed


def test_files_under_paths_are_named_from_name_characters():
    for dirpath, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(dirpath, f)
