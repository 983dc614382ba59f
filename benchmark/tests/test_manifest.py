"""BENCHMARK.json and the data files keep to the contract's limits."""

import json
import os
import re

import pytest

import readers

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return load("BENCHMARK.json")


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["paths"]) <= 16 and all(PATH.match(p) for p in manifest["paths"])
    assert len(manifest["command"]) <= 32 and all(line(w) for w in manifest["command"])
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_units_and_lines(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), group, entry["name"]))
    metric_names = [n for is_metric, _g, n in names if is_metric]
    assert len(metric_names) == len(set(metric_names))
    for group in ("configs", "workloads"):
        own = [e["name"] for e in manifest[group]]
        assert len(own) == len(set(own))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert line(m["layer"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_configs_and_cells(manifest):
    under = tuple(p.rstrip("/") + "/" for p in manifest["paths"])
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert line(c["source"]) and line(c["why"])
        assert c["file"].startswith(under) and PATH.match(c["file"])
        data = load(c["file"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in data["reduced"], key
        for key in ("vdaf", "guarantees", "assumed", "job_creator", "job_driver", "device_executor"):
            assert key in data, (c["name"], key)
    used = set()
    pairs = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        traffic = load("benchmark", "traffic", w["traffic"] + ".json")
        assert traffic["arrivals"] == "poisson"
        assert traffic["rate"] > 0 and traffic["lead_in_s"] >= 0
    assert used == {c["name"] for c in manifest["configs"]}
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 2)


def test_every_metric_has_a_reader_file_and_moves_what_its_cells_report(manifest):
    cells = [w["name"] for w in manifest["workloads"]]

    def reported_in(metric):
        return set(metric.get("workloads", cells))

    end = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in end and "workloads" not in end["setup_s"]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        data = load("benchmark", "metrics", m["name"] + ".json")
        assert data["reader"] in readers.KINDS, m["name"]
        assert reported_in(m) <= set(cells)
    for m in manifest["per_layer"]:
        assert m["moves"] in end, m["name"]
        # only the phases of set-up move the set-up time (test_setup.py holds
        # each of them against its file)
        assert (m["moves"] == "setup_s") == (m["layer"] == "set-up"), m["name"]
        assert reported_in(m) <= reported_in(end[m["moves"]]), m["name"]
    for cell in cells:
        ends = [n for n, m in end.items() if cell in reported_in(m)]
        assert "setup_s" in ends and len(ends) >= 2
        assert any(cell in reported_in(m) for m in manifest["per_layer"])
    # one name for one layer, letter for letter
    layers = {m["layer"] for m in manifest["per_layer"]}
    assert len({l.lower() for l in layers}) == len(layers)


def test_files_under_paths_are_named_from_the_allowed_characters(manifest):
    for base in manifest["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert PATH.match(rel), rel


def test_the_harness_names_no_cell_configuration_or_metric(manifest):
    names = [e["name"] for g in ("configs", "workloads", "end_to_end", "per_layer")
             for e in manifest[g]] + [w["traffic"] for w in manifest["workloads"]]
    for module in os.listdir(BENCH):
        if not module.endswith(".py"):
            continue
        with open(os.path.join(BENCH, module)) as f:
            text = f.read()
        for name in names:
            # ``setup_s`` is the one name the contract itself fixes: the
            # record carries the set-up time under it
            if name != "setup_s":
                assert not re.search(rf"\b{re.escape(name)}\b", text), (module, name)
