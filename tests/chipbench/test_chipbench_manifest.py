"""BENCHMARK.json against the files it names and the contract's limits on
names, units and sizes."""

import json
import os
import re

import pytest

from chipbench import check

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["command"]) <= 32 and all(_line(w) for w in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16
    for path in manifest["paths"]:
        assert PATH.match(path) and not path.startswith("/") and ".." not in path
        assert os.path.isdir(os.path.join(ROOT, path))
    # a full check has to fit: 2 + 14 runs a cell at the full 24 cells
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_every_named_file_exists(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    for config in manifest["configs"]:
        assert any(config["file"].startswith(p + "/") for p in manifest["paths"])
        with open(os.path.join(ROOT, config["file"])) as fh:
            body = json.load(fh)
        assert body["name"] == config["name"]
        assert set(config["reduced"]) == set(body["reduced"])
    for cell in manifest["workloads"]:
        assert os.path.exists(os.path.join(ROOT, "chipbench", "mixes", cell["traffic"] + ".json"))
        with open(os.path.join(ROOT, "chipbench", "workloads", cell["name"] + ".json")) as fh:
            assert set(json.load(fh)["limits"]) <= set(check.NUMBERS)
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "chipbench", "metrics", metric["name"] + ".py"))
        assert set(metric.get("workloads", [])) <= set(cells)
    for dirpath, _, files in os.walk(os.path.join(ROOT, "chipbench")):
        if "__pycache__" in dirpath:
            continue
        for name in files:
            rel = os.path.relpath(os.path.join(dirpath, name), ROOT)
            assert PATH.match(rel), rel


def test_names_units_and_entries(manifest):
    names = []
    for config in manifest["configs"]:
        assert set(config) == {"name", "source", "file", "reduced", "why"}
        assert _line(config["source"]) and _line(config["why"])
        assert len(config["reduced"]) <= 16 and all(NAME.match(k) for k in config["reduced"])
        names.append(config["name"])
    configs = set(names)
    pairs = set()
    for cell in manifest["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["config"] in configs and cell["chips"] in (1, 4) and _line(cell["why"])
        assert NAME.match(cell["traffic"])
        pairs.add((cell["config"], cell["traffic"]))
        names.append(cell["name"])
    assert len(pairs) == len(manifest["workloads"])
    assert {c["config"] for c in manifest["workloads"]} == configs
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for metric in manifest["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    for metric in manifest["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert metric["moves"] in e2e and _line(metric["layer"])
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(metric["unit"]) and metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
        names.append(metric["name"])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert any("mfu" in re.split(r"[_.]", m["name"]) for m in manifest["per_layer"])
