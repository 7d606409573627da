"""BENCHMARK.json against the contract's shape, and every name in it
against a file: a cell, a configuration, a mix or a per-layer metric is
added by adding files and entries."""

import importlib
import json
import os
import re

import pytest

from benchmark import harness

ROOT = harness.ROOT
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
PERF_MD = open(os.path.join(ROOT, "PERF.md")).read()
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
LEVERS = re.compile(
    r"--(host-dedup|compact-|gfull|segtotal|fused-embed|sparse-update|"
    r"param-dtype|compute-dtype|collective-dtype|score-sharded)")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_top_level_shape():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark"]
    assert MANIFEST["command"][-1].startswith("benchmark/")
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    names = [e["name"] for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for e in MANIFEST[group]]
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        own = [e["name"] for e in MANIFEST[group]]
        assert len(own) == len(set(own))
    assert all(len(e["why"]) <= 200
               for e in MANIFEST["configs"] + MANIFEST["workloads"])


def test_cells_and_configs():
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert 2 <= len(pairs) <= 24
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in MANIFEST["workloads"])
    assert len(four) <= max(1, len(pairs) // 4)
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    for c in MANIFEST["configs"]:
        doc = json.load(open(os.path.join(ROOT, c["file"])))
        assert c["file"].startswith("benchmark/configs/")
        assert doc["name"] == c["name"] and doc["reduced"] == c["reduced"]
        assert not any(re.search(r"rank|_dim|width|hidden", k)
                       for k in c["reduced"])
        importlib.import_module(f"benchmark.reference.{doc['reference']}")


def test_metrics():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] == 0.1
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1 and m["better"] in ("lower", "higher")
    for m in MANIFEST["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in e2e and "bound" not in m
        assert LAYER.match(m["layer"]), m["layer"]
        assert f"\n| {m['layer']} |" in PERF_MD, m["layer"]
        reader = importlib.import_module(
            f"benchmark.layer_metrics.{m['name']}")
        assert callable(reader.read)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = harness.load_cell(name)
    importlib.import_module(f"benchmark.drivers.{cell.driver}")
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    # A per-layer metric is reported only where the metric it moves is.
    assert all(m["moves"] in reported for m in cell.per_layer)
    tiny = harness.load_cell(name, rehearse=True)
    assert tiny.config["model"]["bucket"] < cell.config["model"]["bucket"]
    assert tiny.config["as_written"] == cell.config


def test_no_benchmark_file_names_a_lever():
    hits = []
    paths = [os.path.join(ROOT, "BENCHMARK.json")]
    for base, dirs, files in os.walk(harness.HERE):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        paths += [os.path.join(base, f) for f in files
                  if os.path.join(base, f) != os.path.abspath(__file__)]
    for path in paths:
        with open(path, "rb") as f:
            text = f.read().decode("utf-8", "replace")
        hits += [(path, m.group(0)) for m in LEVERS.finditer(text)]
    assert not hits
    assert all(re.fullmatch(r"[A-Za-z0-9_.\-/]+", os.path.relpath(p, ROOT))
               for p in paths)
