"""BENCHMARK.json against the benchmark's contract, and every file a cell
needs found by name."""
import importlib
import json
import os
import re

import pytest

import harness

ROOT = os.path.dirname(harness.BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and ".." not in p and not p.startswith("/")
    assert 1 <= len(SPEC["command"]) <= 32
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


def _names(key):
    return [e["name"] for e in SPEC[key]]


@pytest.mark.parametrize("key", ["configs", "workloads", "end_to_end",
                                 "per_layer"])
def test_names_and_units(key):
    names = _names(key)
    assert len(names) == len(set(names))
    for entry in SPEC[key]:
        assert NAME.match(entry["name"]), entry["name"]
        for field in ("why", "layer", "source"):
            if field in entry and key != "end_to_end":
                text = entry[field]
                assert 1 <= len(text) <= 200 and "\n" not in text \
                    and "\t" not in text
        if "unit" in entry:
            assert UNIT.match(entry["unit"]), entry["unit"]
            assert entry["better"] in ("lower", "higher")
            assert entry["source"] in SOURCES


def test_metric_names_unique_across_kinds():
    names = _names("end_to_end") + _names("per_layer")
    assert len(names) == len(set(names))


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_configs_resolve():
    used = {w["config"] for w in SPEC["workloads"]}
    files = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert c["file"].startswith(SPEC["paths"][0] + "/")
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg
            assert not re.search(r"(_dim|_rank|hidden|intermediate|head)",
                                 key)
        importlib.import_module(f"reference.{cfg['model']}")
        importlib.import_module(f"flops.{cfg['model']}")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_files_resolve(cell):
    c = harness.Cell(SPEC, cell)
    assert c.entry["chips"] in (1, 4)
    assert set(c.entry) == {"name", "config", "traffic", "chips", "why"}
    assert c.check_blocks >= 2
    assert set(c.limits) == set(
        importlib.import_module("check").NAMES)
    assert c.end_to_end() and c.per_layer()
    assert "setup_s" in [m["name"] for m in c.end_to_end()]
    for m in c.per_layer():
        assert callable(importlib.import_module(f"metrics.{m['name']}")
                        .read)


def test_per_layer_entries():
    e2e = set(_names("end_to_end"))
    cells = set(_names("workloads"))
    layers = {}
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        layers.setdefault(m["layer"], []).append(m["name"])
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_four_chip_share():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_peaks_table_names_its_source():
    peaks = harness.load_json("peaks.json")
    assert "819" in peaks["source"] and "197" in peaks["source"]
    assert peaks["devices"]["TPU v5 lite"]["bf16_flops"] == 197e12
