"""BENCHMARK.json against the contract's shape: names, units, keys."""
import json
import os
import re

from benchmark import harness

ROOT = harness.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmark"]
    assert 1 <= m["run_seconds"] <= 51


def test_names_and_units_use_allowed_characters():
    m = manifest()
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in m[section]]
        assert len(names) == len(set(names))
        for n in names:
            assert NAME.match(n), n
    for w in m["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
    for c in m["configs"]:
        for key in c["reduced"]:
            assert NAME.match(key)
    for metric in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(metric["unit"]), metric["unit"]
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES


def test_entries_have_just_their_keys():
    m = manifest()
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert p["moves"] == "change_p95_ms"


def test_every_named_file_exists():
    m = manifest()
    for c in m["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in m["workloads"]:
        cfg = harness.load_cell(w["name"])
        assert cfg["cell"]["rate_per_s"] > 0
    for p in m["per_layer"]:
        assert callable(harness.load_reader(
            os.path.join(ROOT, "benchmark"), p["name"]))
