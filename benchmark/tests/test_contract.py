"""BENCHMARK.json against what of its contract can be checked here, and
the harness against its own rule: every cell, configuration, traffic mix
and per-layer metric is data found by name."""

import importlib
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def test_keys_names_units_and_lengths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = [w["name"] for w in BENCH["workloads"]]
    configs = [c["name"] for c in BENCH["configs"]]
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    for names in (cells, configs, metrics):
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(cells) // 4)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
        assert c["file"].startswith("benchmark/")
    ends = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in ends
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in ends and m["source"] in SOURCES
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)


def test_every_cell_reports_enough():
    by_name = {m["name"]: m for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        def here(m):
            return w["name"] in m.get("workloads", [w["name"]])
        ends = [m["name"] for m in BENCH["end_to_end"] if here(m)]
        assert "setup_s" in ends and len(ends) >= 2
        layers = [m for m in BENCH["per_layer"] if here(m)]
        assert layers
        # a per-layer metric is reported only where the metric it moves is
        assert all(here(by_name[m["moves"]]) for m in layers)


def test_every_named_thing_is_a_file_found_by_name():
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert set(c["reduced"]) == set(cfg["reduced_from_source"])
        assert cfg["reference"]["loss_tolerance"] > 0
    for w in BENCH["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        driver = importlib.import_module(
            "benchmark.drivers." + traffic["driver"])
        assert callable(driver.run)
        assert traffic["throughput_metric"] in {
            m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        reader = importlib.import_module(
            "benchmark.layer_metrics." + m["name"])
        assert callable(reader.read)


def test_no_cell_is_special_cased_in_code():
    words = [w["name"] for w in BENCH["workloads"]] \
        + [c["name"] for c in BENCH["configs"]]
    for folder in ("", "drivers", "harness", "hosts", "reduce",
                   "layer_metrics", "reference"):
        path = os.path.join(ROOT, "benchmark", folder)
        for name in os.listdir(path):
            if not name.endswith(".py"):
                continue
            with open(os.path.join(path, name)) as f:
                text = f.read()
            assert not any(w in text for w in words), (folder, name)
