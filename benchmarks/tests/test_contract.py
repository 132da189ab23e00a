"""BENCHMARK.json against the limits the driver refuses a file over, and
against the files it names: run this after adding an entry."""

import json
import os
import re

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def one_line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def listed(metric, cells):
    return metric.get("workloads", cells)


def test_benchmark_json_keeps_the_contract():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert len(b["command"]) <= 32 and all(one_line(c) for c in b["command"])
    assert 1 <= len(b["paths"]) <= 16 and all(PATH.match(p)
                                              for p in b["paths"])

    configs = [c["name"] for c in b["configs"]]
    assert len(set(configs)) == len(configs) <= 24
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["source"])
        assert one_line(c["why"]) and len(c["reduced"]) <= 16
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert os.path.exists(os.path.join(
            os.path.dirname(os.path.join(ROOT, c["file"])),
            held["netconfig"]))
    assert len({c["file"] for c in b["configs"]}) == len(configs)

    cells = [w["name"] for w in b["workloads"]]
    assert len(set(cells)) == len(cells) <= 24
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) \
        == len(cells)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert one_line(w["why"])
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            driver = json.load(f)["driver"]
        assert os.path.exists(os.path.join(BENCH, "drivers", driver + ".py"))
    assert {w["config"] for w in b["workloads"]} == set(configs)
    assert sum(w["chips"] == 4 for w in b["workloads"]) \
        <= max(1, len(cells) // 4)

    e2e = {}
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(listed(m, cells)) <= set(cells)
        e2e[m["name"]] = m
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert len(e2e) == len(b["end_to_end"]) <= 16

    names = set(e2e)
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert one_line(m["layer"]) and m["name"] not in names
        names.add(m["name"])
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           m["name"] + ".py"))
        # the metric it moves is reported wherever this one is
        assert set(listed(m, cells)) <= set(listed(e2e[m["moves"]], cells))
    assert len(b["per_layer"]) <= 128

    for cell in cells:
        assert any(cell in listed(m, cells) for m in b["end_to_end"]
                   if m["name"] != "setup_s"), cell
        assert any(cell in listed(m, cells) for m in b["per_layer"]), cell

    # a full check with all 24 cells at this length fits the driver's day
    full = (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200
    assert full <= 43200


def test_files_under_paths_are_named_from_a_names_characters():
    for root, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), ROOT)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
