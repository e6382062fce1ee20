"""BENCHMARK.json against the limits its contract states, and against the
files the harness finds by name: every configuration, traffic mix and
metric an entry names is a file of its own under ``perfbench/``."""

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load():
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def test_keys_names_and_limits():
    b = load()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["perfbench"] and b["command"][-1].startswith("perfbench/")
    cells = len(b["workloads"])
    # 2 + 14 x cells runs, sized for the full 24 cells
    assert 1 <= b["run_seconds"] <= 51
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= cells <= 24 and 1 <= len(b["configs"]) <= 24
    four = sum(1 for w in b["workloads"] if w["chips"] == 4)
    assert four <= max(1, cells // 4)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("perfbench/")
        assert any(w["config"] == c["name"] for w in b["workloads"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) == cells
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and "\n" not in m["layer"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])


def test_every_name_is_a_file_and_every_cell_reports_enough():
    b = load()
    cells = [w["name"] for w in b["workloads"]]
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        chips = {w["chips"] for w in b["workloads"] if w["config"] == c["name"]}
        assert chips == {cfg["serve"]["chips"]}
    for w in b["workloads"]:
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    for m in b["end_to_end"] + b["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics", m["name"] + ".py"))
        assert set(m.get("workloads", cells)) <= set(cells)
    e2e_of = {c: {m["name"] for m in b["end_to_end"]
                  if c in m.get("workloads", cells)} for c in cells}
    for c in cells:
        assert "setup_s" in e2e_of[c] and len(e2e_of[c]) >= 2
        layer = [m for m in b["per_layer"] if c in m.get("workloads", cells)]
        assert layer
        for m in layer:  # the metric it moves is reported in that cell
            assert m["moves"] in e2e_of[c], (m["name"], c)
    # a share of a roofline or of a peak: named as the contract says
    assert any(m["name"].endswith("_roofline") and m["unit"] == "%"
               for m in b["per_layer"])
    assert any("mfu" in re.split(r"[._\-]", m["name"]) for m in b["per_layer"])
