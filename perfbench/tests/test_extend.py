"""The harness is driven by data: a later PR adds a configuration, a
traffic mix and a per-layer metric as files of their own plus entries in
the benchmark file, and edits nothing that is there.  Shown by a dry run
in a scratch copy of ``perfbench/``: the three files are added, the
entries appended, no existing file changes, and a traced run of the new
cell reports the new metric."""

import argparse
import filecmp
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run as runmod  # noqa: E402


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(BENCH, copy / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.load(open(os.path.join(HERE, "BENCHMARK.tiny.json")))

    # 1. a configuration: its file of sizes (here: the tiny one, two slots)
    cfg = json.load(open(copy / "perfbench/configs/qwen3-tiny-cpu.json"))
    cfg["name"] = "tiny-two-slots"
    cfg["serve"]["max_batch_size"] = 2
    cfg["serve"]["flags"][1] = "2"
    json.dump(cfg, open(copy / "perfbench/configs/tiny-two-slots.json", "w"))
    # 2. a traffic mix: a data file the one generator reads
    mix = json.load(open(copy / "perfbench/traffic/tiny-poisson.json"))
    mix["rate_rps"] = 2.0  # its rows, at half the rate
    mix["requests"] = [[2 * d, p, o] for d, p, o in mix["requests"]]
    json.dump(mix, open(copy / "perfbench/traffic/tiny-trickle.json", "w"))
    # 3. a per-layer metric: a small reader of its own
    (copy / "perfbench/metrics/requests_finished.py").write_text(
        '"""Counted requests that finished."""\n\n\n'
        "def read(run):\n"
        "    return float(sum(1 for r in run.counted if r.ok))\n")
    # ... and their entries
    cell = "tiny-two-slots.tiny-trickle"
    bench["configs"].append({
        "name": "tiny-two-slots", "source": "test",
        "file": "perfbench/configs/tiny-two-slots.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": cell, "config": "tiny-two-slots", "traffic": "tiny-trickle",
        "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "requests_finished", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "benchmark", "moves": "ttft_p50_ms",
        "workloads": [cell]})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p50_ms", "ttft_p90_ms"):
            m["workloads"].append(cell)
    for m in bench["per_layer"]:
        if m["name"] in ("warm_tour_s", "stall_p90_ms"):
            m["workloads"].append(cell)

    # nothing that was there has changed
    cmp = filecmp.dircmp(BENCH, copy / "perfbench", ignore=["__pycache__"])

    def differing(d):
        out = list(d.diff_files) + list(d.left_only)
        for sub in d.subdirs.values():
            out += differing(sub)
        return out

    assert differing(cmp) == []

    args = argparse.Namespace(
        workload=cell, seed=5, seconds=4.0, trace=1, platform="cpu",
        bench_root=str(copy), program_root=ROOT,
        trace_fixture=os.path.join(HERE, "data", "tiny.xplane.pb"))
    res = runmod.measure(args, bench, str(tmp_path / "run"))
    assert res["correct"] is True
    assert res["engine"]["token_budget"] == 96
    assert res["metrics"]["requests_finished"]["value"] >= 3
    assert {"warm_tour_s", "stall_p90_ms"} <= set(res["metrics"])
    assert "gen_late_p90_ms" not in res["metrics"]  # lists other cells
