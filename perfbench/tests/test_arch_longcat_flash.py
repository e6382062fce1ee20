"""``model_type`` "longcat_flash" through the harness: the tiny
configuration is served by the program on the CPU and judged by
``perfbench/arch/longcat_flash.py`` in a scratch copy (``run.measure`` as
the driver starts it), ``correct: true``; its ``--control`` (the int8
reference's first tokens in the served tokens' place) is ``correct:
false`` by ``gap_mean``; and with the identity experts' term left out of
the architecture's file the same run is ``correct: false``: the file
that is named is the file that judges."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

WHOLE = 'return y + identity[:, None] * h'
LEFT_OUT = 'return y'
CELL = "longcat-flash-tiny-cpu.tiny-saturated"


def checkout(tmp_path, leave_out_identity):
    copy = tmp_path / "checkout"
    shutil.copytree(BENCH, copy / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    arch = copy / "perfbench/arch/longcat_flash.py"
    source = arch.read_text()
    assert source.count(WHOLE) == 1
    if leave_out_identity:
        arch.write_text(source.replace(WHOLE, LEFT_OUT))
    with open(os.path.join(HERE, "BENCHMARK.tiny.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "longcat-flash-tiny-cpu", "source": "test", "reduced": [],
        "why": "test", "file": "perfbench/configs/longcat-flash-tiny-cpu.json"})
    bench["workloads"].append({
        "name": CELL, "config": "longcat-flash-tiny-cpu",
        "traffic": "tiny-saturated", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("out_tokens_per_s", "step_mfu"):
            m["workloads"].append(CELL)
    with open(copy / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return copy


@pytest.mark.parametrize("case", ["restated-whole", "control",
                                  "identity-term-left-out"])
def test_the_tiny_configuration_is_served_and_judged(tmp_path, case):
    copy = checkout(tmp_path, case == "identity-term-left-out")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".xla_cache"))
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL, "--seed",
         str(2**31 + 29), "--seconds", "4", "--trace", "0", "--platform",
         "cpu", "--program-root", ROOT] + (
             ["--control"] if case == "control" else []), cwd=copy, env=env,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["failed"] == 0 and res["attempted"] > 10
    mean, mean_limit = res["compared"]["gap_mean"]
    assert res["compared"]["served_tokens_compared"][0] > 20
    if case == "restated-whole":  # readings: the configuration's file
        assert res["correct"] is True and mean <= mean_limit
    elif case == "control":  # int8 in bfloat16's place fails the mean gap
        assert res["correct"] is False and mean > mean_limit
        assert res["compared"]["served_gap_mean"][0] <= mean_limit
    else:  # another model's logits
        assert res["correct"] is False and mean > 5 * mean_limit
