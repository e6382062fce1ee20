"""``model_type`` "deepseek_v2" through the harness: the tiny
configuration is served by the program on the CPU and judged by
``perfbench/arch/deepseek_v2.py`` in a scratch copy (``run.measure`` as
the driver starts it), ``correct: true``; and with
``routed_scaling_factor`` left out of the architecture's file (the routed
experts weigh 1 / 4 of what the model says) the same run is ``correct:
false``: the file that is named is the file that judges."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

SCALED = 'else vals * z["routed_scale"])'
UNSCALED = 'else vals)'
CELL = "deepseek-v2-tiny-cpu.tiny-saturated"


def checkout(tmp_path, leave_out_scaling):
    copy = tmp_path / "checkout"
    shutil.copytree(BENCH, copy / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    arch = copy / "perfbench/arch/deepseek_v2.py"
    source = arch.read_text()
    assert source.count(SCALED) == 1
    if leave_out_scaling:
        arch.write_text(source.replace(SCALED, UNSCALED))
    with open(os.path.join(HERE, "BENCHMARK.tiny.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "deepseek-v2-tiny-cpu", "source": "test", "reduced": [],
        "why": "test", "file": "perfbench/configs/deepseek-v2-tiny-cpu.json"})
    bench["workloads"].append({
        "name": CELL, "config": "deepseek-v2-tiny-cpu",
        "traffic": "tiny-saturated", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("out_tokens_per_s", "step_mfu"):
            m["workloads"].append(CELL)
    with open(copy / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return copy


@pytest.mark.parametrize("leave_out_scaling", [False, True],
                         ids=["restated-whole", "routed-scaling-left-out"])
def test_the_tiny_configuration_is_served_and_judged(tmp_path,
                                                     leave_out_scaling):
    copy = checkout(tmp_path, leave_out_scaling)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".xla_cache"))
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", CELL, "--seed",
         str(2**31 + 29), "--seconds", "4", "--trace", "0", "--platform",
         "cpu", "--program-root", ROOT], cwd=copy, env=env,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["failed"] == 0 and res["attempted"] > 10
    mean, mean_limit = res["compared"]["gap_mean"]
    if leave_out_scaling:  # another model's logits.  Readings (CPU): mean
        # gap 0.22 and widest gap 2.5 against the limits 0.02 and 1.5
        assert res["correct"] is False and mean > 5 * mean_limit
    else:  # readings (CPU, bfloat16 served against float32): mean gap
        # 0.0020-0.0045, widest gap 0.45-0.83 (a router near-tie that flips)
        assert res["correct"] is True and mean <= mean_limit
        assert res["compared"]["served_tokens_compared"][0] > 20
