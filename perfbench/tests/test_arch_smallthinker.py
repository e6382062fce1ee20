"""``model_type`` "smallthinker" through the harness: the tiny
configuration (two periods of [full + NoPE, 3 x window + rope], a window
of 24 that every prompt of the mix passes, 8 ReLU-gated experts routed
from the layer's input) is served by the program on the CPU through both
pools of its cache and judged by ``perfbench/arch/smallthinker.py`` in a
scratch copy (``run.measure`` as the driver starts it), ``correct:
true``; its ``--control`` (the int8 reference's first tokens in the
served tokens' place) is ``correct: false`` by ``gap_mean``; and with
the architecture's file altered (full attention in the windowed layers'
place; the router reading the normed MLP input) the same run is
``correct: false``: the file that is named is the file that judges, and
the limits see each mechanism."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

CELL = "smallthinker-tiny-cpu.tiny-saturated"
ALTERED = {
    "windowed-layers-given-full-attention": (
        'z["W"] if windowed else None', 'None'),
    "router-reading-the-normed-mlp-input": (
        'return a + expert_layer(quant, layer, weights, rms_norm(a, z["eps"]))',
        'return a + expert_layer(quant, layer, route(z, rms_norm(a, z["eps"]),'
        ' w_of("router")), rms_norm(a, z["eps"]))'),
}


def checkout(tmp_path, altered):
    copy = tmp_path / "checkout"
    shutil.copytree(BENCH, copy / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    if altered:
        arch = copy / "perfbench/arch/smallthinker.py"
        source = arch.read_text()
        old, new = ALTERED[altered]
        assert source.count(old) == 1
        arch.write_text(source.replace(old, new))
    with open(os.path.join(HERE, "BENCHMARK.tiny.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "smallthinker-tiny-cpu", "source": "test", "reduced": [],
        "why": "test", "file": "perfbench/configs/smallthinker-tiny-cpu.json"})
    bench["workloads"].append({
        "name": CELL, "config": "smallthinker-tiny-cpu",
        "traffic": "tiny-saturated", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("out_tokens_per_s", "step_mfu"):
            m["workloads"].append(CELL)
    with open(copy / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return copy


@pytest.mark.parametrize("case", ["restated-whole", "control", *ALTERED])
def test_the_tiny_configuration_is_served_and_judged(tmp_path, case):
    copy = checkout(tmp_path, case if case in ALTERED else None)
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
