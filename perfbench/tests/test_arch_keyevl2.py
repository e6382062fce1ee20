"""``model_type`` "KeyeVL2" through the harness: the tiny configuration
(two layers whose queries attend over the top 24 positions of a learned
indexer, pages of 16 positions, 8 experts routed 2 a token) is served by
the program on the CPU through its indexer-key cache and judged by
``perfbench/arch/KeyeVL2.py`` in a scratch copy (``run.measure`` as the
benchmark's command starts it), ``correct: true``; its ``--control`` (the int8
reference's first tokens in the served tokens' place) is ``correct:
false`` by ``gap_mean``; and with the architecture's file
altered (dense attention in the sparse layers' place; the last 24
positions in the indexer's place) the same run is ``correct: false``:
the file that is named is the file that judges, and the limits see the
indexer's choice."""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

CELL = "keye-vl2-tiny-cpu.tiny-saturated"
ALTERED = {
    "sparse-layers-given-dense-attention": (
        '        if S > z["K"]:\n', '        if False:\n'),
    "indexer-given-the-last-topk-positions": (
        'score = jnp.where(seen, jnp.where(score == 0, 0.0, score), -jnp.inf)',
        'score = jnp.where(seen, t[None, :].astype(jnp.float32), -jnp.inf)'),
}


def checkout(tmp_path, altered):
    copy = tmp_path / "checkout"
    shutil.copytree(BENCH, copy / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    if altered:
        arch = copy / "perfbench/arch/KeyeVL2.py"
        source = arch.read_text()
        old, new = ALTERED[altered]
        assert source.count(old) == 1
        arch.write_text(source.replace(old, new))
    with open(os.path.join(HERE, "BENCHMARK.tiny.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "keye-vl2-tiny-cpu", "source": "test", "reduced": [],
        "why": "test", "file": "perfbench/configs/keye-vl2-tiny-cpu.json"})
    bench["workloads"].append({
        "name": CELL, "config": "keye-vl2-tiny-cpu",
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
    else:  # another choice of positions than the served one
        assert res["correct"] is False and mean > 2 * mean_limit
