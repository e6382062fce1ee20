"""An architecture is found BY NAME: the configuration's ``model_type``
names ``perfbench/arch/<model_type>.py``, which holds that architecture's
plain forward and its work counts.  A later PR adds an architecture with
files and entries alone, the file that is named is the file that judges,
and a name with no file stops a run before any server starts."""

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run as runmod  # noqa: E402
import work  # noqa: E402

QK_NORM = ('        q = rope(rms_norm(q, z["eps"]), pos, z["theta"])\n'
           '        k = rope(rms_norm(k, z["eps"]), pos, z["theta"])\n')
NO_QK_NORM = ('        q = rope(q, pos, z["theta"])\n'
              '        k = rope(k, pos, z["theta"])\n')


def config_of(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


# ---- an architecture is added with files alone ---------------------------

def checkout_with_another_architecture(tmp_path, leave_out_qk_norm):
    """A copy of ``perfbench/`` plus what a ``model_config`` PR brings:
    ``arch/other_arch.py`` (the served tiny model restated under another
    name, whole or with the per-head norm on q and k left out), a
    configuration whose ``model_type`` is that name, a cell.  Nothing that
    was there is edited."""
    copy = tmp_path / "checkout"
    shutil.copytree(BENCH, copy / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    source = (copy / "perfbench/arch/qwen3.py").read_text()
    assert source.count(QK_NORM) == 1
    if leave_out_qk_norm:
        source = source.replace(QK_NORM, NO_QK_NORM)
    (copy / "perfbench/arch/other_arch.py").write_text(source)
    cfg = config_of("qwen3-tiny-cpu")
    cfg.update(name="other-tiny", model_type="other_arch")
    with open(copy / "perfbench/configs/other-tiny.json", "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(HERE, "BENCHMARK.tiny.json")) as f:
        bench = json.load(f)
    cell = "other-tiny.tiny-saturated"
    bench["configs"].append({
        "name": "other-tiny", "source": "test", "reduced": [], "why": "test",
        "file": "perfbench/configs/other-tiny.json"})
    bench["workloads"].append({
        "name": cell, "config": "other-tiny", "traffic": "tiny-saturated",
        "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("out_tokens_per_s", "step_mfu"):
            m["workloads"].append(cell)
    with open(copy / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return copy, cell


@pytest.mark.parametrize("leave_out_qk_norm", [False, True],
                         ids=["restated-whole", "qk-norm-left-out"])
def test_a_new_architecture_is_files_alone_and_its_file_judges(
        tmp_path, leave_out_qk_norm):
    """The copy's own ``run.py``, as the driver starts it: one root holds
    the harness, the module and the counts; the program stays the
    checkout's."""
    copy, cell = checkout_with_another_architecture(tmp_path, leave_out_qk_norm)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".xla_cache"))
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", cell, "--seed",
         str(2**31 + 27), "--seconds", "4", "--trace", "0", "--platform",
         "cpu", "--program-root", ROOT], cwd=copy, env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    with open(copy / ".perfbench_run/reference_job.json") as f:
        assert json.load(f)["arch"] == str(copy / "perfbench/arch/other_arch.py")
    assert res["failed"] == 0 and res["attempted"] > 10
    value, limit = res["compared"]["gap_mean"]
    if leave_out_qk_norm:  # another model's logits.  Readings (CPU, three
        # runs): mean gap 0.064 and widest gap 0.94-1.12 against the limits
        # 0.0006 and 0.5; the whole restatement reads 0.00013-0.00015
        assert res["correct"] is False and value > 20 * limit
    else:
        assert res["correct"] is True and value <= limit
        assert res["compared"]["served_tokens_compared"][0] > 20


# ---- a name with no file stops the run before any server ------------------

@pytest.mark.parametrize("model_type", [None, "no_such_arch"])
def test_a_model_type_with_no_file_fails_before_any_server(
        tmp_path, monkeypatch, model_type):
    root = tmp_path / "checkout"
    os.makedirs(root / "perfbench/configs")
    os.makedirs(root / "perfbench/arch")
    cfg = config_of("qwen3-tiny-cpu")
    del cfg["model_type"]
    if model_type:
        cfg["model_type"] = model_type
    with open(root / "perfbench/configs/qwen3-tiny-cpu.json", "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(HERE, "BENCHMARK.tiny.json")) as f:
        bench = json.load(f)

    def no_server(*_args, **_kwargs):
        raise AssertionError("a server was started")

    monkeypatch.setattr(runmod.serverproc, "Server", no_server)
    args = argparse.Namespace(
        workload="qwen3-tiny-cpu.tiny-saturated", seed=1, seconds=1.0,
        trace=0, platform="cpu", bench_root=str(root), program_root=ROOT,
        trace_fixture="")
    t = time.monotonic()
    looked_for = os.path.join(str(root), "perfbench", "arch",
                              (model_type or "<model_type>") + ".py")
    with pytest.raises(runmod.RunFailure) as failure:
        runmod.measure(args, bench, str(tmp_path / "run"))
    assert time.monotonic() - t < 1.0
    assert looked_for in str(failure.value)
    assert not (tmp_path / "run").exists()


# ---- the counts, through work.py's five names ----------------------------

def test_the_counts_of_the_cells_configuration_are_todays():
    """``step_mfu`` and ``attn_decode_roofline`` read these through
    ``work.py``; the values are ``work.py``'s own at PR 26, to the
    integer."""
    cfg = config_of("qwen3-1.7b")
    assert work.matmul_params(cfg) == 1_720_451_072
    assert work.token_flops(cfg, 1000) == 3_670_278_144
    assert work.token_flops(cfg, 1000, with_head=False) == 3_047_948_288
    assert work.prompt_flops(cfg, 512) == 1_473_854_832_640
    assert work.kv_bytes_per_position(cfg) == 114_688
    assert work.kv_bytes_per_position(cfg, kv_dtype_bytes=1) == 57_344
    assert work.decode_kv_bytes(cfg, [100, 200]) == 34_406_400


@pytest.mark.parametrize("benchmark", [
    os.path.join(ROOT, "BENCHMARK.json"),
    os.path.join(HERE, "BENCHMARK.tiny.json")], ids=["cells", "tiny"])
def test_every_configuration_resolves_to_an_architectures_file(benchmark):
    with open(benchmark) as f:
        bench = json.load(f)
    for entry in bench["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            cfg = json.load(f)
        path = work.arch_path(cfg)
        assert path == os.path.join(BENCH, "arch", cfg["model_type"] + ".py")
        mod = work.load_arch(path)
        assert callable(mod.Forward)
        assert work.matmul_params(cfg) > 0 and work.decode_kv_bytes(cfg, [1]) > 0


def test_an_unknown_model_type_raises_with_the_path():
    cfg = dict(config_of("qwen3-tiny-cpu"), model_type="no_such_arch")
    with pytest.raises(ValueError, match="arch/no_such_arch.py"):
        work.token_flops(cfg, 10)
    del cfg["model_type"]
    with pytest.raises(ValueError, match="no model_type"):
        work.matmul_params(cfg)


def test_reading_the_counts_imports_neither_jax_nor_the_program():
    """``run.py`` never touches jax (the chip belongs to its children),
    and the yardstick takes nothing from the program."""
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {BENCH!r})\n"
        "import work\n"
        f"cfg = json.load(open({os.path.join(BENCH, 'configs', 'qwen3-1.7b.json')!r}))\n"
        "mod = work.load_arch(work.arch_path(cfg))\n"
        "assert work.prompt_flops(cfg, 8) > 0\n"
        "bad = [m for m in sys.modules if m.split('.')[0] in "
        "('jax', 'jaxlib', 'fusioninfer_tpu')]\n"
        "print(json.dumps(bad))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60, check=True)
    assert json.loads(out.stdout.strip().splitlines()[-1]) == []


def test_the_padded_length_is_the_least_power_of_two_that_holds_it():
    import reference

    assert [reference.seq_bucket(n) for n in
            (1, 1024, 1025, 2048, 2049, 4096, 4097, 16384, 100_000)] == [
        1024, 1024, 2048, 2048, 4096, 4096, 8192, 16384, 131072]
    assert all(reference.seq_bucket(n) % reference.Q_BLOCK == 0
               for n in (1, 3000, 70_000))
