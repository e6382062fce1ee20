"""Nothing on the serve path may hide the device.

The platform guard (CPU only when asked for by name), the peaks table
(an unknown accelerator raises), ``bench.py``'s chip-or-fail exit, and
``chip_smoke.py`` — its CPU dry mode end to end plus the failure modes
it must turn into a non-zero exit.  ``chip_smoke`` never imports jax,
so its pieces are driven in-process here; only the dry run and the
bench exit start children.
"""

import json
import os
import subprocess
import sys
import types

import pytest

import bench
import chip_smoke
from fusioninfer_tpu.benchmark import mfu
from fusioninfer_tpu.ops import dispatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_jax(backend, platforms):
    return types.SimpleNamespace(
        default_backend=lambda: backend,
        config=types.SimpleNamespace(jax_platforms=platforms))


class TestPlatformGuard:
    def test_unrequested_cpu_exits_nonzero(self, monkeypatch):
        # JAX_PLATFORMS unset, no accelerator found: jax fell back
        monkeypatch.setattr(dispatch, "jax", _fake_jax("cpu", None))
        with pytest.raises(SystemExit) as e:
            dispatch.require_requested_backend()
        assert e.value.code not in (0, None)
        assert "JAX_PLATFORMS=cpu" in str(e.value.code)

    @pytest.mark.parametrize("backend,platforms", [
        ("cpu", "cpu"), ("cpu", "tpu,cpu"), ("tpu", None), ("tpu", "tpu")])
    def test_requested_backends_run(self, monkeypatch, backend, platforms):
        monkeypatch.setattr(dispatch, "jax", _fake_jax(backend, platforms))
        assert dispatch.require_requested_backend() == backend

    def test_tpu_backend_is_the_tpu_platform_only(self, monkeypatch):
        monkeypatch.setattr(dispatch, "jax", _fake_jax("tpu", None))
        assert dispatch.is_tpu_backend()
        for other in ("cpu", "gpu", "rocm"):
            monkeypatch.setattr(dispatch, "jax", _fake_jax(other, None))
            assert not dispatch.is_tpu_backend()

    def test_serve_refuses_before_building_an_engine(self, monkeypatch):
        import argparse

        from fusioninfer_tpu.engine import aot, server

        def refuse():
            raise SystemExit("no accelerator")

        # (the entry points own the process's cache threshold; a test
        # process must keep its own)
        monkeypatch.setattr(aot, "configure_cache", lambda **kw: None)
        monkeypatch.setattr(dispatch, "require_requested_backend", refuse)
        monkeypatch.setattr(
            server, "_engine_from_args",
            lambda args: pytest.fail("built an engine on a refused backend"))
        for entry in (server.serve_from_args, server.warmup_from_args):
            with pytest.raises(SystemExit, match="no accelerator"):
                entry(argparse.Namespace(host="127.0.0.1", port=0,
                                         aot_warmup=False))


class TestPeaksTable:
    def test_v5e_kind_is_a_key(self):
        assert mfu.peak_flops("TPU v5 lite") == 197e12

    def test_cpu_has_no_peak(self):
        assert mfu.peak_flops("cpu") is None

    def test_unknown_accelerator_raises(self):
        with pytest.raises(KeyError, match="TPU v9"):
            mfu.peak_flops("TPU v9 mega")


class TestBenchChipOrFail:
    def test_error_keys_found_at_any_depth(self):
        record = {"value": 1.0, "decode": {"kernel_error": "boom",
                                           "gather_tok_s": 3.0},
                  "admissions": {"error": "x"}, "ladder": [{"error": "y"}]}
        assert bench._error_keys(record) == [
            "decode.kernel_error", "admissions.error", "ladder[0].error"]
        assert bench._error_keys({"value": 1.0, "errors": []}) == []

    def test_no_accelerator_and_no_bench_platform_exits_nonzero(self):
        env = {k: v for k, v in os.environ.items() if k != "BENCH_PLATFORM"}
        env["JAX_PLATFORMS"] = "cpu"  # ambient, NOT a request to bench
        proc = subprocess.run([sys.executable, "bench.py"], cwd=REPO,
                              env=env, capture_output=True, text=True,
                              timeout=120)
        assert proc.returncode != 0
        assert "no accelerator" in proc.stderr
        assert proc.stdout.strip() == ""  # no record from a CPU fallback


def _good_info(**over):
    info = {
        "platform": "cpu", "device_kind": "cpu", "device_count": 1,
        "attention": "flash", "interpret": True, "grid": "coalesced",
        "kv_splits": 8, "sharded_attention": None, "mesh": None,
        "n_pages": 65, "page_size": 32, "max_pages_per_seq": 16,
        "kv_dtype": "model", "token_budget": 128, "decode_burst": 8,
        "compile_cache_dir": "/some/dir",
        "aot": {"entries": 3, "hits": 0, "misses": 3, "errors": [],
                "build_seconds": 1.0},
        "devices": [{"id": 0, "bytes_in_use": None,
                     "peak_bytes_in_use": None, "bytes_limit": None}],
    }
    info.update(over)
    return info


class TestSmokeChecks:
    def test_good_info_passes(self):
        chip_smoke.check_engine_info(chip_smoke.DRY, "t", _good_info(), 1)

    @pytest.mark.parametrize("over,needle", [
        ({"platform": "tpu"}, "platform"),
        ({"attention": "reference"}, "attention"),
        ({"interpret": False}, "interpret"),
        ({"grid": "per-head"}, "grid"),
        ({"kv_splits": 0}, "KV-split"),
        ({"aot": {"entries": 2, "hits": 0, "misses": 2,
                  "errors": ["fused/chunk-t64: refused"],
                  "build_seconds": 1.0}}, "AOT"),
        ({"sharded_attention": "spmd-reference"}, "sharded_attention"),
    ])
    def test_what_hides_the_device_fails(self, over, needle):
        with pytest.raises(chip_smoke.SmokeFailure, match=needle):
            chip_smoke.check_engine_info(chip_smoke.DRY, "t",
                                         _good_info(**over), 1)

    def test_chip_mode_wants_compiled_kernels_on_a_tpu(self):
        # the CPU's answers can never pass for a chip result
        with pytest.raises(chip_smoke.SmokeFailure, match="platform"):
            chip_smoke.check_engine_info(chip_smoke.CHIP, "t",
                                         _good_info(), 1)

    def test_tp_wants_the_kernel_mesh_on_every_device(self):
        devices = [{"id": i, "bytes_in_use": None, "peak_bytes_in_use": None,
                    "bytes_limit": None} for i in range(2)]
        chip_smoke.check_engine_info(
            chip_smoke.DRY, "t",
            _good_info(sharded_attention="kernel-mesh", devices=devices), 2)
        with pytest.raises(chip_smoke.SmokeFailure, match="kernel-mesh"):
            chip_smoke.check_engine_info(
                chip_smoke.DRY, "t", _good_info(devices=devices), 2)

    def _metrics_page(self, **over):
        vals = {"vllm:request_failure_total": 0,
                "vllm:generation_tokens_total": 112,
                "fusioninfer:prefix_hit_tokens_total": 64,
                "fusioninfer:sched_chunks_total": 3,
                "fusioninfer:aot_cache_hits": 0,
                "fusioninfer:aot_cache_misses": 34}
        vals.update(over)
        return "\n".join(f'{k}{{model_name="m"}} {v}' for k, v in vals.items())

    @pytest.mark.parametrize("over,needle", [
        ({}, None),
        ({"vllm:request_failure_total": 1}, "failed"),
        ({"vllm:generation_tokens_total": 111}, "asked for"),
        ({"fusioninfer:prefix_hit_tokens_total": 0}, "prefix"),
        ({"fusioninfer:sched_chunks_total": 0}, "chunk"),
    ])
    def test_metrics_gate(self, monkeypatch, over, needle):
        page = self._metrics_page(**over)
        monkeypatch.setattr(chip_smoke, "http",
                            lambda base, path, **kw: (200, page))
        if needle is None:
            chip_smoke.check_metrics(chip_smoke.DRY, "t", "http://x", 112)
        else:
            with pytest.raises(chip_smoke.SmokeFailure, match=needle):
                chip_smoke.check_metrics(chip_smoke.DRY, "t", "http://x", 112)

    def test_uneven_mesh_memory_fails(self, monkeypatch):
        def health(used):
            return lambda base, path, **kw: (200, {"engine": {"devices": [
                {"id": i, "bytes_in_use": u, "peak_bytes_in_use": u,
                 "bytes_limit": 16 << 30} for i, u in enumerate(used)]}})

        monkeypatch.setattr(chip_smoke, "http", health([100, 96, 99, 98]))
        chip_smoke.check_memory(chip_smoke.CHIP, "t", "http://x", 4)
        # everything quietly resident on device 0
        monkeypatch.setattr(chip_smoke, "http", health([400, 96, 99, 98]))
        with pytest.raises(chip_smoke.SmokeFailure, match="bytes_in_use"):
            chip_smoke.check_memory(chip_smoke.CHIP, "t", "http://x", 4)

    def test_dead_server_child_fails_with_its_last_lines(self, monkeypatch,
                                                         tmp_path):
        monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
        server = chip_smoke.Server(chip_smoke.DRY, "dead", [], 1)
        server.cmd = [sys.executable, "-c",
                      "import sys; print('warmup refused'); sys.exit(3)"]
        server.start()
        try:
            with pytest.raises(chip_smoke.SmokeFailure) as e:
                server.wait_ready(deadline=float("inf"))
        finally:
            server.kill()
        assert "exited 3" in str(e.value) and "warmup refused" in str(e.value)


def test_cpu_dry_mode_end_to_end(tmp_path):
    """The identical control flow on the CPU: a real ``engine serve``
    child (qwen3-tiny, interpret kernels), every request kind, the
    metrics gate, SIGTERM drain — and a result that says platform=cpu,
    so it can never be read as a chip result."""
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--cpu-dry-run"], cwd=REPO,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    # (count follows the tier's virtual-device XLA_FLAGS the child inherits)
    assert result["ok"] is True and set(result) == {"ok", "device"}
    assert result["device"]["platform"] == "cpu"
    assert set(result["device"]) == {"platform", "kind", "count"}
    out = proc.stdout
    assert "platform=cpu" in out and "interpret=True" in out
    assert "attention=flash" in out and "grid=coalesced" in out
    assert "request_failure_total=0" in out
    assert "sigterm_exit_code=0" in out
