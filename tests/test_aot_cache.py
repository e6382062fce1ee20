"""AOT warm start: cache placement, keying, warmup accounting, metrics.

The warm-start contract (docs/design/parallelism.md): the cache lives
where ``JAX_COMPILATION_CACHE_DIR`` says, else at one fixed git-ignored
path in the checkout; a fingerprint covers everything that changes the
compiled executables; a warmup's manifest turns a twin pod's build into
a load (hits, ~zero build seconds); a warm-up error is fatal on the
serve path; and the ``fusioninfer:aot_cache_*`` /
``cold_start_to_first_token_s`` metrics are what ``chip_smoke.py`` and
the fleetsim gate read.  Cold-versus-warm on a device is
``chip_smoke.py`` twice against one directory."""

import json
import os
import subprocess

import pytest

from fusioninfer_tpu.engine import aot
from fusioninfer_tpu.engine.engine import NativeEngine
from fusioninfer_tpu.engine.kv_cache import CacheConfig
from fusioninfer_tpu.engine.metrics import EngineMetrics
from fusioninfer_tpu.models.config import get_preset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# spelled in pieces so this file is not itself a hit for the grep below
RETIRED_KNOB = "FUSIONINFER_" + "AOT_CACHE"
RETIRED_DEFAULT = "fusioninfer-" + "xla-cache"


def tiny_engine(**kw):
    kw.setdefault("cache_cfg", CacheConfig(n_pages=17, page_size=32,
                                           max_pages_per_seq=2))
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("token_budget", 32)
    kw.setdefault("decode_burst_steps", 1)
    kw.setdefault("fused_step", True)
    return NativeEngine(get_preset("qwen3-tiny"), **kw)


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    """A cache directory placed the only way there is: from outside,
    through jax's own variable.  The process's jax config is restored
    so the rest of the session keeps the tier's cache."""
    import jax

    prior = jax.config.jax_compilation_cache_dir
    path = str(tmp_path / "aot")
    monkeypatch.setenv(aot.ENV_CACHE_DIR, path)
    yield path
    jax.config.update("jax_compilation_cache_dir", prior)


class TestCacheResolution:
    def test_env_wins_and_default_is_fixed_in_checkout(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        # the retired knob is not consulted any more
        monkeypatch.setenv(RETIRED_KNOB, "/tmp/retired-knob")
        assert aot.resolve_cache_dir() == "/some/dir"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert aot.resolve_cache_dir() == aot.DEFAULT_CACHE_DIR
        # fixed: a function of where the checkout is, nothing else
        assert aot.DEFAULT_CACHE_DIR == os.path.join(REPO, ".xla_cache")

    def test_default_dir_is_git_ignored(self):
        probe = os.path.join(aot.DEFAULT_CACHE_DIR, "jit_x-cache")
        rc = subprocess.run(["git", "check-ignore", "-q", probe],
                            cwd=REPO).returncode
        assert rc == 0, ".xla_cache/ must be listed in .gitignore"

    def test_configured_dir_holds_cache_and_manifest(self, cache_dir):
        assert aot.configure_cache() == cache_dir
        report = aot.warmup(tiny_engine(),
                            signatures=[("ok/one", lambda: None)])
        assert report["cache_dir"] == cache_dir
        assert any(n.startswith("aot-manifest-")
                   for n in os.listdir(cache_dir))

    def test_unusable_named_dir_is_an_error(self, tmp_path, monkeypatch):
        blocker = tmp_path / "a-file"
        blocker.write_text("not a directory")
        monkeypatch.setenv(aot.ENV_CACHE_DIR, str(blocker / "cache"))
        with pytest.raises(RuntimeError, match="JAX_COMPILATION_CACHE_DIR"):
            aot.configure_cache()

    def test_unusable_default_degrades_to_uncached(self, tmp_path,
                                                   monkeypatch):
        blocker = tmp_path / "a-file"
        blocker.write_text("not a directory")
        monkeypatch.delenv(aot.ENV_CACHE_DIR, raising=False)
        monkeypatch.setattr(aot, "DEFAULT_CACHE_DIR",
                            str(blocker / "cache"))
        assert aot.configure_cache() is None

    def test_no_temporary_cache_names_in_tracked_code(self):
        """The path never comes from a temporary name, a pid or the
        time, and the retired knob and default are gone from everything
        git tracks (the operator's metrics TLS dir is not a compile
        cache)."""
        out = subprocess.run(
            ["git", "grep", "-n", "-e", "mkd" + "temp", "-e", RETIRED_KNOB,
             "-e", RETIRED_DEFAULT, "--", "*.py", "Makefile"],
            cwd=REPO, capture_output=True, text=True).stdout
        hits = [ln for ln in out.splitlines()
                if "fusioninfer-metrics-tls-" not in ln]
        assert hits == []


class TestFingerprint:
    def test_registry_signature_is_stable(self):
        a, b = aot.registry_signature(), aot.registry_signature()
        assert a == b and len(a) == 16

    def test_fingerprint_covers_engine_knobs(self):
        e1 = tiny_engine()
        e2 = tiny_engine(max_batch_size=4)
        assert aot.fingerprint(e1) == aot.fingerprint(e1)
        assert aot.fingerprint(e1) != aot.fingerprint(e2)

    def test_fingerprint_covers_axis_rules(self, monkeypatch):
        """An axis-rules change must invalidate persisted executables
        — the rules fingerprint rides the cache key."""
        from fusioninfer_tpu.parallel import axes

        e = tiny_engine()
        before = aot.fingerprint(e)
        monkeypatch.setattr(
            axes, "MEGATRON_RULES",
            axes.MEGATRON_RULES.with_overrides(heads=None))
        monkeypatch.setattr(axes, "default_rules",
                            lambda: axes.MEGATRON_RULES)
        assert aot.fingerprint(e) != before


class TestSignatures:
    def test_signature_names_cover_the_serving_paths(self):
        e = tiny_engine()
        names = [n for n, _ in e.aot_signatures()]
        assert any(n.startswith("prefill/") for n in names)
        # the one ragged forward at its three LIVE selector shapes:
        # split decode (chunk_rows=0), chunk-only (batched suffix /
        # chunk advance), and — on this fused burst-1 engine — mixed
        assert any(n.startswith("fused/decode-") for n in names)
        assert any(n.startswith("fused/chunk-") for n in names)
        assert any(n.startswith("fused/mixed-") for n in names)
        assert any(n.startswith("sample/") for n in names)
        # burst-1 engine: no burst entries
        assert not any(n.startswith("burst/") for n in names)

    def test_burst_engine_skips_mixed_fused(self):
        # burst engines never run the fused mixed step (split
        # dispatch-ahead path) — but chunk advances still ride the
        # ragged forward, so the chunk-only shapes stay covered
        e = tiny_engine(decode_burst_steps=4, fused_step=False)
        names = [n for n, _ in e.aot_signatures()]
        assert not any(n.startswith("fused/mixed-") for n in names)
        assert any(n.startswith("fused/chunk-") for n in names)

    def test_burst_engine_adds_burst_spans(self):
        e = tiny_engine(decode_burst_steps=4, fused_step=False)
        names = [n for n, _ in e.aot_signatures()]
        assert "burst/s1-plain" in names and "burst/s4-plain" in names
        assert "burst/s1-greedy" in names and "burst/s4-greedy" in names

    def test_prefill_entries_follow_bucket_and_group_discipline(self):
        names = {n for n, _ in tiny_engine(token_budget=None).aot_signatures()}
        # buckets [32, 64] x pow2 groups {1, 2}
        for bucket in (32, 64):
            for rows in (1, 2):
                assert f"prefill/b{bucket}r{rows}" in names
        # under a token budget of 32 a prompt of 33-64 tokens is chunked
        # and never prefilled whole: its bucket is not built
        names = {n for n, _ in tiny_engine().aot_signatures()}
        assert {"prefill/b32r1", "prefill/b32r2"} <= names
        assert not any(n.startswith("prefill/b64") for n in names)


class TestWarmup:
    def test_cold_build_then_twin_hits(self, cache_dir, tmp_path):
        e = tiny_engine()
        cold = aot.warmup(e)
        assert cold["misses"] == cold["entries"] > 0
        assert cold["hits"] == 0 and cold["errors"] == []
        assert e.aot_stats is cold
        manifest = json.loads(
            (tmp_path / "aot" /
             f"aot-manifest-{cold['fingerprint'][:16]}.json").read_text())
        assert manifest["fingerprint"] == cold["fingerprint"]
        assert len(manifest["entries"]) == cold["entries"]
        # a twin engine (same fingerprint) loads instead of building
        twin = tiny_engine()
        warm = aot.warmup(twin)
        assert warm["hits"] == cold["entries"] and warm["misses"] == 0
        # the load is not a rebuild: orders of magnitude cheaper
        assert warm["build_seconds"] < max(1.0, cold["build_seconds"] / 3)

    def test_fingerprint_drift_misses(self, cache_dir):
        aot.warmup(tiny_engine())
        drifted = aot.warmup(tiny_engine(max_batch_size=4))
        assert drifted["hits"] == 0 and drifted["misses"] > 0

    def test_force_rebuilds_hits(self, cache_dir):
        aot.warmup(tiny_engine())
        forced = aot.warmup(tiny_engine(), force=True)
        assert forced["hits"] == 0 and forced["misses"] == forced["entries"]

    def test_report_names_every_bad_signature(self, cache_dir):
        def boom():
            raise RuntimeError("lowering exploded")

        e = tiny_engine()
        report = aot.warmup(
            e, signatures=[("ok/trivial", lambda: None), ("bad/boom", boom),
                           ("bad/boom2", boom)])
        assert report["entries"] == 1
        assert len(report["errors"]) == 2
        assert "bad/boom" in report["errors"][0]

    def test_serve_path_warmup_error_is_fatal(self, cache_dir, monkeypatch):
        """``engine serve`` must not open admission over a signature
        the compiler refused: the engine would turn it into a failed
        request and keep serving."""
        import argparse

        from fusioninfer_tpu.engine import server

        def refused(engine):
            return {"entries": 3, "hits": 0, "misses": 3,
                    "errors": ["fused/chunk-t64: MosaicError: no"]}

        monkeypatch.setattr(server, "_engine_from_args",
                            lambda args: (tiny_engine(), "qwen3-tiny"))
        monkeypatch.setattr(aot, "warmup", refused)
        monkeypatch.setattr(
            server.EngineServer, "serve_forever",
            lambda self: pytest.fail("served over a refused signature"))
        with pytest.raises(SystemExit, match="fused/chunk-t64"):
            server.serve_from_args(argparse.Namespace(
                host="127.0.0.1", port=0, aot_warmup=True))

    def test_warmed_engine_streams_identically(self, cache_dir):
        """Warmup must be invisible to outputs: greedy tokens from a
        warmed engine match an unwarmed twin bit-for-bit (AOT lowering
        executes nothing and donates nothing)."""
        from fusioninfer_tpu.engine.engine import Request
        from fusioninfer_tpu.engine.sampler import SamplingParams

        def drain(e):
            e.add_request(Request("r", [3, 1, 4, 1, 5],
                                  SamplingParams(max_tokens=6,
                                                 temperature=0.0)))
            toks = []
            while e.has_work():
                toks += [o.token for o in e.step()]
            return toks

        warmed = tiny_engine()
        aot.warmup(warmed)
        assert drain(warmed) == drain(tiny_engine())


class TestMetricsSurface:
    def test_aot_families_render_after_warmup(self, cache_dir):
        e = tiny_engine()
        aot.warmup(e, signatures=[("ok/one", lambda: None)])
        m = EngineMetrics("tiny")
        text = m.render(e)
        assert "fusioninfer:aot_cache_hits{" in text
        assert "fusioninfer:aot_cache_misses{" in text
        assert "fusioninfer:aot_cache_build_seconds{" in text
        # no first token served yet: the cold-start gauge is absent
        assert "cold_start_to_first_token_s" not in text
        m.cold_start_ttft_s = 3.25
        text = m.render(e)
        assert ("fusioninfer:cold_start_to_first_token_s"
                '{model_name="tiny"} 3.250') in text

    def test_unwarmed_engine_omits_families(self):
        m = EngineMetrics("tiny")
        text = m.render(tiny_engine())
        assert "aot_cache" not in text


class TestServerColdStartGauge:
    def test_first_token_stamps_the_gauge_once(self):
        from fusioninfer_tpu.engine.server import EngineServer

        srv = EngineServer(model="qwen3-tiny", host="127.0.0.1", port=0,
                           engine=tiny_engine(), boot_t0=0.0)
        srv.start()
        try:
            import urllib.request

            body = json.dumps({"model": "qwen3-tiny", "prompt": "hi",
                               "max_tokens": 2}).encode()
            for _ in range(2):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{srv.port}/v1/completions", body,
                    headers={"Content-Type": "application/json"})
                urllib.request.urlopen(req, timeout=120).read()
            first = srv.metrics.cold_start_ttft_s
            assert first is not None and first > 0
            # a later request must NOT move it (boot -> FIRST token)
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/v1/completions", body,
                headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=120).read()
            assert srv.metrics.cold_start_ttft_s == first
        finally:
            srv.stop()

    def test_no_boot_t0_no_gauge(self):
        from fusioninfer_tpu.engine.server import EngineServer

        srv = EngineServer(model="qwen3-tiny", host="127.0.0.1", port=0,
                           engine=tiny_engine())
        assert srv.boot_t0 is None


class TestFleetChecker:
    def test_fleet_checker_gates_warm_start(self):
        from tools.check_fleet_record import check_record

        # minimal record that reaches the warm-start check: assert the
        # new complaints appear when the block is absent vs unbounded
        problems = check_record({"schema": "fleet-v1"})
        assert any("scale_up_warm_start" in p for p in problems)
        rec = {"schema": "fleet-v1",
               "slo": {"scale_up_warm_start": {
                   "pods": {"p": {"ttfst_s": 99.0, "aot_hits": 0}},
                   "ttfst_bound_s": 30.0, "bounded": False,
                   "aot_cache_hits": 0}}}
        problems = check_record(rec)
        assert any("exceeded the bound" in p for p in problems)
        assert any("aot_cache_hits is zero" in p for p in problems)
