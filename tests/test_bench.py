"""Unit tests for bench.py's host-side helpers and the record checker.

Pure-host helpers — no backend is initialized here.
"""

import pytest

import bench


class TestSharedPrefixLoadgen:
    def test_prefix_deterministic_and_shared(self):
        from fusioninfer_tpu.benchmark.loadgen import random_prompt

        a = random_prompt(96, 7)
        b = random_prompt(96, 7)
        assert a == b and len(a) == 96
        assert random_prompt(96, 8) != a

class TestRaggedDecode:
    @pytest.mark.slow  # ~8 s full ragged decode drive; the bench
    # record checks keep this surface gated in tier-1 (870 s budget)
    def test_ragged_prefix_lens_decode(self):
        """run_decode's ragged mode (the long-context TPU leg, r5): every
        batch row decodes from its own context depth; throughput must be
        finite and the allocator must fit the stratified lengths."""
        import jax

        import bench as bench_mod
        from fusioninfer_tpu.engine.kv_cache import CacheConfig
        from fusioninfer_tpu.models.config import get_preset

        cfg = get_preset("qwen3-tiny")
        lens = [16, 40, 70, 100]
        cc = CacheConfig(
            n_pages=bench_mod.decode_pool_pages(lens, 1, 4, 64, reps=1),
            page_size=64, max_pages_per_seq=4)
        r = bench_mod.run_decode(jax, cfg, 4, cc, 0, 1, 4, reps=1,
                                 prefix_lens=lens)
        assert r["tok_s"] > 0


class TestStratifiedLensGuard:
    def test_batch_one_does_not_divide_by_zero(self):
        """The long-context stratified-lengths divisor: a
        batch == 1 TPU leg must produce a valid single-length list —
        exercised through the bench helper main() actually calls."""
        assert bench.stratified_lens(1, 128 * 16, 200) == [256]

    def test_strata_span_base_to_cap(self):
        lens = bench.stratified_lens(32, 128 * 16, 200)
        assert len(lens) == 32
        assert lens[0] == 256 and lens[-1] == 128 * 16 - 200
        assert lens == sorted(lens)


class TestBenchRecordChecker:
    """tools/check_bench_record.py gates the CPU bench smoke on the
    serving-path-gap fields (make bench-smoke / CI)."""

    def _good(self):
        return {"kernel_microbench": {
            "ragged": {"calls_per_s": 10.0, "rel_iqr": 0.01},
            "gather": {"calls_per_s": 5.0, "rel_iqr": 0.01},
            "ragged_vs_gather": 2.0,
            "mfu_box": 0.3,
            "longctx": {
                "kvsplit_vs_singlewalk": 2.1,
                "kvsplit_kernel_ok": True,
                "contexts": {
                    "4096": {"singlewalk": {"calls_per_s": 9.0,
                                            "rel_iqr": 0.02},
                             "kvsplit": {"calls_per_s": 19.0,
                                         "rel_iqr": 0.02},
                             "kvsplit_vs_singlewalk": 2.1},
                    "32768": {"singlewalk": {"calls_per_s": 1.0,
                                             "rel_iqr": 0.02},
                              "kvsplit": {"calls_per_s": 2.1,
                                          "rel_iqr": 0.02},
                              "kvsplit_vs_singlewalk": 2.1},
                },
            },
        }, "config_ladder": [
            {"model": "qwen3-1.7b", "quantization": "none",
             "fits_v5e_16gib": True, "dry_run": True},
            {"model": "qwen3-8b", "quantization": "int8",
             "weights_gib": 7.63, "fits_v5e_16gib": True,
             "dry_run": True},
        ], "http": {
            "ceiling_fraction": 0.4,
            "weight_passes_per_step": 1.05,
            "fused_sampling": {"enabled": True, "steps": 120,
                               "load_top_k": 40, "rides_burst": False},
            "decode_burst": 1,
            "queue_wait_ms": {"p50": 1.0, "p90": 2.0, "max": 3.0},
            "scheduler": {"token_budget": 64, "budget_utilization": 0.5,
                          "burst_span_steps": {"1": 3},
                          "burst_clamped": 1,
                          "fused_steps": 7, "weight_passes": 21,
                          "deadline_shed": 0, "tier_preemptions": 0,
                          "preempt_parks": 0, "preempt_resumes": 0},
        }, "workload_sharedprefix": {
            "prefix_cache_hit_rate": 0.5,
            "cold_ttft_ms": {"p50": 500.0, "p90": 520.0},
            "warm_ttft_ms": {"p50": 120.0, "p90": 300.0},
            "warm_faster": True,
            "host_tier": {"offloads": 250, "restores": 90,
                          "host_hits": 90, "corrupt_dropped": 0,
                          "evictions": 0},
        }, "workload_sharedprefix_tp": {
            "tensor_parallel": 2,
            "prefix_cache_hit_rate": 0.5,
            "cold_ttft_ms": {"p50": 700.0, "p90": 900.0},
            "warm_ttft_ms": {"p50": 200.0, "p90": 400.0},
            "warm_faster": True,
            "host_tier": {"offloads": 200, "restores": 80,
                          "host_hits": 80, "corrupt_dropped": 0,
                          "evictions": 0},
        }}

    def test_complete_record_passes(self):
        from tools.check_bench_record import check_record

        assert check_record(self._good()) == []

    def test_missing_fields_flagged(self):
        from tools.check_bench_record import check_record

        rec = self._good()
        del rec["http"]["ceiling_fraction"]
        del rec["http"]["scheduler"]["token_budget"]
        del rec["http"]["scheduler"]["preempt_parks"]
        problems = check_record(rec)
        assert any("ceiling_fraction" in p for p in problems)
        assert any("token_budget" in p for p in problems)
        assert any("preempt_parks" in p for p in problems)

    def test_missing_fused_evidence_flagged(self):
        """The fused-step evidence fields (weight_passes_per_step +
        scheduler.fused_steps/weight_passes) gate the smoke like the
        round-5 ceiling_fraction fields do."""
        from tools.check_bench_record import check_record

        rec = self._good()
        del rec["http"]["weight_passes_per_step"]
        del rec["http"]["scheduler"]["fused_steps"]
        del rec["http"]["scheduler"]["weight_passes"]
        problems = check_record(rec)
        assert any("weight_passes_per_step" in p for p in problems)
        assert any("scheduler.fused_steps" in p for p in problems)
        assert any("scheduler.weight_passes" in p for p in problems)

    def test_missing_kernel_microbench_flagged(self):
        """The ragged-kernel leg (r06): dispersion + the ratio field
        + mfu_box must land in every record."""
        from tools.check_bench_record import check_record

        rec = self._good()
        del rec["kernel_microbench"]
        assert any("kernel_microbench" in p for p in check_record(rec))
        rec = self._good()
        del rec["kernel_microbench"]["ragged_vs_gather"]
        del rec["kernel_microbench"]["mfu_box"]
        del rec["kernel_microbench"]["ragged"]["rel_iqr"]
        problems = check_record(rec)
        assert any("ragged_vs_gather" in p for p in problems)
        assert any("mfu_box" in p for p in problems)
        assert any("rel_iqr" in p for p in problems)

    def test_sharedprefix_leg_required_with_http(self):
        """The hierarchical-KV leg (r08): hit rate must be OFF 0.0,
        warm turns must beat cold turns, and the host tier's
        offload/restore/hit counters must be nonzero."""
        from tools.check_bench_record import check_record

        rec = self._good()
        del rec["workload_sharedprefix"]
        assert any("workload_sharedprefix leg missing" in p
                   for p in check_record(rec))
        rec = self._good()
        rec["workload_sharedprefix"]["error"] = "boom"
        assert any("errored" in p for p in check_record(rec))

    def test_sharedprefix_zero_hit_rate_flagged(self):
        from tools.check_bench_record import check_record

        rec = self._good()
        rec["workload_sharedprefix"]["prefix_cache_hit_rate"] = 0.0
        assert any("prefix_cache_hit_rate" in p for p in check_record(rec))

    def test_sharedprefix_warm_must_beat_cold(self):
        from tools.check_bench_record import check_record

        rec = self._good()
        rec["workload_sharedprefix"]["warm_faster"] = False
        assert any("warm-turn" in p for p in check_record(rec))
        rec = self._good()
        del rec["workload_sharedprefix"]["warm_ttft_ms"]
        assert any("warm_ttft_ms" in p for p in check_record(rec))

    def test_sharedprefix_tier_counters_gated(self):
        from tools.check_bench_record import check_record

        for counter in ("offloads", "restores", "host_hits"):
            rec = self._good()
            rec["workload_sharedprefix"]["host_tier"][counter] = 0
            assert any(counter in p for p in check_record(rec)), counter
        rec = self._good()
        del rec["workload_sharedprefix"]["host_tier"]
        assert any("host_tier" in p for p in check_record(rec))

    def test_tp_sharedprefix_leg_gated(self):
        """The tp=2 leg carries the same sharedprefix contract plus the
        tensor_parallel tag — MULTICHIP evidence past the smoke dryrun."""
        from tools.check_bench_record import check_record

        rec = self._good()
        del rec["workload_sharedprefix_tp"]
        assert any("workload_sharedprefix_tp leg missing" in p
                   for p in check_record(rec))
        rec = self._good()
        rec["workload_sharedprefix_tp"]["prefix_cache_hit_rate"] = 0.0
        assert any("workload_sharedprefix_tp.prefix_cache_hit_rate" in p
                   for p in check_record(rec))
        rec = self._good()
        rec["workload_sharedprefix_tp"]["tensor_parallel"] = 1
        assert any("tensor_parallel must be 2" in p
                   for p in check_record(rec))

    def test_decode_only_run_is_exempt(self):
        """BENCH_SKIP_HTTP=1 records have no http leg by design — the
        checker must not fail the http fields on them; an errored bench
        still flags, and the kernel microbench + config ladder are
        required regardless (both run before the http legs)."""
        from tools.check_bench_record import check_record

        assert check_record(
            {"value": 1.0,
             "kernel_microbench": self._good()["kernel_microbench"],
             "config_ladder": self._good()["config_ladder"]}) == []
        assert check_record({"error": "boom"}) == ["bench errored: boom"]
        assert check_record({"value": 1.0}) == [
            "kernel_microbench leg missing", "config_ladder missing"]

    def test_longctx_stratum_gated(self):
        """The flash-decode leg (r15): the longctx stratum must be
        present with the 32k shape, a >= 1 speedup, dispersion on both
        legs, and the kernel-agreement probe green."""
        from tools.check_bench_record import check_record

        rec = self._good()
        del rec["kernel_microbench"]["longctx"]
        assert any("longctx stratum missing" in p for p in
                   check_record(rec))
        rec = self._good()
        rec["kernel_microbench"]["longctx"]["kvsplit_vs_singlewalk"] = 0.9
        assert any("kvsplit_vs_singlewalk" in p for p in
                   check_record(rec))
        rec = self._good()
        del rec["kernel_microbench"]["longctx"]["contexts"]["32768"]
        assert any("32768" in p for p in check_record(rec))
        rec = self._good()
        del rec["kernel_microbench"]["longctx"]["contexts"]["4096"][
            "kvsplit"]["rel_iqr"]
        assert any("dispersion" in p for p in check_record(rec))
        rec = self._good()
        rec["kernel_microbench"]["longctx"]["kvsplit_kernel_ok"] = False
        assert any("kvsplit_kernel_ok" in p for p in check_record(rec))

    def test_config_ladder_gated(self):
        """The README's Qwen3-8B-int8 rung must exist and fit a v5e."""
        from tools.check_bench_record import check_record

        rec = self._good()
        rec["config_ladder"] = [rec["config_ladder"][0]]
        assert any("qwen3-8b int8 rung" in p for p in check_record(rec))
        rec = self._good()
        rec["config_ladder"][1]["fits_v5e_16gib"] = False
        assert any("fit a 16 GiB" in p for p in check_record(rec))

    def test_fused_sampling_evidence_gated(self):
        """A burst-1 engine with fused sampling enabled must have
        sampled through the fused path; burst engines are exempt (their
        in-scan sampler is a different animal)."""
        from tools.check_bench_record import check_record

        rec = self._good()
        del rec["http"]["fused_sampling"]
        assert any("fused_sampling evidence missing" in p
                   for p in check_record(rec))
        rec = self._good()
        rec["http"]["fused_sampling"]["steps"] = 0
        assert any("fused_sampling.steps" in p for p in check_record(rec))
        rec = self._good()
        rec["http"]["fused_sampling"]["steps"] = 0
        rec["http"]["decode_burst"] = 8
        assert not any("fused_sampling" in p for p in check_record(rec))
