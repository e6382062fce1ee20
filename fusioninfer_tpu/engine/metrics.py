"""Prometheus text-format metrics, vLLM-compatible names.

The EPP's scorers (prefix-cache / kv-cache-utilization / queue-size,
``fusioninfer_tpu.router.strategy``) scrape model servers expecting vLLM
metric names; the native engine exports the same family so it is a
drop-in routing target.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional, Sequence


def histogram_quantile(
    bounds: Sequence[float], cumulative: Sequence[float], q: float
) -> Optional[float]:
    """Prometheus ``histogram_quantile`` over cumulative bucket counts.

    ``bounds`` are the finite upper bounds (ascending), ``cumulative`` the
    matching cumulative counts plus one trailing entry for the +Inf
    bucket (``len(cumulative) == len(bounds) + 1``).  Linear
    interpolation inside the target bucket, the lowest bound for the
    first bucket, and the highest finite bound when the quantile lands
    in +Inf — identical conventions to PromQL, so a scraped exposition
    and an in-process :class:`Histogram` answer the same way.  Returns
    ``None`` when the histogram is empty (no observations → no signal).
    """
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile must be in [0, 1], got {q}")
    if len(cumulative) != len(bounds) + 1:
        raise ValueError(
            f"need {len(bounds) + 1} cumulative counts for {len(bounds)} "
            f"bounds, got {len(cumulative)}"
        )
    total = cumulative[-1]
    if total <= 0:
        return None
    rank = q * total
    prev_cum = 0.0
    for i, (bound, cum) in enumerate(zip(bounds, cumulative)):
        if cum >= rank:
            lower = bounds[i - 1] if i > 0 else 0.0
            if cum == prev_cum:  # defensive: malformed non-increasing input
                return bound
            return lower + (bound - lower) * (rank - prev_cum) / (cum - prev_cum)
        prev_cum = cum
    # quantile falls in the +Inf bucket: PromQL returns the highest
    # finite bound rather than inventing a value beyond the histogram
    return bounds[-1] if bounds else None


@dataclass
class Histogram:
    buckets: tuple[float, ...]
    counts: list[int] = field(default_factory=list)
    total: float = 0.0
    n: int = 0

    def __post_init__(self):
        if not self.counts:
            self.counts = [0] * (len(self.buckets) + 1)

    def observe(self, value: float) -> None:
        self.total += value
        self.n += 1
        for i, b in enumerate(self.buckets):
            if value <= b:
                self.counts[i] += 1
                return
        self.counts[-1] += 1

    def quantile(self, q: float) -> Optional[float]:
        """Estimated q-quantile of everything observed so far (None when
        empty).  Feeds the autoscaler's TTFT-p90 signal; interpolation
        matches PromQL so dashboards and scaling decisions agree."""
        cumulative: list[float] = []
        running = 0
        for c in self.counts:
            running += c
            cumulative.append(running)
        return histogram_quantile(self.buckets, cumulative, q)

    def render(self, name: str, labels: str) -> list[str]:
        out = []
        cumulative = 0
        for b, c in zip(self.buckets, self.counts):
            cumulative += c
            out.append(f'{name}_bucket{{{labels},le="{b}"}} {cumulative}')
        cumulative += self.counts[-1]
        out.append(f'{name}_bucket{{{labels},le="+Inf"}} {cumulative}')
        out.append(f"{name}_sum{{{labels}}} {self.total}")
        out.append(f"{name}_count{{{labels}}} {self.n}")
        return out


def _counter(name: str, help_: str, labels: str, value) -> list[str]:
    return [f"# HELP {name} {help_}", f"# TYPE {name} counter",
            f"{name}{{{labels}}} {value}"]


TTFT_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)
TPOT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0)


class EngineMetrics:
    def __init__(self, model_name: str):
        self.model_name = model_name
        self.start_time = time.monotonic()
        self.ttft = Histogram(TTFT_BUCKETS)
        self.tpot = Histogram(TPOT_BUCKETS)
        self.e2e_latency = Histogram((0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0))
        # resilience counters (server-side): PD pulls that degraded to a
        # local re-prefill, and watchdog deadline/stall aborts
        self.kv_transfer_fallbacks = 0
        self.watchdog_aborts = 0
        # AOT warm start: time from process/engine boot to the FIRST
        # token this server ever streamed (None until it happens; the
        # server stamps it once when boot_t0 was provided) — the scale-up
        # latency the warm-start cache exists to shrink
        self.cold_start_ttft_s: float | None = None
        # per-SLO-tier families, keyed by tier name.  register_tiers
        # pre-seeds every dict at server construction so the /metrics
        # exposition (HTTP thread) never iterates a dict a handler
        # thread is resizing.
        self.tier_ttft: dict[str, Histogram] = {}
        self.tier_tpot: dict[str, Histogram] = {}
        self.tier_requests: dict[str, int] = {}
        self.tier_shed: dict[str, int] = {}
        # the stream handlers' spans, chunks and delays (one clock for
        # every handler thread of the server)
        from fusioninfer_tpu.utils import spans

        self.stream = spans.StreamClock()

    def register_tiers(self, names) -> None:
        """Install the per-tier metric families for the server's SLO
        tiers (fixed at construction — tiers never churn mid-serve)."""
        for name in names:
            self.tier_ttft[name] = Histogram(TTFT_BUCKETS)
            self.tier_tpot[name] = Histogram(TPOT_BUCKETS)
            self.tier_requests[name] = 0
            self.tier_shed[name] = 0

    @staticmethod
    def _kv_pages_by_kind(engine, labels: str) -> list[str]:
        """KV pages by layer kind: ``full`` is the model's one pool, or
        the full-attention layers' over a cache kept by layer kind;
        ``window`` exists only there.  A scraper that sums a family's
        samples cannot tell kinds apart, so the window kind's two
        counters also stand under families of their own."""
        alloc = getattr(engine, "alloc", None)
        in_use = getattr(alloc, "pages_in_use", None)
        if in_use is None:
            return []
        allocated = alloc.pages_allocated_total
        lines = [
            "# HELP fusioninfer:kv_pages_in_use KV pages handed out to sequences now, by layer kind (full: the full-attention layers' pool, or the model's one pool; window: the windowed layers' pool of a cache kept by layer kind).",
            "# TYPE fusioninfer:kv_pages_in_use gauge",
            *[f'fusioninfer:kv_pages_in_use{{{labels},kind="{kind}"}} {n}'
              for kind, n in in_use().items()],
            "# HELP fusioninfer:kv_pages_allocated_total KV pages handed out since start, by layer kind; a window-kind page is handed out when a step is about to write it.",
            "# TYPE fusioninfer:kv_pages_allocated_total counter",
            *[f'fusioninfer:kv_pages_allocated_total{{{labels},kind="{kind}"}} {allocated[kind]}'
              for kind in in_use()],
            "# HELP fusioninfer:kv_window_pages_allocated_total The kind=\"window\" sample of fusioninfer:kv_pages_allocated_total under a family of its own.",
            "# TYPE fusioninfer:kv_window_pages_allocated_total counter",
            f"fusioninfer:kv_window_pages_allocated_total{{{labels}}} {allocated['window']}",
            "# HELP fusioninfer:kv_window_pages_trimmed_total Pages released because every position in them fell below the sliding window of the sequence that held them (the windowed layers' pages alone over a cache kept by layer kind).",
            "# TYPE fusioninfer:kv_window_pages_trimmed_total counter",
            f"fusioninfer:kv_window_pages_trimmed_total{{{labels}}} {alloc.window_pages_trimmed_total}",
        ]
        return lines

    def render(self, engine) -> str:
        """Text exposition from live engine state + accumulated histograms."""
        labels = f'model_name="{self.model_name}"'
        lines = [
            "# HELP vllm:num_requests_running Number of requests currently running.",
            "# TYPE vllm:num_requests_running gauge",
            f"vllm:num_requests_running{{{labels}}} {engine.num_running}",
            "# HELP vllm:num_requests_waiting Number of requests waiting to be processed.",
            "# TYPE vllm:num_requests_waiting gauge",
            f"vllm:num_requests_waiting{{{labels}}} {engine.num_waiting}",
            "# HELP fusioninfer:num_requests_prefilling Requests mid-chunked-prefill.",
            "# TYPE fusioninfer:num_requests_prefilling gauge",
            f"fusioninfer:num_requests_prefilling{{{labels}}} {engine.num_prefilling}",
            "# HELP vllm:gpu_cache_usage_perc KV-cache usage (1 = full).",
            "# TYPE vllm:gpu_cache_usage_perc gauge",
            f"vllm:gpu_cache_usage_perc{{{labels}}} {engine.kv_cache_usage():.6f}",
            "# HELP vllm:kv_cache_usage_perc KV-cache usage (1 = full).",
            "# TYPE vllm:kv_cache_usage_perc gauge",
            f"vllm:kv_cache_usage_perc{{{labels}}} {engine.kv_cache_usage():.6f}",
            *self._kv_pages_by_kind(engine, labels),
            "# HELP vllm:prompt_tokens_total Prefill tokens processed.",
            "# TYPE vllm:prompt_tokens_total counter",
            f"vllm:prompt_tokens_total{{{labels}}} {engine.prompt_tokens_total}",
            "# HELP vllm:generation_tokens_total Generation tokens produced.",
            "# TYPE vllm:generation_tokens_total counter",
            f"vllm:generation_tokens_total{{{labels}}} {engine.generation_tokens_total}",
            "# HELP vllm:spec_decode_num_draft_tokens_total Draft tokens proposed by the speculator.",
            "# TYPE vllm:spec_decode_num_draft_tokens_total counter",
            f"vllm:spec_decode_num_draft_tokens_total{{{labels}}} {engine.spec_proposed_total}",
            "# HELP vllm:spec_decode_num_accepted_tokens_total Draft tokens accepted by verification.",
            "# TYPE vllm:spec_decode_num_accepted_tokens_total counter",
            f"vllm:spec_decode_num_accepted_tokens_total{{{labels}}} {engine.spec_accepted_total}",
            "# HELP fusioninfer:fused_sampling_steps_total Decode steps sampled through the fused lm_head top-k path (no [rows, vocab] logits materialized).",
            "# TYPE fusioninfer:fused_sampling_steps_total counter",
            f"fusioninfer:fused_sampling_steps_total{{{labels}}} {getattr(engine, 'fused_sampling_steps_total', 0)}",
            *[line for name, what in (
                ("assignments", "(token, expert) assignments the routers of the expert layers made, over the routers' whole width"),
                ("assignments_local", "Assignments to an expert held by this process, each computed (no capacity, none dropped)"),
                ("expert_touches", "Held experts with at least one row, summed over expert layers and forward passes"),
                ("layer_passes", "Forward passes through an expert layer"),
                ("assignments_zero", "Assignments to an identity (zero-compute) expert: the token's own input, weighted, computed where the token lives with no weights read and nothing exchanged"),
            ) for line in (
                f"# HELP fusioninfer:moe_{name}_total {what}.",
                f"# TYPE fusioninfer:moe_{name}_total counter",
                f"fusioninfer:moe_{name}_total{{{labels}}} {getattr(engine, 'moe_stats_total', {}).get(name, 0)}",
            )],
            *[line for name, what in (
                ("scored", "Cached positions the sparse-attention indexer scored, summed over layers (decode and chunk rows alike)"),
                ("selected", "Positions the sparse attention attended over, summed over layers (at most the indexer's top-k a query)"),
            ) for line in (
                f"# HELP fusioninfer:dsa_positions_{name}_total {what}.",
                f"# TYPE fusioninfer:dsa_positions_{name}_total counter",
                f"fusioninfer:dsa_positions_{name}_total{{{labels}}} {getattr(engine, 'dsa_stats_total', {}).get(name, 0)}",
            )],
            "# HELP vllm:num_preemptions_total Requests preempted to reclaim KV-cache pages.",
            "# TYPE vllm:num_preemptions_total counter",
            f"vllm:num_preemptions_total{{{labels}}} {engine.preemptions_total}",
            "# HELP vllm:request_success_total Requests finished successfully.",
            "# TYPE vllm:request_success_total counter",
            f"vllm:request_success_total{{{labels}}} {engine.finished_total}",
            "# HELP vllm:request_failure_total Requests finished with an error.",
            "# TYPE vllm:request_failure_total counter",
            f"vllm:request_failure_total{{{labels}}} {engine.errors_total}",
            "# HELP vllm:request_cancelled_total Requests cancelled by the client.",
            "# TYPE vllm:request_cancelled_total counter",
            f"vllm:request_cancelled_total{{{labels}}} {engine.cancelled_total}",
            "# HELP fusioninfer:kv_transfer_fallbacks_total PD pulls degraded to a local re-prefill.",
            "# TYPE fusioninfer:kv_transfer_fallbacks_total counter",
            f"fusioninfer:kv_transfer_fallbacks_total{{{labels}}} {self.kv_transfer_fallbacks}",
            "# HELP fusioninfer:watchdog_aborts_total requests aborted by the deadline/stall watchdog.",
            "# TYPE fusioninfer:watchdog_aborts_total counter",
            f"fusioninfer:watchdog_aborts_total{{{labels}}} {self.watchdog_aborts}",
            "# HELP vllm:gpu_prefix_cache_hit_rate fraction of prompt tokens served from cached prefix pages.",
            "# TYPE vllm:gpu_prefix_cache_hit_rate gauge",
            f"vllm:gpu_prefix_cache_hit_rate{{{labels}}} {engine.prefix_cache_hit_rate():.6f}",
            "# HELP vllm:time_to_first_token_seconds Time from request arrival to first emitted token.",
            "# TYPE vllm:time_to_first_token_seconds histogram",
            *self.ttft.render("vllm:time_to_first_token_seconds", labels),
            "# HELP vllm:time_per_output_token_seconds Per-token decode latency after the first token.",
            "# TYPE vllm:time_per_output_token_seconds histogram",
            *self.tpot.render("vllm:time_per_output_token_seconds", labels),
            "# HELP vllm:e2e_request_latency_seconds End-to-end request latency.",
            "# TYPE vllm:e2e_request_latency_seconds histogram",
            *self.e2e_latency.render("vllm:e2e_request_latency_seconds", labels),
        ]
        lines += self._render_slo_tiers(labels)
        lines += self._render_kv_tiers(engine, labels)
        lines += self._render_kv_fabric(engine, labels)
        lines += self._render_evacuation(engine, labels)
        lines += self._render_scheduler(engine, labels)
        lines += self._render_host(engine, labels)
        lines += self._render_stream(labels)
        lines += self._render_aot(engine, labels)
        return "\n".join(lines) + "\n"

    def _render_aot(self, engine, labels: str) -> list[str]:
        """AOT warm-start families (docs/design/parallelism.md): the
        warmup's cache accounting plus the boot→first-token gauge.
        Engines that never ran a warmup simply omit the families."""
        stats = getattr(engine, "aot_stats", None) or {}
        lines: list[str] = []
        if stats:
            lines += [
                "# HELP fusioninfer:aot_cache_hits Warmup entry points whose compiled executable was persisted by a prior same-fingerprint build.",
                "# TYPE fusioninfer:aot_cache_hits gauge",
                f"fusioninfer:aot_cache_hits{{{labels}}} {stats.get('hits', 0)}",
                "# HELP fusioninfer:aot_cache_misses Warmup entry points compiled fresh (no persisted twin).",
                "# TYPE fusioninfer:aot_cache_misses gauge",
                f"fusioninfer:aot_cache_misses{{{labels}}} {stats.get('misses', 0)}",
                "# HELP fusioninfer:aot_cache_build_seconds Wall time the pre-admission warmup spent lowering + compiling (small when warm).",
                "# TYPE fusioninfer:aot_cache_build_seconds gauge",
                f"fusioninfer:aot_cache_build_seconds{{{labels}}} {stats.get('build_seconds', 0.0)}",
            ]
        if self.cold_start_ttft_s is not None:
            lines += [
                "# HELP fusioninfer:cold_start_to_first_token_s Seconds from engine boot to the first token this server ever streamed.",
                "# TYPE fusioninfer:cold_start_to_first_token_s gauge",
                f"fusioninfer:cold_start_to_first_token_s{{{labels}}} {self.cold_start_ttft_s:.3f}",
            ]
        return lines

    def _render_slo_tiers(self, labels: str) -> list[str]:
        """Per-SLO-tier families (docs/design/scheduler.md "Overload
        and SLO tiers"): TTFT/TPOT histograms, admission counts, and
        the 429 backpressure sheds, labeled by tier name.  Servers
        without tiers configured simply omit the families."""
        if not self.tier_ttft:
            return []
        lines = [
            "# HELP fusioninfer:tier_requests_total Requests admitted per SLO tier.",
            "# TYPE fusioninfer:tier_requests_total counter",
        ]
        for name in sorted(self.tier_requests):
            lines.append(
                f'fusioninfer:tier_requests_total{{{labels},slo_tier="{name}"}} '
                f"{self.tier_requests[name]}")
        lines += [
            "# HELP fusioninfer:tier_shed_total Requests shed with 429 + Retry-After per SLO tier (queue past its bound).",
            "# TYPE fusioninfer:tier_shed_total counter",
        ]
        for name in sorted(self.tier_shed):
            lines.append(
                f'fusioninfer:tier_shed_total{{{labels},slo_tier="{name}"}} '
                f"{self.tier_shed[name]}")
        lines += [
            "# HELP fusioninfer:tier_ttft_seconds Time to first token per SLO tier.",
            "# TYPE fusioninfer:tier_ttft_seconds histogram",
        ]
        for name in sorted(self.tier_ttft):
            lines += self.tier_ttft[name].render(
                "fusioninfer:tier_ttft_seconds",
                f'{labels},slo_tier="{name}"')
        lines += [
            "# HELP fusioninfer:tier_tpot_seconds Per-token decode latency per SLO tier.",
            "# TYPE fusioninfer:tier_tpot_seconds histogram",
        ]
        for name in sorted(self.tier_tpot):
            lines += self.tier_tpot[name].render(
                "fusioninfer:tier_tpot_seconds",
                f'{labels},slo_tier="{name}"')
        return lines

    @staticmethod
    def _render_kv_tiers(engine, labels: str) -> list[str]:
        """Hierarchical-KV families (docs/design/kv-hierarchy.md):
        per-tier prefix-block residency (the routing signal the EPP's
        residency scorer coarse-checks before fetching the digest) and,
        when a host tier is wired, its offload/restore/corruption
        counters.  Engines predating the hierarchy (test stubs) simply
        omit the families."""
        residency = getattr(engine, "prefix_residency", None)
        if residency is None:
            return []
        tiers = residency(limit=0)["tiers"]
        lines = [
            "# HELP fusioninfer:prefix_blocks_resident Content-addressed prefix KV blocks resident per tier.",
            "# TYPE fusioninfer:prefix_blocks_resident gauge",
            f'fusioninfer:prefix_blocks_resident{{{labels},tier="hbm"}} {tiers["hbm"]}',
            f'fusioninfer:prefix_blocks_resident{{{labels},tier="host"}} {tiers["host"]}',
        ]
        alloc = getattr(engine, "alloc", None)
        if alloc is not None and hasattr(alloc, "query_tokens_total"):
            # raw counter pair behind vllm:gpu_prefix_cache_hit_rate —
            # the lifetime ratio can't be windowed, so fleet-level
            # harnesses (fusioninfer_tpu.fleetsim) diff these per phase
            # to report a per-phase hit rate across engine generations
            lines += [
                "# HELP fusioninfer:prefix_query_tokens_total Prompt tokens presented to the prefix cache.",
                "# TYPE fusioninfer:prefix_query_tokens_total counter",
                f"fusioninfer:prefix_query_tokens_total{{{labels}}} {alloc.query_tokens_total}",
                "# HELP fusioninfer:prefix_hit_tokens_total Prompt tokens served from cached prefix pages.",
                "# TYPE fusioninfer:prefix_hit_tokens_total counter",
                f"fusioninfer:prefix_hit_tokens_total{{{labels}}} {alloc.hit_tokens_total}",
            ]
        tier = getattr(engine, "host_kv_tier", None)
        if tier is None:
            return lines
        c = tier.counters()
        lines += [
            "# HELP fusioninfer:kv_host_offloads_total KV pages offloaded HBM -> host tier.",
            "# TYPE fusioninfer:kv_host_offloads_total counter",
            f"fusioninfer:kv_host_offloads_total{{{labels}}} {c['offloads']}",
            "# HELP fusioninfer:kv_host_restores_total KV pages restored host tier -> HBM.",
            "# TYPE fusioninfer:kv_host_restores_total counter",
            f"fusioninfer:kv_host_restores_total{{{labels}}} {c['restores']}",
            "# HELP fusioninfer:kv_host_hits_total Host-tier lookups that served a page.",
            "# TYPE fusioninfer:kv_host_hits_total counter",
            f"fusioninfer:kv_host_hits_total{{{labels}}} {c['host_hits']}",
            "# HELP fusioninfer:kv_host_evictions_total Host-tier entries evicted at the byte-capacity watermark.",
            "# TYPE fusioninfer:kv_host_evictions_total counter",
            f"fusioninfer:kv_host_evictions_total{{{labels}}} {c['evictions']}",
            "# HELP fusioninfer:kv_host_corrupt_dropped_total Host-tier frames CRC-rejected at restore and dropped (prefix recomputed).",
            "# TYPE fusioninfer:kv_host_corrupt_dropped_total counter",
            f"fusioninfer:kv_host_corrupt_dropped_total{{{labels}}} {c['corrupt_dropped']}",
            "# HELP fusioninfer:kv_host_offload_failed_total Offloads dropped before commit (injected or real serialization faults).",
            "# TYPE fusioninfer:kv_host_offload_failed_total counter",
            f"fusioninfer:kv_host_offload_failed_total{{{labels}}} {c['offload_failed']}",
            "# HELP fusioninfer:kv_host_tier_bytes Host-tier slab pool bytes in use.",
            "# TYPE fusioninfer:kv_host_tier_bytes gauge",
            f"fusioninfer:kv_host_tier_bytes{{{labels}}} {c['bytes_used']}",
            "# HELP fusioninfer:kv_host_imported_total Frames adopted from an evacuating peer's host tier.",
            "# TYPE fusioninfer:kv_host_imported_total counter",
            f"fusioninfer:kv_host_imported_total{{{labels}}} {c['imported']}",
            "# HELP fusioninfer:kv_host_import_rejected_total Peer frames rejected at import (CRC/parse failure).",
            "# TYPE fusioninfer:kv_host_import_rejected_total counter",
            f"fusioninfer:kv_host_import_rejected_total{{{labels}}} {c['import_rejected']}",
        ]
        return lines

    @staticmethod
    def _render_kv_fabric(engine, labels: str) -> list[str]:
        """KV-fabric families (docs/design/pd-disaggregation.md): the
        layer-streamed PD transfer's frame/byte/overlap accounting and
        the cross-engine prefix-pull counters.  The overlap gauge is the
        streamed-vs-slab A/B's figure of merit — payload bytes that
        crossed the wire while the prefiller was still computing,
        divided by all streamed payload bytes (slab transfers read 0).
        Engines predating the fabric (test stubs) omit the families."""
        if not hasattr(engine, "kv_stream_frames_total"):
            return []
        total = engine.kv_stream_bytes_total
        overlap = (engine.kv_stream_overlapped_bytes_total / total
                   if total else 0.0)
        lines = [
            "# HELP fusioninfer:kv_stream_frames_total Layer-streamed PD frames adopted by this decode engine.",
            "# TYPE fusioninfer:kv_stream_frames_total counter",
            f"fusioninfer:kv_stream_frames_total{{{labels}}} {engine.kv_stream_frames_total}",
            "# HELP fusioninfer:kv_stream_bytes_total KV payload bytes received over streamed PD transfers.",
            "# TYPE fusioninfer:kv_stream_bytes_total counter",
            f"fusioninfer:kv_stream_bytes_total{{{labels}}} {engine.kv_stream_bytes_total}",
            "# HELP fusioninfer:kv_stream_overlapped_bytes_total Streamed KV payload bytes that arrived while the prefiller was still computing.",
            "# TYPE fusioninfer:kv_stream_overlapped_bytes_total counter",
            f"fusioninfer:kv_stream_overlapped_bytes_total{{{labels}}} {engine.kv_stream_overlapped_bytes_total}",
            "# HELP fusioninfer:kv_stream_transfer_overlap_fraction Lifetime fraction of streamed KV payload hidden behind prefill compute.",
            "# TYPE fusioninfer:kv_stream_transfer_overlap_fraction gauge",
            f"fusioninfer:kv_stream_transfer_overlap_fraction{{{labels}}} {overlap:.6f}",
            "# HELP fusioninfer:kv_stream_admissions_total Requests admitted from a complete PD frame stream.",
            "# TYPE fusioninfer:kv_stream_admissions_total counter",
            f"fusioninfer:kv_stream_admissions_total{{{labels}}} {engine.kv_stream_admissions_total}",
            "# HELP fusioninfer:kv_stream_fallbacks_total Stream faults degraded to a local re-prefill (bit-identical output).",
            "# TYPE fusioninfer:kv_stream_fallbacks_total counter",
            f"fusioninfer:kv_stream_fallbacks_total{{{labels}}} {engine.kv_stream_fallbacks_total}",
            "# HELP fusioninfer:kv_fabric_restored_blocks_total Prefix blocks restored from a PEER engine's host tier via the fabric pull path.",
            "# TYPE fusioninfer:kv_fabric_restored_blocks_total counter",
            f"fusioninfer:kv_fabric_restored_blocks_total{{{labels}}} {engine.kv_fabric_restored_blocks_total}",
        ]
        fabric = getattr(engine, "_kv_fabric", None)
        if fabric is not None:
            c = fabric.counters()
            lines += [
                "# HELP fusioninfer:kv_fabric_pull_requests_total Cross-engine kv_export pull round-trips attempted.",
                "# TYPE fusioninfer:kv_fabric_pull_requests_total counter",
                f"fusioninfer:kv_fabric_pull_requests_total{{{labels}}} {c['pull_requests']}",
                "# HELP fusioninfer:kv_fabric_pulled_blocks_total Frames fetched from peer host tiers (pre-import).",
                "# TYPE fusioninfer:kv_fabric_pulled_blocks_total counter",
                f"fusioninfer:kv_fabric_pulled_blocks_total{{{labels}}} {c['pulled_blocks']}",
                "# HELP fusioninfer:kv_fabric_pull_rejected_total Pulled frames rejected at the pairing-CRC door.",
                "# TYPE fusioninfer:kv_fabric_pull_rejected_total counter",
                f"fusioninfer:kv_fabric_pull_rejected_total{{{labels}}} {c['pull_rejected']}",
                "# HELP fusioninfer:kv_fabric_pull_faults_total Pull transport faults (peer vanished, timeout, injected).",
                "# TYPE fusioninfer:kv_fabric_pull_faults_total counter",
                f"fusioninfer:kv_fabric_pull_faults_total{{{labels}}} {c['pull_faults']}",
            ]
        return lines

    @staticmethod
    def _render_evacuation(engine, labels: str) -> list[str]:
        """Graceful-evacuation families (docs/design/spot-revocation.md).
        Engines predating evacuation (test stubs) omit them."""
        if not hasattr(engine, "evac_streams_total"):
            return []
        return [
            "# HELP fusioninfer:evac_streams_total In-flight streams failed with a retriable abort by graceful evacuation.",
            "# TYPE fusioninfer:evac_streams_total counter",
            f"fusioninfer:evac_streams_total{{{labels}}} {engine.evac_streams_total}",
            "# HELP fusioninfer:evac_parked_streams_total Evacuation victims whose KV pages were parked before the notice deadline.",
            "# TYPE fusioninfer:evac_parked_streams_total counter",
            f"fusioninfer:evac_parked_streams_total{{{labels}}} {engine.evac_parked_streams_total}",
            "# HELP fusioninfer:evac_parked_pages_total KV pages parked by evacuation victims.",
            "# TYPE fusioninfer:evac_parked_pages_total counter",
            f"fusioninfer:evac_parked_pages_total{{{labels}}} {engine.evac_parked_pages_total}",
            "# HELP fusioninfer:evac_unparked_total Evacuation victims degraded to recompute-on-survivor (notice expired mid-park).",
            "# TYPE fusioninfer:evac_unparked_total counter",
            f"fusioninfer:evac_unparked_total{{{labels}}} {engine.evac_unparked_total}",
        ]

    @staticmethod
    def _render_host(engine, labels: str) -> list[str]:
        """Where the engine thread's time goes (utils/spans.py): self
        wall and self CPU seconds per host span, the loop's wall and CPU
        clocks and its stalls, each request's queue and prefill wait, and
        the process's jit and collector seconds.  Separate families, not
        a label: scrapers that sum a family's samples keep each phase
        apart.  Engines without a span clock (test stubs) omit the
        families."""
        from fusioninfer_tpu.utils import spans

        clock = getattr(engine, "spans", None)
        if clock is None:
            return []
        lines: list[str] = []

        def counter(name: str, help_: str, value) -> None:
            lines.extend(_counter(name, help_, labels, value))

        for span in spans.SPAN_NAMES:
            counter(f"fusioninfer:host_{span.replace('.', '_')}_seconds_total",
                    f"Engine-thread self time inside {span} spans.",
                    clock.ns[span] / 1e9)
        for span in spans.SPAN_NAMES:
            counter(f"fusioninfer:engine_cpu_{span.replace('.', '_')}"
                    "_seconds_total",
                    f"Engine-thread self CPU time inside {span} spans (the "
                    "wall family less this one is time spent waiting).",
                    clock.cpu_ns[span] / 1e9)
        counter("fusioninfer:engine_loop_seconds_total",
                "Wall time since the engine loop started.",
                clock.loop_ns / 1e9)
        counter("fusioninfer:engine_thread_cpu_seconds_total",
                "CPU time of the engine thread since the loop started.",
                clock.loop_cpu_ns / 1e9)
        counter("fusioninfer:engine_stalls_total",
                "Engine-loop iterations of 250 ms or more (each logs one "
                "'engine stall' warning).", clock.stalls)
        counter("fusioninfer:engine_stall_seconds_total",
                "Wall time of the engine-loop iterations counted as stalls.",
                clock.stall_ns / 1e9)
        counter("fusioninfer:jit_seconds_total",
                "Seconds of jax trace + lower + compile on jit-cache misses.",
                spans.jit_totals["seconds"])
        counter("fusioninfer:gc_seconds_total",
                "Wall seconds inside the cyclic garbage collector, any "
                "thread, any generation (every thread waits for it).",
                spans.gc_totals["seconds"])
        for name, help_, hist in (
                ("vllm:request_queue_time_seconds",
                 "Arrival to the pop for admission.", engine.queue_time),
                ("vllm:request_prefill_time_seconds",
                 "Pop for admission to first token, resumes included.",
                 engine.prefill_time)):
            lines += [f"# HELP {name} {help_}", f"# TYPE {name} histogram",
                      *hist.render(name, labels)]
        return lines

    def _render_stream(self, labels: str) -> list[str]:
        """The stream handlers' side (utils/spans.py ``StreamClock``): a
        token's path from ``chan.put`` on the engine thread to its SSE
        chunk's socket write, summed over every handler thread."""
        stream = self.stream
        chunks, delay_s = stream.chunks, stream.delay_ns / 1e9
        lines: list[str] = []
        for family, help_, value in (
                ("stream_render_seconds_total",
                 "Stream-handler wall time rendering chunks: a token's text, "
                 "stop check, chunk and its JSON.",
                 stream.render_ns / 1e9),
                ("stream_write_seconds_total",
                 "Stream-handler wall time writing chunks to the socket.",
                 stream.write_ns / 1e9),
                ("stream_cpu_seconds_total",
                 "Stream-handler threads' CPU time while they stream, system "
                 "time outside the interpreter lock included (each thread's "
                 "clock read at most once a second).",
                 stream.cpu_seconds()),
                ("stream_chunks_total",
                 "SSE chunks written that carry an engine output (one per "
                 "streamed token; a tool-call stream's are not counted).",
                 chunks),
                ("stream_writes_total",
                 "Socket writes that carry such chunks (a stream's chunks "
                 "of one engine step go out in one write).",
                 stream.writes)):
            lines += _counter("fusioninfer:" + family, help_, labels, value)
        name = "fusioninfer:stream_delay_seconds"
        lines += [f"# HELP {name} From an output's hand-over to its stream "
                  "(engine thread) to its chunk's write returning.",
                  f"# TYPE {name} summary",
                  f"{name}_sum{{{labels}}} {delay_s}",
                  f"{name}_count{{{labels}}} {chunks}"]
        return lines

    @staticmethod
    def _render_scheduler(engine, labels: str) -> list[str]:
        """Token-budget scheduler families (docs/design/scheduler.md):
        budget utilization, the scheduler's decision counters, and the
        adaptive-burst span histogram.  Engines predating the budget
        scheduler (test stubs) simply omit the families."""
        sched = getattr(engine, "sched", None)
        if sched is None:
            return []
        lines = [
            "# HELP fusioninfer:sched_token_budget Configured tokens-per-step budget (0 = unbudgeted).",
            "# TYPE fusioninfer:sched_token_budget gauge",
            f"fusioninfer:sched_token_budget{{{labels}}} {sched.tokens_per_step or 0}",
            "# HELP fusioninfer:sched_budget_utilization Lifetime fraction of budgeted tokens spent (decode + prefill).",
            "# TYPE fusioninfer:sched_budget_utilization gauge",
            f"fusioninfer:sched_budget_utilization{{{labels}}} {sched.utilization():.4f}",
            "# HELP fusioninfer:sched_steps_total Engine scheduler steps executed.",
            "# TYPE fusioninfer:sched_steps_total counter",
            f"fusioninfer:sched_steps_total{{{labels}}} {sched.steps_total}",
            "# HELP fusioninfer:sched_decode_tokens_total Decode tokens charged against the step budget.",
            "# TYPE fusioninfer:sched_decode_tokens_total counter",
            f"fusioninfer:sched_decode_tokens_total{{{labels}}} {sched.decode_tokens_total}",
            "# HELP fusioninfer:sched_prefill_tokens_total Prefill tokens charged against the step budget.",
            "# TYPE fusioninfer:sched_prefill_tokens_total counter",
            f"fusioninfer:sched_prefill_tokens_total{{{labels}}} {sched.prefill_tokens_total}",
            "# HELP fusioninfer:sched_chunks_total Adaptively-sized prefill chunk forwards scheduled.",
            "# TYPE fusioninfer:sched_chunks_total counter",
            f"fusioninfer:sched_chunks_total{{{labels}}} {sched.chunks_total}",
            "# HELP fusioninfer:sched_admission_deferred_total Admissions routed to chunked prefill because the step budget was spent.",
            "# TYPE fusioninfer:sched_admission_deferred_total counter",
            f"fusioninfer:sched_admission_deferred_total{{{labels}}} {sched.admission_deferred_total}",
            "# HELP fusioninfer:sched_burst_clamped_total Decode bursts clamped to span 1 because work was admissible or a waiter's slot would free inside the span; a clamped burst still pipelines (see sched_dispatch_ahead_total).",
            "# TYPE fusioninfer:sched_burst_clamped_total counter",
            f"fusioninfer:sched_burst_clamped_total{{{labels}}} {sched.burst_clamped_total}",
            "# HELP fusioninfer:sched_dispatch_ahead_total Steps whose successor (a decode burst or a mixed step) was dispatched before the blocking fetch: runs whenever nothing is admissible.",
            "# TYPE fusioninfer:sched_dispatch_ahead_total counter",
            f"fusioninfer:sched_dispatch_ahead_total{{{labels}}} {sched.dispatch_ahead_total}",
            "# HELP fusioninfer:sched_mixed_dispatch_ahead_total Mixed steps whose successor mixed step was dispatched before the blocking fetch, its decode rows' input tokens carried on the device (a part of sched_dispatch_ahead_total).",
            "# TYPE fusioninfer:sched_mixed_dispatch_ahead_total counter",
            f"fusioninfer:sched_mixed_dispatch_ahead_total{{{labels}}} {sched.mixed_dispatch_ahead_total}",
            "# HELP fusioninfer:sched_kv_restores_total KV pages restored from the host tier, charged against the step budget.",
            "# TYPE fusioninfer:sched_kv_restores_total counter",
            f"fusioninfer:sched_kv_restores_total{{{labels}}} {sched.kv_restores_total}",
            "# HELP fusioninfer:sched_kv_restore_tokens_total Prefix tokens covered by host-tier restores (prefill work not recomputed).",
            "# TYPE fusioninfer:sched_kv_restore_tokens_total counter",
            f"fusioninfer:sched_kv_restore_tokens_total{{{labels}}} {sched.kv_restore_tokens_total}",
            "# HELP fusioninfer:sched_kv_restore_deferred_total Host-tier restore plans truncated because the step's prefill budget was spent.",
            "# TYPE fusioninfer:sched_kv_restore_deferred_total counter",
            f"fusioninfer:sched_kv_restore_deferred_total{{{labels}}} {sched.kv_restore_deferred_total}",
            "# HELP fusioninfer:sched_deadline_shed_total Queued requests shed at admission because their deadline had already expired.",
            "# TYPE fusioninfer:sched_deadline_shed_total counter",
            f"fusioninfer:sched_deadline_shed_total{{{labels}}} {sched.deadline_shed_total}",
            "# HELP fusioninfer:sched_tier_preemptions_total Running sequences preempted because their tier squeezed a more urgent tier's budget share.",
            "# TYPE fusioninfer:sched_tier_preemptions_total counter",
            f"fusioninfer:sched_tier_preemptions_total{{{labels}}} {sched.tier_preemptions_total}",
            "# HELP fusioninfer:sched_preempt_parks_total Preemption victims whose computed KV pages were parked (content-registered, host-offloaded) instead of dropped.",
            "# TYPE fusioninfer:sched_preempt_parks_total counter",
            f"fusioninfer:sched_preempt_parks_total{{{labels}}} {sched.preempt_parks_total}",
            "# HELP fusioninfer:sched_preempt_parked_pages_total KV pages parked by preemption victims.",
            "# TYPE fusioninfer:sched_preempt_parked_pages_total counter",
            f"fusioninfer:sched_preempt_parked_pages_total{{{labels}}} {sched.preempt_parked_pages_total}",
            "# HELP fusioninfer:sched_preempt_resumes_total Preempted requests re-admitted to continue their stream.",
            "# TYPE fusioninfer:sched_preempt_resumes_total counter",
            f"fusioninfer:sched_preempt_resumes_total{{{labels}}} {sched.preempt_resumes_total}",
            "# HELP fusioninfer:sched_preempt_resume_reused_tokens_total Resume prefix tokens served from parked/restored pages instead of recompute.",
            "# TYPE fusioninfer:sched_preempt_resume_reused_tokens_total counter",
            f"fusioninfer:sched_preempt_resume_reused_tokens_total{{{labels}}} {sched.preempt_resume_reused_tokens_total}",
            "# HELP fusioninfer:sched_fused_steps_total Steps that ran the fused mixed-batch forward (decode + prefill chunks in one weight pass).",
            "# TYPE fusioninfer:sched_fused_steps_total counter",
            f"fusioninfer:sched_fused_steps_total{{{labels}}} {sched.fused_steps_total}",
            "# HELP fusioninfer:sched_weight_passes_total Weight-streaming forward passes dispatched on the serving path (a span-k decode burst counts k).",
            "# TYPE fusioninfer:sched_weight_passes_total counter",
            f"fusioninfer:sched_weight_passes_total{{{labels}}} {sched.weight_passes_total}",
            "# HELP fusioninfer:sched_burst_span_steps_total Decode dispatches by fused span (adaptive-burst histogram).",
            "# TYPE fusioninfer:sched_burst_span_steps_total counter",
        ]
        for span, count in sorted(sched.burst_span_steps.items()):
            lines.append(
                f'fusioninfer:sched_burst_span_steps_total{{{labels},span="{span}"}} {count}')
        lines += [
            "# HELP fusioninfer:sched_fused_packed_tokens Real (non-padding) tokens packed into each fused mixed-batch forward.",
            "# TYPE fusioninfer:sched_fused_packed_tokens histogram",
        ]
        from fusioninfer_tpu.engine.sched import PACKED_TOKENS_BUCKETS

        cumulative = 0
        for b in PACKED_TOKENS_BUCKETS:
            cumulative += sched.fused_packed_tokens.get(b, 0)
            lines.append(
                f'fusioninfer:sched_fused_packed_tokens_bucket{{{labels},le="{b}"}} {cumulative}')
        cumulative += sched.fused_packed_tokens.get(float("inf"), 0)
        lines.append(
            f'fusioninfer:sched_fused_packed_tokens_bucket{{{labels},le="+Inf"}} {cumulative}')
        lines.append(
            f"fusioninfer:sched_fused_packed_tokens_sum{{{labels}}} "
            f"{sched.fused_packed_tokens_sum}")
        lines.append(
            f"fusioninfer:sched_fused_packed_tokens_count{{{labels}}} "
            f"{sched.fused_steps_total}")
        return lines
