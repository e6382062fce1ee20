"""Assert the bench record carries the serving-path-gap evidence fields.

The CPU bench smoke (``make bench-smoke``, CI's "bench smoke" step) runs
``bench.py`` and then this checker against the sidecar record: the
``http`` leg must report ``ceiling_fraction`` (HTTP output tok/s over
the same-config raw decode tok/s), ``weight_passes_per_step`` (the
fused-step evidence: weight-streaming forwards per engine step — ≈ 1
under mixed load on the fused path, ≥ 2 split) and the token-budget
scheduler's fields (``scheduler.token_budget``, ``fused_steps``,
``weight_passes`` etc., see engine/sched.py) plus the TTFT
decomposition's ``queue_wait_ms`` — so a regression that silently
drops the scheduling evidence fails CI instead of shipping a blind
record.  Usage: ``python tools/check_bench_record.py [BENCH_OUT.json]``.
"""

from __future__ import annotations

import json
import pathlib
import sys


def check_record(record: dict) -> list[str]:
    """Return the list of missing-field complaints (empty = pass)."""
    problems: list[str] = []
    if record.get("error"):
        problems.append(f"bench errored: {record['error']}")
        return problems
    # ragged-kernel microbench leg (r06): dispersion + the ratio
    # field + mfu_box must land in every record, so a regression that
    # silently drops the kernel evidence fails CI
    micro = record.get("kernel_microbench")
    if not isinstance(micro, dict):
        problems.append("kernel_microbench leg missing")
    elif micro.get("error"):
        problems.append(f"kernel_microbench errored: {micro['error']}")
    else:
        for field in ("ragged_vs_gather", "mfu_box"):
            if field not in micro:
                problems.append(f"kernel_microbench.{field} missing")
        ragged = micro.get("ragged")
        if not isinstance(ragged, dict) or "rel_iqr" not in ragged:
            problems.append(
                "kernel_microbench.ragged dispersion (rel_iqr) missing")
        # flash-decode longctx stratum (r15): the KV-split grid's
        # evidence leg must be present at every context depth with
        # dispersion, and the kvsplit schedule must never LOSE to the
        # single walk (the acceptance target is >= 2x at 32k; the gate
        # floors at >= 1 so a regressed-but-plausible record still
        # fails loudly rather than hiding the leg)
        lc = micro.get("longctx")
        if not isinstance(lc, dict):
            problems.append("kernel_microbench.longctx stratum missing")
        elif lc.get("error"):
            problems.append(f"kernel_microbench.longctx errored: "
                            f"{lc['error']}")
        else:
            ratio = lc.get("kvsplit_vs_singlewalk")
            if not isinstance(ratio, (int, float)) or ratio < 1.0:
                problems.append(
                    "kernel_microbench.longctx.kvsplit_vs_singlewalk "
                    f"must be >= 1, got {ratio!r}")
            ctxs = lc.get("contexts")
            if not isinstance(ctxs, dict) or "32768" not in ctxs:
                problems.append(
                    "kernel_microbench.longctx.contexts must include "
                    "the 32768 decode shape")
            else:
                for depth, entry in ctxs.items():
                    for leg_name in ("singlewalk", "kvsplit"):
                        if "rel_iqr" not in (entry.get(leg_name) or {}):
                            problems.append(
                                f"kernel_microbench.longctx.contexts."
                                f"{depth}.{leg_name} dispersion missing")
            if lc.get("kvsplit_kernel_ok") is not True:
                problems.append(
                    "kernel_microbench.longctx.kvsplit_kernel_ok must "
                    f"be true, got {lc.get('kvsplit_kernel_ok')!r}")
    # serving config ladder (r15): the README's Qwen3-8B-int8 rung must
    # exist with its memory-fit arithmetic asserted (VERDICT weak #3/#4:
    # the claim had never been measured NOR sized in-record)
    ladder = record.get("config_ladder")
    if not isinstance(ladder, list):
        problems.append("config_ladder missing")
    else:
        rung8b = [r for r in ladder
                  if r.get("model") == "qwen3-8b"
                  and r.get("quantization") == "int8"]
        if not rung8b:
            problems.append("config_ladder lacks the qwen3-8b int8 rung")
        elif rung8b[0].get("fits_v5e_16gib") is not True:
            problems.append(
                "config_ladder qwen3-8b int8 rung must fit a 16 GiB "
                f"v5e (fits_v5e_16gib={rung8b[0].get('fits_v5e_16gib')!r}, "
                f"weights={rung8b[0].get('weights_gib')!r} GiB)")
    http = record.get("http")
    if not isinstance(http, dict):
        # a decode-only run (BENCH_SKIP_HTTP=1) is exempt from the http
        # assertions — there is no http leg to assert against
        return problems
    if "ceiling_fraction" not in http:
        problems.append("http.ceiling_fraction missing")
    if "weight_passes_per_step" not in http:
        problems.append(
            "http.weight_passes_per_step (fused-step evidence) missing")
    # fused-sampling evidence (r15): the http leg's load rides bounded
    # top-k, so ceiling_fraction is measured ON the fused lm_head→top-k
    # path — the leg must say so, and a burst-1 engine with the path
    # enabled must demonstrably have sampled through it
    fs = http.get("fused_sampling")
    if not isinstance(fs, dict):
        problems.append("http.fused_sampling evidence missing")
    elif (fs.get("enabled") and http.get("decode_burst") == 1
          and not fs.get("steps")):
        problems.append(
            "http.fused_sampling.steps must be nonzero on a burst-1 "
            f"engine with the path enabled, got {fs.get('steps')!r}")
    sched = http.get("scheduler")
    if not isinstance(sched, dict):
        problems.append("http.scheduler missing")
    else:
        for field in ("token_budget", "budget_utilization",
                      "burst_span_steps", "burst_clamped",
                      "fused_steps", "weight_passes",
                      # overload-robustness ledger (r10): the
                      # deadline-shed and KV-preserving-preemption
                      # counters must land in every record so a
                      # regression that silently drops them fails CI
                      "deadline_shed", "preempt_parks",
                      "preempt_resumes", "tier_preemptions"):
            if field not in sched:
                problems.append(f"http.scheduler.{field} missing")
    if "queue_wait_ms" not in http:
        problems.append("http.queue_wait_ms (TTFT decomposition) missing")
    # hierarchical-KV leg (r08): the shared-prefix workload must drive
    # the hit rate off 0.0, warm turns must beat cold turns, and the
    # host tier must demonstrably carry chains (offloads AND restores
    # AND host hits nonzero) — a record without this evidence is the
    # pre-hierarchy blind spot shipping again
    problems += check_sharedprefix_leg(record, "workload_sharedprefix")
    # r12: the SAME workload through a tp=2 tensor-parallel engine —
    # MULTICHIP evidence past the smoke-only dryrun (ROADMAP gap)
    problems += check_sharedprefix_leg(record, "workload_sharedprefix_tp")
    tp_leg = record.get("workload_sharedprefix_tp")
    if isinstance(tp_leg, dict) and not tp_leg.get("error") and \
            tp_leg.get("tensor_parallel") != 2:
        problems.append(
            "workload_sharedprefix_tp.tensor_parallel must be 2, got "
            f"{tp_leg.get('tensor_parallel')!r}")
    return problems


def check_sharedprefix_leg(record: dict, leg: str) -> list[str]:
    """The sharedprefix evidence contract, shared by the single-chip
    and tensor-parallel legs."""
    problems: list[str] = []
    sp = record.get(leg)
    if not isinstance(sp, dict):
        return [f"{leg} leg missing"]
    if sp.get("error"):
        return [f"{leg} errored: {sp['error']}"]
    rate = sp.get("prefix_cache_hit_rate")
    if not isinstance(rate, (int, float)) or rate <= 0.0:
        problems.append(
            f"{leg}.prefix_cache_hit_rate must be > 0, got {rate!r}")
    for field in ("cold_ttft_ms", "warm_ttft_ms"):
        if not (sp.get(field) or {}).get("p50"):
            problems.append(f"{leg}.{field}.p50 missing")
    if sp.get("warm_faster") is not True:
        problems.append(
            f"{leg}: warm-turn TTFT p50 must beat "
            f"cold-turn p50 (warm_faster={sp.get('warm_faster')!r}, "
            f"warm={(sp.get('warm_ttft_ms') or {}).get('p50')}ms, "
            f"cold={(sp.get('cold_ttft_ms') or {}).get('p50')}ms)")
    tier = sp.get("host_tier")
    if not isinstance(tier, dict):
        problems.append(f"{leg}.host_tier counters missing")
    else:
        for counter in ("offloads", "restores", "host_hits"):
            if not tier.get(counter):
                problems.append(
                    f"{leg}.host_tier.{counter} must be "
                    f"nonzero, got {tier.get(counter)!r}")
    return problems


def main(argv: list[str]) -> int:
    path = pathlib.Path(argv[1]) if len(argv) > 1 else (
        pathlib.Path(__file__).resolve().parent.parent / "BENCH_OUT.json")
    try:
        record = json.loads(path.read_text())
    except (OSError, ValueError) as e:
        print(f"check_bench_record: cannot read {path}: {e}",
              file=sys.stderr)
        return 2
    problems = check_record(record)
    if problems:
        for p in problems:
            print(f"check_bench_record: {p}", file=sys.stderr)
        return 1
    print(f"check_bench_record: {path.name} carries ceiling_fraction + "
          "scheduler budget fields and the tp sharedprefix leg")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
