"""Largest reading of vllm:kv_cache_usage_perc, polled at 1 Hz in the
window."""


def read(run):
    vals = [p["vllm:kv_cache_usage_perc"] for p in run.polls
            if "vllm:kv_cache_usage_perc" in p]
    return 100.0 * max(vals) if vals else None
