"""Entry points the server's AOT warm-up compiled fresh (0 when warm)."""


def read(run):
    return run.metrics_ready.get("fusioninfer:aot_cache_misses")
