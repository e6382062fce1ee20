"""Seconds of jax trace + lower + compile inside the window
(``fusioninfer:jit_seconds_total``): a program's first live dispatch
shows here even where it writes no new cache file (should be 0)."""


def read(run):
    return run.delta("fusioninfer:jit_seconds_total")
