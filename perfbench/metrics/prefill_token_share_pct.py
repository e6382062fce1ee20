"""Prefill's share of the tokens the scheduler charged in the window."""


def read(run):
    d = run.delta("fusioninfer:sched_decode_tokens_total")
    p = run.delta("fusioninfer:sched_prefill_tokens_total")
    if d is None or p is None or d + p <= 0:
        return None
    return 100.0 * p / (d + p)
