"""Tokens the scheduler charged per weight-streaming forward, over the
window: how well each pass over the weights is filled."""


def read(run):
    toks = [run.delta("fusioninfer:sched_decode_tokens_total"),
            run.delta("fusioninfer:sched_prefill_tokens_total")]
    passes = run.delta("fusioninfer:sched_weight_passes_total")
    if None in toks or not passes:
        return None
    return sum(toks) / passes
