"""Requests preempted to reclaim KV pages inside the window."""


def read(run):
    return run.delta("vllm:num_preemptions_total")
