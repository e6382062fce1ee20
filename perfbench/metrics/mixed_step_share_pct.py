"""Scheduler steps that carried their decode rows and their prefill
chunks in ONE weight pass (the mixed ``fused_step``), over all steps of
the window."""


def read(run):
    f = run.delta("fusioninfer:sched_fused_steps_total")
    s = run.delta("fusioninfer:sched_steps_total")
    if f is None or not s:
        return None
    return 100.0 * f / s
