"""Scheduler steps whose decode burst was clamped to one step because
admission work was pending, over all steps of the window."""


def read(run):
    c = run.delta("fusioninfer:sched_burst_clamped_total")
    s = run.delta("fusioninfer:sched_steps_total")
    if c is None or not s:
        return None
    return 100.0 * c / s
