"""Scheduler steps whose decode burst had its successor dispatched before
the blocking fetch (the host's turn hidden behind the device), over all
steps of the window."""


def read(run):
    a = run.delta("fusioninfer:sched_dispatch_ahead_total")
    s = run.delta("fusioninfer:sched_steps_total")
    if a is None or not s:
        return None
    return 100.0 * a / s
