"""Mixed steps whose successor mixed step was dispatched before their
blocking fetch (the step's decode rows' inputs carried on the device),
over all mixed steps of the window."""


def read(run):
    a = run.delta("fusioninfer:sched_mixed_dispatch_ahead_total")
    f = run.delta("fusioninfer:sched_fused_steps_total")
    if a is None or not f:
        return None
    return 100.0 * a / f
