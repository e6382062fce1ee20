"""Wall time inside the cyclic garbage collector, whichever thread
triggered it (``fusioninfer:gc_seconds_total``; every thread waits for
it), per scheduler step of the window.  Nothing on a program without the
family."""


def read(run):
    gc_s, steps = (run.delta("fusioninfer:gc_seconds_total"),
                   run.delta("fusioninfer:sched_steps_total"))
    if gc_s is None or not steps:
        return None
    return 1e3 * gc_s / steps
