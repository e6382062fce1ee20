"""The stream handler threads' CPU time while they stream (waking for a
token, rendering its SSE chunk, writing it:
``fusioninfer:stream_cpu_seconds_total``, each thread's clock read at
most once a second) per scheduler step of the window.  Handler CPU,
including system time outside the interpreter lock (a socket write
releases it): an upper bound on what the handlers take from the engine
thread's interpreter lock, not a measure of it.  Nothing on a program
without the family."""


def read(run):
    cpu, steps = (run.delta("fusioninfer:stream_cpu_seconds_total"),
                  run.delta("fusioninfer:sched_steps_total"))
    if cpu is None or not steps:
        return None
    return 1e3 * cpu / steps
