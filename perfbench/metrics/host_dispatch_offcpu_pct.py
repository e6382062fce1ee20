"""The share of the engine thread's self time in ``step.dispatch`` spans
(uploads and the jitted calls until they return) that it spent off the
CPU: 1 - Δ``fusioninfer:engine_cpu_step_dispatch_seconds_total`` (its self
CPU time) / Δ``fusioninfer:host_step_dispatch_seconds_total`` (its self
wall time).  Waiting for the interpreter lock or another lock, not
enqueueing.  Nothing on a program without the CPU family."""
import spanread


def read(run):
    cpu = run.delta("fusioninfer:engine_cpu_step_dispatch_seconds_total")
    wall = spanread.seconds(run, "step.dispatch")
    if cpu is None or not wall:
        return None
    return 100.0 * (1.0 - cpu / wall)
