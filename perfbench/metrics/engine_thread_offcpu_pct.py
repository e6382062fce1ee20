"""The share of the engine thread's working time (the loop's wall time
less blocking on the device and idle sleeps) that it spent off the CPU:
waiting for the interpreter lock or another lock.  Floored at 0."""
import spanread


def read(run):
    loop, cpu = run.delta(spanread.LOOP), run.delta(spanread.CPU)
    fetch = spanread.seconds(run, "step.fetch")
    idle = spanread.seconds(run, "loop.idle")
    if None in (loop, cpu, fetch, idle) or loop - fetch - idle <= 0:
        return None
    return max(0.0, 100.0 * (1.0 - cpu / (loop - fetch - idle)))
