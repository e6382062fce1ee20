"""Engine-thread self time in ``step.dispatch`` spans (uploads plus the
jitted call until it returns: the enqueue, not the run) per scheduler
step of the window."""
import spanread


def read(run):
    return spanread.ms_per_step(run, "step.dispatch")
