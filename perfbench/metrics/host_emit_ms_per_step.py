"""Engine-thread self time in ``step.emit`` spans (the per-token loops:
stop checks, finishes, slot release) per scheduler step of the window."""
import spanread


def read(run):
    return spanread.ms_per_step(run, "step.emit")
