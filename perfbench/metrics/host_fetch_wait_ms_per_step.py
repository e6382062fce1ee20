"""Engine-thread time blocked on a device value (``step.fetch`` spans) per
scheduler step of the window: the host waiting means the chip is the one
working."""
import spanread


def read(run):
    return spanread.ms_per_step(run, "step.fetch")
