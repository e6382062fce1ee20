"""Engine-thread self time in ``step.pack`` spans (the decode half's host
work: page growth, burst span, control arrays, ragged packing) per
scheduler step of the window."""
import spanread


def read(run):
    return spanread.ms_per_step(run, "step.pack")
