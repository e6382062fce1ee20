"""Engine-thread self time in ``step.admit`` spans (cancellations, PD and
embedding service, the step's ledger, the queue pop, hash chain, page
allocation) per scheduler step of the window."""
import spanread


def read(run):
    return spanread.ms_per_step(run, "step.admit")
