"""Engine-thread self time in ``step.prefill`` spans (host work of prompt
forwards: packing, bookkeeping, activation) per scheduler step of the
window."""
import spanread


def read(run):
    return spanread.ms_per_step(run, "step.prefill")
