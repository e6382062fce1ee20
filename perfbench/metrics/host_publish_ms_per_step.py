"""Engine-thread self time in ``loop.publish`` spans (histogram observes,
channel lookups under the server's lock, channel puts) per scheduler step
of the window."""
import spanread


def read(run):
    return spanread.ms_per_step(run, "loop.publish")
