"""From a token's hand-over to its stream on the engine thread to its SSE
chunk's socket write returning, mean over the chunks written inside the
window (``fusioninfer:stream_delay_seconds``): how far the clients' view
lags the engine.  Nothing on a program without the histogram."""
import spanread


def read(run):
    return spanread.mean_ms(run, "fusioninfer:stream_delay_seconds")
