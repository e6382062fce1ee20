"""Token chunks per socket write over the window
(Δ``fusioninfer:stream_chunks_total`` / Δ``fusioninfer:stream_writes_total``):
how many of a stream's tokens one write of its SSE handler carries, 1.0
where every write carries one.  Nothing on a program without the writes
counter."""


def read(run):
    chunks, writes = (run.delta("fusioninfer:stream_chunks_total"),
                      run.delta("fusioninfer:stream_writes_total"))
    if chunks is None or not writes:
        return None
    return chunks / writes
