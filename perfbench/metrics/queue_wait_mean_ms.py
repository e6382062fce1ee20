"""Arrival to the pop for admission, mean over the requests whose first
token came inside the window (``vllm:request_queue_time_seconds``)."""
import spanread


def read(run):
    return spanread.mean_ms(run, "vllm:request_queue_time_seconds")
