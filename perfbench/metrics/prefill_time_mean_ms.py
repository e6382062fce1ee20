"""The pop for admission to the first token, mean over the requests whose
first token came inside the window
(``vllm:request_prefill_time_seconds``)."""
import spanread


def read(run):
    return spanread.mean_ms(run, "vllm:request_prefill_time_seconds")
