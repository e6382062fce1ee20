"""The engine thread's host spans over the window: deltas of the
program's ``fusioninfer:host_<span>_seconds_total`` families (self
seconds per span, ``fusioninfer_tpu/utils/spans.py``) between the
window's two ``/metrics`` reads.  A program without the families reads
as None, and the line leaves the metric out."""

HOST = "fusioninfer:host_"
SECONDS = "_seconds_total"
STEPS = "fusioninfer:sched_steps_total"
LOOP = "fusioninfer:engine_loop_seconds_total"
CPU = "fusioninfer:engine_thread_cpu_seconds_total"


def seconds(run, span: str):
    return run.delta(HOST + span.replace(".", "_") + SECONDS)


def ms_per_step(run, span: str):
    s, steps = seconds(run, span), run.delta(STEPS)
    if s is None or not steps:
        return None
    return 1e3 * s / steps


def all_spans_seconds(run):
    """Every span's self seconds, summed: the time the loop spent inside
    spans (whatever spans the program has)."""
    deltas = [run.delta(f) for f in run.counters_close
              if f.startswith(HOST) and f.endswith(SECONDS)]
    if not deltas or None in deltas:
        return None
    return sum(deltas)


def mean_ms(run, histogram: str):
    """Mean of a histogram family's observations inside the window."""
    total, n = run.delta(histogram + "_sum"), run.delta(histogram + "_count")
    if total is None or not n:
        return None
    return 1e3 * total / n
