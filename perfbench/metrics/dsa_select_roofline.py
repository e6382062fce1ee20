"""The sparse-attention selection kernel's share of its memory roofline.

Least time, from the program's own counter over the window: every
indexer score the selection must read once (``fusioninfer:
dsa_positions_scored_total``, one float32 score a cached position a
query scored, every layer, decode and chunk rows alike) at the chip's
HBM bandwidth, as a rate per second of window.  Time taken: the summed
device time of the selection kernel's events (``sparse_select``) in
every program, as a rate per second of traced window times the chips.
Returns nothing where the trace has no such kernel or the program has no
such counter."""

SCORE_BYTES = 4  # a float32 score


def read(run):
    if not run.trace or not run.peaks:
        return None
    scored = run.delta("fusioninfer:dsa_positions_scored_total")
    if not scored:
        return None
    kernel = sum(v for k, v in run.trace["ops"].items()
                 if "sparse_select" in k.split("/", 1)[-1])
    if kernel <= 0:
        return None
    least_s = scored * SCORE_BYTES / run.peaks["hbm_bytes_per_s"]
    taken_per_s = kernel / run.trace["window_s"] / run.trace["chips"]
    return 100.0 * least_s / run.seconds / run.chips / taken_per_s
