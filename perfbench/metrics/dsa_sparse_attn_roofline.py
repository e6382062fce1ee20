"""The sparse attention kernel's share of its memory roofline.

Least time: the K/V bytes of the ``min(context, topk)`` chosen positions
that the output tokens of the window must read (``sparse_attn_bytes`` of
the architecture's file, from the client's own record), at the chip's
HBM bandwidth, as a rate per second of window.  Time taken: the summed
device time of the sparse attention kernel's events
(``sparse_paged_attention``) inside the two programs that produce output
tokens, ``jit_decode_burst`` and ``jit_fused_step``, as a rate per second
of traced window times the chips.  The prompt chunks of a fused step go
through the same calls and their reads are not counted, so the share
reads low, never high.  Returns nothing where the trace has no such
kernel or the architecture's file has no such count."""
import work

DECODE_PROGRAMS = ("decode_burst", "fused_step")


def read(run):
    if not run.trace or not run.peaks:
        return None
    kernel = sum(v for k, v in run.trace["ops"].items()
                 if k.split("/", 1)[0].endswith(DECODE_PROGRAMS)
                 and "sparse_paged_attention" in k)
    if kernel <= 0:
        return None
    try:
        count = work.load_arch(work.arch_path(run.config)).sparse_attn_bytes
    except (AttributeError, ValueError):
        return None
    contexts = [r.prompt_len + j for r in run.records
                for j, s in enumerate(r.stamps)
                if j > 0 and run.t_open <= s <= run.t_close]
    if not contexts:
        return None
    least_per_s = (count(run.config, contexts)
                   / run.peaks["hbm_bytes_per_s"] / run.seconds)
    taken_per_s = kernel / run.trace["window_s"] / run.trace["chips"]
    return 100.0 * least_per_s / run.chips / taken_per_s
