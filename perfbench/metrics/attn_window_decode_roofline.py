"""The windowed layers' decode attention's share of its memory roofline.

Least time: the KV bytes that the window's output tokens must read in
the WINDOWED layers (per token: the lesser of its context and the
sliding window x windowed layers x 2 x kv_heads x head_dim x 2 bytes:
``decode_window_kv_bytes`` of the architecture's file, from the client's
own record and the configuration's file) at the chip's HBM bandwidth, as
a rate per second of window.  Time taken: the summed device time of the
window kind's paged-attention calls (they carry a name of their own in
the trace, ``ragged_paged_attention_window...``) inside the two programs
that produce output tokens, ``jit_decode_burst`` and ``jit_fused_step``,
as a rate per second of traced window times the chips.  The prompt
chunks of a fused step go through the same calls and their reads are not
counted, so the share reads low, never high.  Bound: memory.  Returns
nothing where the trace has no such kernel (a program without layer
kinds) or the architecture's file has no such count."""
import work

DECODE_PROGRAMS = ("decode_burst", "fused_step")


def read(run):
    if not run.trace or not run.peaks:
        return None
    kernel = sum(v for k, v in run.trace["ops"].items()
                 if k.split("/", 1)[0].endswith(DECODE_PROGRAMS)
                 and "paged_attention_window" in k)
    if kernel <= 0:
        return None
    try:
        count = work.load_arch(work.arch_path(run.config)).decode_window_kv_bytes
    except (AttributeError, ValueError):
        return None
    contexts = [r.prompt_len + j for r in run.records
                for j, s in enumerate(r.stamps)
                if j > 0 and run.t_open <= s <= run.t_close]
    if not contexts:
        return None
    least_per_s = (count(run.config, contexts)
                   / run.peaks["hbm_bytes_per_s"] / run.seconds)
    # kernel seconds are summed over chips, and so is the bandwidth
    taken_per_s = kernel / run.trace["window_s"] / run.trace["chips"]
    return 100.0 * least_per_s / run.chips / taken_per_s
