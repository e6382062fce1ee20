"""The decode attention kernel's share of its memory roofline.

Least time: the KV bytes that the output tokens of the window must read
(per token: its context x 2 x layers x kv_heads x head_dim x 2 bytes,
from the client's own record and the configuration's file) at the
chip's HBM bandwidth, as a rate per second of window.  Time taken: the
summed device time of the paged-attention kernel's events inside the
decode programs, all chips, as a rate per second of traced window times
the chips (each chip reads its own share of the heads).  The decode
programs are the two that produce output tokens, ``jit_decode_burst``
and ``jit_fused_step`` (the programs of ``decode_program_share_pct``):
a token decoded as a row of a fused step is in the bytes, so that
step's kernel time is in the seconds.  The prompt chunks of a fused
step go through the same kernel calls and their reads are not counted,
so the share reads low, never high.  Work is defined by the traffic,
not by the kernel.  Bound: memory.  Returns nothing where the trace has
no such kernel."""
import work

DECODE_PROGRAMS = ("decode_burst", "fused_step")


def read(run):
    if not run.trace or not run.peaks:
        return None
    kernel = sum(v for k, v in run.trace["ops"].items()
                 if k.split("/", 1)[0].endswith(DECODE_PROGRAMS)
                 and "paged_attention" in k)
    if kernel <= 0:
        return None
    contexts = [r.prompt_len + j for r in run.records
                for j, s in enumerate(r.stamps)
                if j > 0 and run.t_open <= s <= run.t_close]
    if not contexts:
        return None
    least_per_s = (work.decode_kv_bytes(run.config, contexts)
                   / run.peaks["hbm_bytes_per_s"] / run.seconds)
    # kernel seconds are summed over chips, and so is the bandwidth
    taken_per_s = kernel / run.trace["window_s"] / run.trace["chips"]
    return 100.0 * least_per_s / run.chips / taken_per_s
