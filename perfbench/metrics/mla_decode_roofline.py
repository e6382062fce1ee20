"""The latent (MLA) paged-attention kernel's share of its roofline.

Least time: for the output tokens of the window (each at its own
context, from the client's record), the larger of the latent bytes they
must read (``decode_kv_bytes`` of the architecture's file: one latent
row a layer and position) at the chip's HBM bandwidth and the
arithmetic of attending straight over latent rows
(``decode_attn_flops``: the absorbed form, every head against a row
once) at the chip's bf16 peak, as a rate per second of window.  Time
taken: the summed device time of the latent kernel's events inside the
two programs that produce output tokens, ``jit_decode_burst`` and
``jit_fused_step``, as a rate per second of traced window.  Prompt
chunks of a fused step go through the same kernel calls and their work
is not counted, so the share reads low, never high.  Bound: whichever
of the two is larger (at published widths the two lie close: 241 FLOP a
byte, the v5e's ridge).  Returns nothing where the trace has no such
kernel or the architecture's file has no such count."""
import work

DECODE_PROGRAMS = ("decode_burst", "fused_step")


def read(run):
    if not run.trace or not run.peaks:
        return None
    arch = work.load_arch(work.arch_path(run.config))
    if not hasattr(arch, "decode_attn_flops"):
        return None
    kernel = sum(v for k, v in run.trace["ops"].items()
                 if k.split("/", 1)[0].endswith(DECODE_PROGRAMS)
                 and "mla_ragged_paged_attention" in k)
    if kernel <= 0:
        return None
    contexts = [r.prompt_len + j for r in run.records
                for j, s in enumerate(r.stamps)
                if j > 0 and run.t_open <= s <= run.t_close]
    if not contexts:
        return None
    least_s = max(
        arch.decode_kv_bytes(run.config, contexts) / run.peaks["hbm_bytes_per_s"],
        arch.decode_attn_flops(run.config, contexts) / run.peaks["flops_bf16"])
    taken_per_s = kernel / run.trace["window_s"] / run.trace["chips"]
    return 100.0 * least_s / run.seconds / run.chips / taken_per_s
