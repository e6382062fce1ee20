"""The whole step's share of the chips' peak: model FLOPs of every token
the window processed (each output token at its own context, each prompt
whose first token arrived in the window as one causal prefill) over
window seconds x chips x peak bf16 FLOP/s.  Counts come from the
client's record, sizes from the configuration's file."""
import work


def read(run):
    if not run.peaks:
        return None
    flops = 0.0
    for r in run.records:
        for j, s in enumerate(r.stamps):
            if not run.t_open <= s <= run.t_close:
                continue
            if j == 0:
                flops += work.prompt_flops(run.config, r.prompt_len)
            else:
                flops += work.token_flops(run.config, r.prompt_len + j)
    if flops <= 0:
        return None
    return 100.0 * flops / (run.seconds * run.chips * run.peaks["flops_bf16"])
