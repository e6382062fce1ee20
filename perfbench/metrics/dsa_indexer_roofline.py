"""The sparse-attention indexer kernel's share of its roofline.

Least time, from the client's own record and the architecture's counts
(``indexer_flops`` / ``indexer_bytes`` / ``prompt_indexer_flops`` of
``perfbench/arch/<model_type>.py``): the indexer over the whole context
of every output token of the window, each at its own context, and over
the causal triangle of every prompt whose first token arrived in the
window; the larger of its indexer-key bytes (an output token reads its
whole context's keys; a prompt's queries share its keys, read once) at
the chip's HBM bandwidth and its FLOPs at the bf16 peak, as a rate per
second of window.  Time
taken: the summed device time of the indexer kernel's events
(``indexer_paged_scores``) in every program, as a rate per second of
traced window times the chips.  Returns nothing where the trace has no
such kernel or the architecture's file has no such count."""
import work


def read(run):
    if not run.trace or not run.peaks:
        return None
    kernel = sum(v for k, v in run.trace["ops"].items()
                 if "indexer_paged_scores" in k.split("/", 1)[-1])
    if kernel <= 0:
        return None
    try:
        arch = work.load_arch(work.arch_path(run.config))
        flops_of, bytes_of = arch.indexer_flops, arch.indexer_bytes
        prompt_flops_of = arch.prompt_indexer_flops
    except (AttributeError, ValueError):
        return None
    contexts, flops, prompt_keys = [], 0.0, 0
    for r in run.records:
        for j, s in enumerate(r.stamps):
            if not run.t_open <= s <= run.t_close:
                continue
            if j == 0:
                n = r.prompt_len
                flops += prompt_flops_of(run.config, n)
                prompt_keys += n
            else:
                contexts.append(r.prompt_len + j)
    flops += flops_of(run.config, contexts)
    key_bytes = bytes_of(run.config, contexts + [prompt_keys])
    if flops <= 0:
        return None
    least_s = max(key_bytes / run.peaks["hbm_bytes_per_s"],
                  flops / run.peaks["flops_bf16"])
    taken_per_s = kernel / run.trace["window_s"] / run.trace["chips"]
    return 100.0 * least_s / run.seconds / run.chips / taken_per_s
