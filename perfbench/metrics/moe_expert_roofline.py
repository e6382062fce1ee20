"""The routed experts' grouped matrix product's share of its roofline.

Least time, from the program's own counters over the window: the larger
of the bytes of the held experts that had at least one row (each touch
reads the expert's three matrices once: 3 x hidden x expert width x 2
bytes) at the chip's HBM bandwidth, and the arithmetic of the rows
computed (each local assignment through the three matrices, 2 FLOP a
multiply-add) at the chip's bf16 peak, as a rate per second of window.
Time taken: the summed device time of the grouped-matmul kernel's events
in the traced window, as a rate per second of traced window.  Bound:
memory while an expert sees fewer than ~240 rows a touch (the v5e's
ridge), as in every decode pass.  Returns nothing where the trace has no
such kernel (the product is not a kernel there) or the program has no
such counters."""


def read(run):
    if not run.trace or not run.peaks:
        return None
    touches = run.delta("fusioninfer:moe_expert_touches_total")
    local = run.delta("fusioninfer:moe_assignments_local_total")
    if not touches or local is None:
        return None
    kernel = sum(v for k, v in run.trace["ops"].items()
                 if "gmm" in k.split("/", 1)[-1])
    if kernel <= 0:
        return None
    per_expert = 3 * run.config["hidden_size"] * run.config["moe_intermediate_size"]
    least_s = max(touches * per_expert * 2 / run.peaks["hbm_bytes_per_s"],
                  local * per_expert * 2 / run.peaks["flops_bf16"])
    taken_per_s = kernel / run.trace["window_s"] / run.trace["chips"]
    return 100.0 * least_s / run.seconds / run.chips / taken_per_s
