"""Trace: time in Pallas custom calls (the attention kernels) over all
device busy time."""

KERNELS = ("paged_attention", "flash_attention", "custom-call", "pallas")


def read(run):
    if not run.trace:
        return None
    ops = run.trace["ops"]
    total = sum(ops.values())
    inside = sum(v for k, v in ops.items()
                 if any(s in k.split("/", 1)[-1] for s in KERNELS))
    return 100.0 * inside / total if total > 0 and inside > 0 else None
