"""Trace: device time inside the decode programs (jit_decode_burst and
jit_fused_step) over all device busy time."""


def read(run):
    if not run.trace:
        return None
    ops = run.trace["ops"]
    total = sum(ops.values())
    inside = sum(v for k, v in ops.items()
                 if k.split("/", 1)[0].endswith(("decode_burst", "fused_step")))
    return 100.0 * inside / total if total > 0 and inside > 0 else None
