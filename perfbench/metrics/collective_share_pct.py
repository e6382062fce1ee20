"""Trace: all-reduce / all-gather / reduce-scatter / permute / all-to-all
time over all device busy time (cells that shard over chips)."""

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def read(run):
    if not run.trace or run.chips < 2:
        return None
    ops = run.trace["ops"]
    total = sum(ops.values())
    inside = sum(v for k, v in ops.items()
                 if any(s in k.split("/", 1)[-1] for s in COLLECTIVES))
    return 100.0 * inside / total if total > 0 and inside > 0 else None
