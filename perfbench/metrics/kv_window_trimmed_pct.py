"""Share of the window kind's KV pages handed out in the window that
were given back because the sliding window had passed them, over the
window: the program's own counters, ``fusioninfer:
kv_window_pages_trimmed_total`` over ``fusioninfer:
kv_window_pages_allocated_total`` (the ``kind="window"`` sample of
``fusioninfer:kv_pages_allocated_total`` under a family of its own: the
harness sums a family's samples, so a label cannot be read apart).  A
request that runs past the window gives back what it no longer sees
while it still decodes; one that never reaches it gives back nothing
before it ends.  Nothing where the program keeps no cache by layer kind
(it has no such counters)."""


def read(run):
    trimmed = run.delta("fusioninfer:kv_window_pages_trimmed_total")
    handed_out = run.delta("fusioninfer:kv_window_pages_allocated_total")
    if trimmed is None or not handed_out:
        return None
    return 100.0 * trimmed / handed_out
