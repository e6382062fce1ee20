"""Rows a held expert computes each time its weights are read: local
assignments over expert touches (experts with at least one row, summed
over expert layers and forward passes), over the window.  An expert's
matrices cost the same to read for one row as for a hundred: this is how
well each read is filled.  Nothing where the program has no such
counters."""


def read(run):
    local = run.delta("fusioninfer:moe_assignments_local_total")
    touches = run.delta("fusioninfer:moe_expert_touches_total")
    if local is None or not touches:
        return None
    return local / touches
