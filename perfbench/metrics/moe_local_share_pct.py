"""Share of the routers' (token, expert) assignments that chose an expert
held by this process, over the window: held / published experts where
routing is even (40 of 160: 25 %).  The rest is what the absent chips of
the deployment would compute.  Nothing where the program has no such
counters."""


def read(run):
    local = run.delta("fusioninfer:moe_assignments_local_total")
    every = run.delta("fusioninfer:moe_assignments_total")
    if local is None or not every:
        return None
    return 100.0 * local / every
