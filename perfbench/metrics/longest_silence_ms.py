"""Client side: the longest stretch of the window in which no client
received a token (a server that stops for every stream at once)."""


def read(run):
    stamps = sorted(s for r in run.records for s in r.stamps
                    if run.t_open <= s <= run.t_close)
    edges = [run.t_open] + stamps + [run.t_close]
    return max(b - a for a, b in zip(edges, edges[1:])) * 1e3
