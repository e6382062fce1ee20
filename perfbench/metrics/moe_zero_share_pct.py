"""Share of the routers' (token, expert) assignments that chose an
identity ("zero-compute") expert, over the window: identity outputs /
the router's width where routing is even (256 of 768: 33.3 %).  Such an
assignment returns the token's own input, weighted, where the token
lives: no weights are read and nothing is exchanged, so it is the share
of the expert layer's choices that cost this chip (and the deployment)
nothing.  Nothing where the program has no such counter."""


def read(run):
    zero = run.delta("fusioninfer:moe_assignments_zero_total")
    every = run.delta("fusioninfer:moe_assignments_total")
    if zero is None or not every:
        return None
    return 100.0 * zero / every
