"""Positions the sparse attention attended over, as a share of the
cached positions its indexer scored, over the window: the program's own
counters (``fusioninfer:dsa_positions_selected_total`` over
``fusioninfer:dsa_positions_scored_total``, decode and chunk rows alike,
every layer).  Returns nothing where the program has no such counters."""


def read(run):
    scored = run.delta("fusioninfer:dsa_positions_scored_total")
    selected = run.delta("fusioninfer:dsa_positions_selected_total")
    if not scored or selected is None:
        return None
    return 100.0 * selected / scored
