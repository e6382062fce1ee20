"""Trace: 1 - union of device operation intervals over the traced
window, on the chip that was idle most."""


def read(run):
    return run.trace["idle_pct_worst"] if run.trace else None
