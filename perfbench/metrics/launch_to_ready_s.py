"""Spawn of the server child -> /health answers 200."""


def read(run):
    return run.timing.get("launch_to_ready_s")
