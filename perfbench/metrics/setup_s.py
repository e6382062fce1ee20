"""Process start -> the window opens: launch, weights, AOT load, warm
tour, ramp."""


def read(run):
    return run.timing.get("setup_s")
