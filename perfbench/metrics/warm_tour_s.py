"""The harness's clock around its warm-tour requests."""


def read(run):
    return run.timing.get("warm_tour_s")
