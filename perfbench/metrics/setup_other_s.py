"""What set-up spends outside the server's launch, the warm tour and the
ramp: the harness's own start, schedule and scrapes."""


def read(run):
    t = run.timing
    if "setup_s" not in t:
        return None
    return (t["setup_s"] - t["launch_to_ready_s"] - t["warm_tour_s"]
            - t["ramp_s"])
