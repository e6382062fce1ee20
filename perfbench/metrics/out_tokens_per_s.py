"""Every output token whose chunk a client received inside the window,
over the window's seconds: the whole replica, not per chip."""
import stats


def read(run):
    return stats.tokens_in(run.records, run.t_open, run.t_close) / run.seconds
