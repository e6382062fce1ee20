"""How late the generator ran: 90th percentile of send time - due time
over the counted requests of an open loop."""
import stats


def read(run):
    late = [(r.sent - r.due) * 1e3 for r in run.counted
            if r.due is not None and r.sent is not None]
    return stats.percentile(late, 90)
