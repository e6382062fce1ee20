"""Client side: 90th percentile over counted requests of the largest
gap between two of a request's chunks."""
import stats


def read(run):
    vals = [v for v in (stats.largest_gap_ms(r) for r in run.counted if r.ok)
            if v is not None]
    return stats.percentile(vals, 90)
