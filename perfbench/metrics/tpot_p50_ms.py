"""Median over counted requests of (last token - first token) /
(tokens - 1), on the client's clock."""
import stats


def read(run):
    vals = [v for v in (stats.tpot_ms(r) for r in run.counted if r.ok)
            if v is not None]
    return stats.percentile(vals, 50)
