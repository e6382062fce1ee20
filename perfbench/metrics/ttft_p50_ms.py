"""Median over all counted requests of due time -> first streamed token
(a failed request counts as never answered)."""
import stats


def read(run):
    return stats.percentile([stats.ttft_ms(r) for r in run.counted], 50)
