"""The share of the engine loop's wall time that no host span covers
(``loop.idle`` and the self time of ``step`` are spans too)."""
import spanread


def read(run):
    loop, covered = run.delta(spanread.LOOP), spanread.all_spans_seconds(run)
    if not loop or covered is None:
        return None
    return 100.0 * (loop - covered) / loop
