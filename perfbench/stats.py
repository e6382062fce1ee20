"""The arithmetic from client records to end-to-end metrics.  Kept with
the benchmark so that no later PR can change how a number is taken."""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float | None:
    """Linear-interpolated percentile (numpy's default); None for none.
    A missing value (a failed request) is passed as ``math.inf`` and
    sorts last, so it counts as having missed any latency."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if xs[lo] == math.inf or xs[hi] == math.inf:
        return math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def ttft_ms(rec) -> float:
    """Due time (open loop) or send time (closed) -> first token."""
    start = rec.due if rec.due is not None else rec.sent
    if not rec.stamps or start is None:
        return math.inf
    return (rec.stamps[0] - start) * 1e3


def tpot_ms(rec) -> float | None:
    """(last token - first token) / (tokens - 1) of one request."""
    if len(rec.stamps) < 2:
        return None
    return (rec.stamps[-1] - rec.stamps[0]) / (len(rec.stamps) - 1) * 1e3


def largest_gap_ms(rec) -> float | None:
    if len(rec.stamps) < 2:
        return None
    return max(b - a for a, b in zip(rec.stamps, rec.stamps[1:])) * 1e3


def tokens_in(records, t0: float, t1: float) -> int:
    """Output tokens whose chunk a client received inside [t0, t1]."""
    return sum(1 for r in records for s in r.stamps if t0 <= s <= t1)


def silences(records, t0: float, t1: float, least_s: float = 0.25) -> list:
    """Stretches of [t0, t1] of ``least_s`` or more in which no client
    received a token, longest first, as [offset from t0, length] in
    seconds: a server that stops for all its streams at once (a program
    loaded on first use, a pause of the host) shows here and nowhere in
    a median."""
    stamps = sorted(s for r in records for s in r.stamps if t0 <= s <= t1)
    edges = [t0] + stamps + [t1]
    out = [[a - t0, b - a] for a, b in zip(edges, edges[1:]) if b - a >= least_s]
    return sorted(out, key=lambda g: -g[1])
