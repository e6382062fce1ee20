"""``mixedlen-saturated``: its prompts are a MIXTURE of two clipped
log-normals, which ``test_traffic.py``'s one-distribution check of
``drawn_from`` cannot state (that check reads ``drawn_from.prompt`` as ONE
log-normal, so it cannot hold this file; PERF.md, Open questions).  The
rows are held here to what the file says of itself: 128 + 128
mid-quantiles, the outputs' 256, 4 whole cycles of the grid, and the
Latin square's promise that ANY 16 rows in a row hold 8 short and 8 long
prompts, one of every prompt stratum."""

import collections
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import traffic  # noqa: E402
from test_traffic import lognormal_quantiles  # noqa: E402


def test_rows_are_the_two_components_and_the_square():
    mix = traffic.load(traffic.traffic_path(
        os.path.dirname(os.path.dirname(HERE)), "mixedlen-saturated"))
    g = mix["drawn_from"]
    short = lognormal_quantiles(128, g["prompt_short"])
    long_ = lognormal_quantiles(128, g["prompt_long"])
    outputs = lognormal_quantiles(g["n"], g["output"])
    assert max(short) <= 2048 < 4096 <= min(long_) and max(long_) == 14336
    rows = mix["requests"]
    assert len(rows) == 4 * g["n"]
    for c in range(4):  # every cycle is exactly the grid
        cycle = rows[c * 256:(c + 1) * 256]
        assert collections.Counter(p for p, _ in cycle) == collections.Counter(
            short + long_)
        assert collections.Counter(o for _, o in cycle) == collections.Counter(
            outputs)
    assert 660 < sum(short) / 128 < 680 and 8500 < sum(long_) / 128 < 8650
    assert 4600 < sum(p for p, _ in rows) / len(rows) < 4660
    assert 425 < sum(o for _, o in rows) / len(rows) < 440
    assert max(p + o for p, o in rows) <= 15616 <= 16384 - 8
    strata = {p: i // 16 for i, p in enumerate(sorted(short + long_))}
    for i in range(len(rows) - 16):
        window = [p for p, _ in rows[i:i + 16]]
        assert sum(p <= 2048 for p in window) == 8, i
        if i % 16 == 0:
            out_strata = sorted(sorted(outputs).index(o) // 16
                                for _, o in rows[i:i + 16])
            assert len(set(strata[p] for p in window)) >= 15, i
            assert len(set(out_strata)) >= 14, i
    s = traffic.schedule(mix, 51.0, 32)
    assert s["clients"] == 64 and s["loop"] == "closed"
