"""The reduction from a profiler trace (``.xplane.pb``) to numbers: the
union of the intervals in which an operation ran on each chip, the idle
share, the time of every operation by name, and each idle gap named by
what the chip ran next (``before:<program>``) or was in the middle of
(``inside:<program>``).

Checked on the small trace kept in ``tests/data`` against hand-counted
values (``tests/test_trace.py``).  Run as a child process so that the
harness itself never imports jax:

    python perfbench/xtrace.py <file.xplane.pb> <out.json>
"""

from __future__ import annotations

import bisect
import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"([._-]\d+)+$")
_MODULE_ID = re.compile(r"\(\d+\)$")


def base_name(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``%fusion.1 = ...`` -> ``fusion``."""
    name = name.split(" = ", 1)[0].lstrip("%").strip()
    return _SUFFIX.sub("", name) or name


def module_name(name: str) -> str:
    """``jit_decode_burst(123456)`` -> ``jit_decode_burst``."""
    return _MODULE_ID.sub("", name.strip())


def leaves(events: list[tuple[int, int, str]]) -> list[tuple[int, int, str]]:
    """Drop events that enclose a later one (a ``while`` around its
    body): only leaves carry time of their own.  ``events`` sorted by
    (start, -end)."""
    out = []
    for i, (s, e, n) in enumerate(events):
        if i + 1 < len(events) and events[i + 1][0] < e and events[i + 1][1] <= e:
            continue
        out.append((s, e, n))
    return out


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def reduce_planes(planes: dict[int, dict]) -> dict:
    """``planes``: chip -> {"ops": [(start_ns, end_ns, name)], "modules":
    [(start_ns, end_ns, name)]}.  Pure arithmetic, no jax."""
    if not planes or not any(p["ops"] for p in planes.values()):
        return {"chips": 0}
    t0 = min(s for p in planes.values() for s, _, _ in p["ops"])
    t1 = max(e for p in planes.values() for _, e, _ in p["ops"])
    ops: dict[str, float] = {}
    gaps: dict[str, float] = {}
    busy_by_chip: dict[int, float] = {}
    for chip, p in sorted(planes.items()):
        mods = sorted(p["modules"])
        mod_starts = [m[0] for m in mods]

        def module_at(t: int) -> int:
            """Index of the program running at ``t``, -1 for none."""
            i = bisect.bisect_right(mod_starts, t) - 1
            return i if i >= 0 and mods[i][0] <= t < mods[i][1] else -1

        def name_of(i: int) -> str:
            return module_name(mods[i][2]) if i >= 0 else "no_module"

        evs = leaves(sorted(p["ops"], key=lambda e: (e[0], -e[1])))
        for s, e, n in evs:
            key = f"{name_of(module_at(s))}/{base_name(n)}"
            ops[key] = ops.get(key, 0.0) + (e - s) * 1e-9
        merged = union([(s, e) for s, e, _ in evs])
        busy_by_chip[chip] = sum(e - s for s, e in merged) * 1e-9
        # a gap is named by the program that runs next; a gap between
        # two operations of one launch of a program is inside it
        edges = [(t0, t0)] + merged
        for (_, prev_end), (next_start, _) in zip(edges, edges[1:]):
            if next_start <= prev_end:
                continue
            after = module_at(next_start)
            kind = ("inside" if after >= 0 and prev_end > t0
                    and module_at(prev_end - 1) == after else "before")
            name = f"{kind}:{name_of(after)}"
            gaps[name] = gaps.get(name, 0.0) + (next_start - prev_end) * 1e-9
        if merged[-1][1] < t1:
            gaps["after:last_op"] = (gaps.get("after:last_op", 0.0)
                                     + (t1 - merged[-1][1]) * 1e-9)
    window = (t1 - t0) * 1e-9
    n = len(busy_by_chip)
    return {
        "chips": n,
        "window_s": window,
        "busy_s": sum(busy_by_chip.values()) / n,
        "busy_s_by_chip": busy_by_chip,
        "idle_pct_worst": 100.0 * (1.0 - min(busy_by_chip.values()) / window),
        # per-name times are summed over chips; gaps likewise
        "ops": ops,
        "gaps": gaps,
    }


def read_planes(path: str) -> dict[int, dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes: dict[int, dict] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        entry = {"ops": [], "modules": []}
        for line in plane.lines:
            if line.name == OPS_LINE:
                dest = entry["ops"]
            elif line.name == MODULES_LINE:
                dest = entry["modules"]
            else:
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                dest.append((s, s + int(ev.duration_ns), ev.name))
        planes[int(m.group(1))] = entry
    return planes


def top(d: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def main(argv: list[str]) -> int:
    red = reduce_planes(read_planes(argv[1]))
    with open(argv[2], "w") as f:
        json.dump(red, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
