#!/usr/bin/env python3
"""What the host was doing while the device was idle: each idle gap of
the device planes of an ``.xplane.pb`` (named as ``xtrace.py`` names them,
``before:<program>`` / ``inside:<program>``), split by overlap among the
engine thread's host spans that were open during it.  The spans are the
program's ``TraceAnnotation``s (``fusioninfer_tpu/utils/spans.py``:
``step``, ``step.admit``, ..., ``loop.publish``) on the host plane, which
the profiler stamps on the device planes' clock; where spans nest, the
time goes to the innermost, so the seconds add up.  Also the device's time
by ``jax.named_scope`` (``attn_qkv``, ``attn``, ``mlp``, ...): the scope
path arrives in the ``tf_op`` stat of each ``XLA Ops`` event's metadata.

Run by hand, as ``sweep.py`` is; checked on the small trace kept in
``tests/data`` (``tests/test_hostgaps.py``):

    python3 perfbench/hostgaps.py <file.xplane.pb> [out.json]
"""

from __future__ import annotations

import bisect
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import xtrace  # noqa: E402

HOST_PLANE = "/host:CPU"
SPAN = re.compile(r"^(step|loop)(\.[a-z_]+)?$")
NO_SPAN = "(no span)"
SCOPES = ("attn_qkv", "attn", "attn_out", "mlp", "lm_head", "sample",
          "kv_write")
NO_SCOPE = "(no scope)"


def self_segments(spans: list[tuple[int, int, str]]) -> list[tuple[int, int, str]]:
    """Nested spans of one thread -> disjoint ``(start, end, name)``
    pieces, each named by the innermost span open there."""
    out: list[tuple[int, int, str]] = []
    stack: list[tuple[int, int, str]] = []
    cursor = 0

    def close_until(t: int) -> None:
        nonlocal cursor
        while stack and stack[-1][1] <= t:
            _, end, name = stack.pop()
            if cursor < end:
                out.append((cursor, end, name))
                cursor = end

    for s, e, name in sorted(spans, key=lambda ev: (ev[0], -ev[1])):
        close_until(s)
        if stack and cursor < s:
            out.append((cursor, s, stack[-1][2]))
        cursor = max(cursor, s)
        stack.append((s, e, name))
    close_until(max((e for _, e, _ in spans), default=0))
    return out


def gap_intervals(plane: dict, t0: int, t1: int) -> list[tuple[int, int, str]]:
    """One chip's idle gaps inside ``[t0, t1]`` as ``(start, end, name)``,
    named as ``xtrace.reduce_planes`` names them."""
    mods = sorted(plane["modules"])
    starts = [m[0] for m in mods]

    def module_at(t: int) -> int:
        i = bisect.bisect_right(starts, t) - 1
        return i if i >= 0 and mods[i][0] <= t < mods[i][1] else -1

    evs = xtrace.leaves(sorted(plane["ops"], key=lambda e: (e[0], -e[1])))
    merged = xtrace.union([(s, e) for s, e, _ in evs])
    out = []
    edges = [(t0, t0)] + merged
    for (_, prev_end), (next_start, _) in zip(edges, edges[1:]):
        if next_start <= prev_end:
            continue
        after = module_at(next_start)
        kind = ("inside" if after >= 0 and prev_end > t0
                and module_at(prev_end - 1) == after else "before")
        prog = xtrace.module_name(mods[after][2]) if after >= 0 else "no_module"
        out.append((prev_end, next_start, f"{kind}:{prog}"))
    if merged and merged[-1][1] < t1:
        out.append((merged[-1][1], t1, "after:last_op"))
    return out


def attribute(gaps: list[tuple[int, int, str]],
              segments: list[tuple[int, int, str]]) -> dict[str, dict[str, float]]:
    """gap name -> {span name: seconds}; what no span covers is NO_SPAN."""
    seg_starts = [s for s, _, _ in segments]
    out: dict[str, dict[str, float]] = {}
    for g0, g1, gname in gaps:
        row = out.setdefault(gname, {})
        covered = 0
        i = max(0, bisect.bisect_right(seg_starts, g0) - 1)
        while i < len(segments) and segments[i][0] < g1:
            s, e, name = segments[i]
            lap = min(e, g1) - max(s, g0)
            if lap > 0:
                row[name] = row.get(name, 0.0) + lap * 1e-9
                covered += lap
            i += 1
        if g1 - g0 > covered:
            row[NO_SPAN] = row.get(NO_SPAN, 0.0) + (g1 - g0 - covered) * 1e-9
    return out


def scope_of(op_path: str) -> str | None:
    """``jit(decode_burst)/while/body/attn_qkv/dot_general`` -> ``attn_qkv``:
    the innermost of the program's scopes on the operation's path."""
    for part in reversed(op_path.split("/")):
        if part in SCOPES:
            return part
    return None


def read_host_spans(path: str) -> list[tuple[int, int, str]]:
    """The span events of the host plane's engine-thread line(s); a
    span that names its ``program`` is kept apart by it."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if SPAN.match(ev.name):
                    s = int(ev.start_ns)
                    program = dict(ev.stats).get("program")
                    spans.append((s, s + int(ev.duration_ns), ev.name + (
                        f"[{program}]" if program else "")))
    return spans


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """One protobuf message -> ``(field number, value)``: an int for a
    varint, a memoryview for a length-delimited field (fixed-width
    fields, which nothing here reads, come as their bytes)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            size = {1: 8, 5: 4}.get(wire)
            if size is None:  # 2: length-delimited
                size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _map_entry(buf) -> tuple[int, memoryview]:
    entry = dict(_fields(buf))
    return entry.get(1, 0), entry.get(2, memoryview(b""))


def read_scope_seconds(path: str) -> dict[str, float]:
    """Device seconds of leaf operations by named scope, summed over chips
    ({} where no operation carries a scope path).  The path is the
    ``tf_op`` stat of an ``XLA Ops`` event's METADATA, which
    ``jax.profiler.ProfileData`` does not expose: the file is read on the
    wire (xplane.proto: XSpace.planes = 1; XPlane.name = 2, lines = 3,
    event_metadata = 4, stat_metadata = 5; XLine.name = 2, timestamp_ns
    = 3, events = 4; XEvent.metadata_id = 1, offset_ps = 2, duration_ps
    = 3; XEventMetadata.stats = 5; XStat.metadata_id = 1, str_value = 5,
    ref_value = 7; XStatMetadata.name = 2)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: dict[str, float] = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        parts = list(_fields(plane))
        name = next((bytes(v).decode() for f_, v in parts if f_ == 2), "")
        if not xtrace.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for f_, v in parts:
            if f_ == 5:
                key, meta = _map_entry(v)
                stat_names[key] = next(
                    (bytes(x).decode() for g, x in _fields(meta) if g == 2), "")
        op_path = {}
        for f_, v in parts:
            if f_ != 4:
                continue
            key, meta = _map_entry(v)
            for g, stat in _fields(meta):
                if g != 5:
                    continue
                st = dict(_fields(stat))
                if stat_names.get(st.get(1)) != "tf_op":
                    continue
                op_path[key] = (bytes(st[5]).decode() if 5 in st
                                else stat_names.get(st.get(7), ""))
        for f_, v in parts:
            if f_ != 3:
                continue
            line = list(_fields(v))
            if next((bytes(x) for g, x in line if g == 2), b"").decode() \
                    != xtrace.OPS_LINE:
                continue
            evs = []
            for g, x in line:
                if g == 4:
                    ev = dict(_fields(x))
                    s = ev.get(2, 0)
                    evs.append((s, s + ev.get(3, 0), op_path.get(ev.get(1), "")))
            for s, e, path_ in xtrace.leaves(
                    sorted(evs, key=lambda ev: (ev[0], -ev[1]))):
                scope = scope_of(path_) or NO_SCOPE
                out[scope] = out.get(scope, 0.0) + (e - s) * 1e-12
    return out if set(out) - {NO_SCOPE} else {}


def reduce_file(path: str) -> dict:
    planes = xtrace.read_planes(path)
    planes = {c: p for c, p in planes.items() if p["ops"]}
    if not planes:
        return {"chips": 0}
    t0 = min(s for p in planes.values() for s, _, _ in p["ops"])
    t1 = max(e for p in planes.values() for _, e, _ in p["ops"])
    segments = self_segments(read_host_spans(path))
    by_gap: dict[str, dict[str, float]] = {}
    for plane in planes.values():
        for gname, row in attribute(gap_intervals(plane, t0, t1),
                                    segments).items():
            dest = by_gap.setdefault(gname, {})
            for span, sec in row.items():
                dest[span] = dest.get(span, 0.0) + sec
    by_span: dict[str, float] = {}
    for row in by_gap.values():
        for span, sec in row.items():
            by_span[span] = by_span.get(span, 0.0) + sec
    return {"chips": len(planes), "window_s": (t1 - t0) * 1e-9,
            "idle_s_by_span": by_span, "idle_s_by_gap": by_gap,
            "host_s_by_span": {
                name: sum(e - s for s, e, n in segments if n == name) * 1e-9
                for name in sorted({n for _, _, n in segments})},
            "device_s_by_scope": read_scope_seconds(path)}


def main(argv: list[str]) -> int:
    red = reduce_file(argv[1])
    if len(argv) > 2:
        with open(argv[2], "w") as f:
            json.dump(red, f)
    if not red["chips"]:
        print("no operation ran on a device plane")
        return 1
    print(f"window {red['window_s']:.4f} s, {red['chips']} chip(s)")
    print("idle seconds of the device by the host span open meanwhile:")
    for span, sec in xtrace.top(red["idle_s_by_span"], 20):
        print(f"  {span:<30} {sec:11.6f}")
    for gname, row in sorted(red["idle_s_by_gap"].items(),
                             key=lambda kv: -sum(kv[1].values()))[:8]:
        total = sum(row.values())
        named = total - row.get(NO_SPAN, 0.0)
        print(f"{gname}: {total:.6f} s, {100.0 * named / total:.1f} % in spans")
        for span, sec in xtrace.top(row, 12):
            print(f"  {span:<30} {sec:11.6f}")
    print("host seconds by span (self time, whole capture):")
    for span, sec in xtrace.top(red["host_s_by_span"], 20):
        print(f"  {span:<30} {sec:11.6f}")
    if red["device_s_by_scope"]:
        print("device seconds by named scope:")
        for scope, sec in xtrace.top(red["device_s_by_scope"], 20):
            print(f"  {scope:<30} {sec:11.6f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
