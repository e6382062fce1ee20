"""FLEET evidence record: schema + builder (docs/design/fleet-sim.md).

``FLEET_r0N.json`` is the fleet-level evidence record: per-phase
TTFT/TPOT percentiles and per-stratum percentiles, the scale
events the autoscaler actually applied, the fault ledger (every armed
site with its fired counts), the prefix-hit-rate window per phase, and
an ``slo`` block whose fields are the acceptance criteria themselves —
``tools/check_fleet_record.py`` gates them in CI so a regression that
quietly drops a fleet property fails the build instead of shipping a
blind record.
"""

from __future__ import annotations

import json
import pathlib

# THE percentile builder — shared with the bench legs so FLEET and
# BENCH records can never drift on convention
from fusioninfer_tpu.benchmark.loadgen import pcts_ms

FLEET_SCHEMA_VERSION = "fleet-v1"


def phase_summary(rows: list[dict]) -> dict:
    """One phase's request rows → counts + latency percentiles, overall
    and per stratum."""
    strata: dict[str, list[dict]] = {}
    for r in rows:
        strata.setdefault(r["stratum"], []).append(r)
    out = {
        "requests": len(rows),
        "ok": sum(1 for r in rows if r["ok"]),
        "lost": sum(1 for r in rows if r["lost"]),
        "corrupted": sum(1 for r in rows if r["corrupted"]),
        "retried": sum(1 for r in rows if r["attempts"] > 1),
        "held_429": sum(r.get("held_429", 0) for r in rows),
        "ttft_ms": pcts_ms([r["ttft_s"] for r in rows
                            if r["ttft_s"] is not None]),
        "tpot_ms": pcts_ms([r["tpot_s"] for r in rows
                            if r["tpot_s"] is not None]),
        "strata": {
            name: {
                "requests": len(rs),
                "ok": sum(1 for r in rs if r["ok"]),
                "lost": sum(1 for r in rs if r["lost"]),
                "held_429": sum(r.get("held_429", 0) for r in rs),
                "ttft_ms": pcts_ms([r["ttft_s"] for r in rs
                                    if r["ttft_s"] is not None]),
                "tpot_ms": pcts_ms([r["tpot_s"] for r in rs
                                    if r["tpot_s"] is not None]),
            }
            for name, rs in sorted(strata.items())
        },
    }
    return out


def build_record(*, config: dict, phases: dict, scale_events: list,
                 fault_ledger: list, hit_rates: dict, slo: dict,
                 event_ledger: list, duration_s: float) -> dict:
    return {
        "schema": FLEET_SCHEMA_VERSION,
        "config": config,
        "duration_s": round(duration_s, 3),
        "phases": phases,
        "scale_events": scale_events,
        "fault_ledger": fault_ledger,
        "prefix_hit_rate": hit_rates,
        "slo": slo,
        "event_ledger": event_ledger,
    }


def write_record(record: dict, path: str | pathlib.Path) -> pathlib.Path:
    path = pathlib.Path(path)
    path.write_text(json.dumps(record, indent=1, sort_keys=False) + "\n")
    return path
