#!/usr/bin/env python3
"""Find the knee of an open-loop mix once, on the chip: one server
process, several offered rates, a window each.  The knee is the highest
rate at which the server's waiting queue does not grow over the window
and the generator is not late.  The cell then offers 4/5 of it, as a
number in the mix's file: a run never searches.

    python3 perfbench/sweep.py --workload <open-loop cell> --rates 2,3,4,5,6 --seconds 30
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import load  # noqa: E402
import run as runmod  # noqa: E402
import serverproc  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"))
    args = ap.parse_args()
    bench = runmod.load_json(args.benchmark)
    cell, entry = runmod.find_cell(bench, args.workload)
    config = runmod.load_json(os.path.join(ROOT, entry["file"]))
    mix = traffic.load(traffic.traffic_path(ROOT, cell["traffic"]))
    serve = config["serve"]
    work = os.path.join(ROOT, ".perfbench_run")
    os.makedirs(work, exist_ok=True)
    cache = runmod.compile_cache_dir(ROOT)
    server = serverproc.Server(ROOT, serve["model"], list(serve["flags"]), args.seed,
                               args.platform, {},
                               os.path.join(work, "sweep_server.log"), cache, None)
    server.start()
    rows = []
    try:
        info, ready = server.wait_ready(1100.0)
        print("[engine]", json.dumps({k: v for k, v in info.items() if k != "devices"}), flush=True)
        runmod.warm_tour(server, serve["model"], mix, args.seed)
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            # the mix's own rows, their instants squeezed to this rate
            sched = traffic.schedule(mix, args.seconds, serve["max_batch_size"],
                                     stretch=float(mix["rate_rps"]) / rate)
            t_open = time.monotonic() + sched["ramp_s"]
            loop = load.OpenLoop(server.base, serve["model"], args.seed + k,
                                 sched["requests"], [], t_open)
            loop.start()
            polls = []
            c0 = None
            while time.monotonic() < t_open + args.seconds:
                time.sleep(1.0)
                mm = server.metrics()
                if time.monotonic() >= t_open:
                    c0 = c0 or mm
                    polls.append((time.monotonic() - t_open,
                                  mm.get("vllm:num_requests_waiting", 0.0),
                                  mm.get("vllm:num_requests_running", 0.0)))
            t_close = t_open + args.seconds
            counted = [r for r in loop.records if t_open <= r.due <= t_close]
            loop.wait_counted(counted, t_close + 40.0)
            loop.cut()
            half = len(polls) // 2
            w1 = sum(p[1] for p in polls[:half]) / max(1, half)
            w2 = sum(p[1] for p in polls[half:]) / max(1, len(polls) - half)
            late = [(r.sent - r.due) * 1e3 for r in counted if r.sent is not None]
            row = {
                "rate_rps": rate, "offered": len(counted),
                "failed": sum(1 for r in counted if not r.ok),
                "waiting_first_half": w1, "waiting_second_half": w2,
                "waiting_max": max((p[1] for p in polls), default=0.0),
                "running_mean": sum(p[2] for p in polls) / max(1, len(polls)),
                "ttft_p50_ms": stats.percentile([stats.ttft_ms(r) for r in counted], 50),
                "ttft_p90_ms": stats.percentile([stats.ttft_ms(r) for r in counted], 90),
                "tpot_p50_ms": stats.percentile(
                    [v for v in (stats.tpot_ms(r) for r in counted if r.ok) if v], 50),
                "gen_late_p90_ms": stats.percentile(late, 90),
                "out_tokens_per_s": stats.tokens_in(loop.records, t_open, t_close) / args.seconds,
            }
            rows.append(row)
            print("[sweep]", json.dumps(row), flush=True)
            time.sleep(3.0)
    finally:
        server.stop()
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "sweep.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
