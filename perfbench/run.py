#!/usr/bin/env python3
"""perfbench/run.py — one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is a configuration under a traffic mix.  This file finds both, and
the cell's metrics, BY NAME: ``perfbench/configs/<config>.json`` (the
``file`` of the configuration's entry), ``perfbench/traffic/<traffic>.json``
and ``perfbench/metrics/<metric>.py`` — and the configuration's
architecture, ``perfbench/arch/<model_type>.py`` by the ``model_type`` its
file publishes: that architecture's plain forward, which ``reference.py``
judges the served tokens by, and its work counts, which ``work.py`` looks
up.  There is no registry and no default: a later PR adds a cell, a mix, a
metric or an architecture by adding files and entries.

What a run does, in order (everything before "window" is set-up):

0. resolves the architecture's file, and fails at once, naming the path it
   looked for, where the configuration names none or the file is missing;
1. starts ``python -m fusioninfer_tpu.cli engine serve`` as a child with the
   configuration's flags and ``--seed`` (this process never imports jax),
   waits for ``/health`` and fails if the engine reports a demotion the
   configuration does not expect;
2. sends the mix's warm tour, then starts the mix's load; the window opens
   after the ramp;
3. measures for ``--seconds``: clients stamp every streamed token; the
   server's counters are read at both edges; with ``--trace 1`` the server
   is asked for a few seconds of profile in the middle;
4. closes the window (closed loop: clients hang up; open loop: counted
   requests are followed to their end under continuing load), reads the
   server's memory peak and stops it;
5. runs the plain reference (``reference.py``, over the architecture's
   forward) on a sample of the requests the window finished and compares:
   ``correct``;
6. prints the numbers compared beside their limits, then one JSON line.

With ``--trace 0`` the line carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.  Exit code 0 only with a result.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import math
import os
import random
import shutil
import signal
import subprocess
import sys
import threading
import time

T_PROCESS_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import load  # noqa: E402
import peaks  # noqa: E402
import serverproc  # noqa: E402
import stats  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402
import xtrace  # noqa: E402

SERVER_SEED_MOD = 2147483629  # the server's --seed stays a positive int32
TRACE_SECONDS = 3.0
READY_TIMEOUT_S = 1100.0


class RunFailure(Exception):
    """No result line: the run could not measure what it is for."""


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class Run:
    """Everything one run saw; what the metric readers read."""

    def __init__(self):
        self.cell = self.config = self.mix = None
        self.seed = 0
        self.seconds = 0.0
        self.trace_on = False
        self.chips = 1
        self.platform = "tpu"
        self.timing: dict[str, float] = {}
        self.health_ready: dict = {}
        self.health_end: dict = {}
        self.metrics_ready: dict = {}
        self.counters_open: dict = {}
        self.counters_close: dict = {}
        self.polls: list[dict] = []
        self.cache_files_open = self.cache_files_close = 0
        self.records: list = []      # every request of the loop
        self.counted: list = []      # those the cell's metrics are over
        self.t_open = self.t_close = 0.0
        self.trace: dict | None = None
        self.peaks: dict | None = None

    def delta(self, family: str) -> float | None:
        a, b = self.counters_open.get(family), self.counters_close.get(family)
        return None if a is None or b is None else b - a


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str) -> tuple[dict, dict]:
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise RunFailure(f"no workload {workload!r} in the benchmark file; "
                         f"it has {[w['name'] for w in bench['workloads']]}")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, entry


def metric_reader(root: str, name: str):
    path = os.path.join(root, "perfbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(bench: dict, group: str, cell_name: str,
               reported_e2e: set | None = None) -> list[dict]:
    """The entries of ``group`` that this cell reports: those that list
    it, and those that list no cells at all (for a per-layer metric:
    only if the cell reports the end-to-end metric it moves)."""
    out = []
    for m in bench[group]:
        cells = m.get("workloads")
        if cells is not None:
            if cell_name in cells:
                out.append(m)
        elif reported_e2e is None or m["moves"] in reported_e2e:
            out.append(m)
    return out


def compile_cache_dir(bench_root: str) -> str:
    """Where jax's persistent cache lives: the directory named from
    outside, else a fixed one inside the checkout (the path is part of
    the cache's key, so it never moves)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(bench_root, ".xla_cache"))


def cache_file_count(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def check_expectations(info: dict, expect: dict, chips: int) -> None:
    """A kernel demotion (SPMD fallback, per-head grid, interpret mode, a
    CPU) that the configuration's file does not expect fails the run."""
    for key, want in expect.items():
        if key == "kv_splits_min":
            if (info.get("kv_splits") or 0) < want:
                raise RunFailure(f"engine reports kv_splits="
                                 f"{info.get('kv_splits')!r}, expected >= {want}")
        elif info.get(key) != want:
            raise RunFailure(f"engine reports {key}={info.get(key)!r}, the "
                             f"configuration expects {want!r}: "
                             f"{json.dumps(info)[:600]}")
    aot = info.get("aot") or {}
    if not aot.get("entries") or aot.get("errors"):
        raise RunFailure(f"AOT warm-up did not build cleanly: {aot}")
    if len(info.get("devices") or []) != chips:
        raise RunFailure(f"expected {chips} engine devices, the server "
                         f"reports {info.get('devices')}")


def warm_tour(server, model: str, mix: dict, seed: int) -> None:
    """Load every program the mix reaches before the clock matters: one
    long decode as an anchor (so volleys queue behind a burst and are
    admitted as a group), volleys of equal-bucket prompts at power-of-two
    group sizes, and single long prompts whose last chunk lands in each
    flat-token bucket.  Lengths come from the mix's file."""
    warm = mix.get("warm") or {}
    rng = random.Random(seed ^ 0x5EED)
    stop = threading.Event()
    conns: set = set()
    recs, threads = [], []

    def fire(prompt_len: int, out: int) -> None:
        rec = load.Record({"i": -1 - len(recs), "prompt_len": prompt_len,
                           "max_tokens": out}, None)
        rec.prompt = traffic.prompt_text(prompt_len, rng)
        recs.append(rec)
        t = threading.Thread(target=load.stream_one, args=(
            server.base, model, rec, stop, conns), daemon=True)
        t.start()
        threads.append(t)

    gap = float(warm.get("gap_s", 0.3))
    if warm.get("anchor_out"):
        fire(int(warm.get("anchor_prompt", 64)), int(warm["anchor_out"]))
        time.sleep(gap)
    for prompt_len, count, out in warm.get("volleys", []):
        for _ in range(int(count)):
            fire(int(prompt_len), int(out))
        time.sleep(gap)
    deadline = time.monotonic() + float(warm.get("timeout_s", 120.0))
    for t in threads:
        t.join(max(0.1, deadline - time.monotonic()))
    bad = [r for r in recs if not r.ok]
    if bad:
        raise RunFailure(f"warm tour: {len(bad)} of {len(recs)} requests "
                         f"failed, first: {bad[0].error!r} "
                         f"({len(bad[0].tokens)}/{bad[0].max_tokens} tokens)")


def poll_loop(server, run: Run, stop: threading.Event) -> None:
    while not stop.wait(1.0):
        try:
            m = server.metrics()
        except (serverproc.ServerFailure, OSError):
            continue
        m["_t"] = time.monotonic()
        run.polls.append(m)


def take_profile(server, run: Run, at: float, seconds: float) -> None:
    delay = at - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    try:
        status, body = serverproc.http_json(
            server.base, "/debug/profile", {"seconds": seconds},
            timeout=seconds + 120.0)
        run.timing["profile_status"] = status
        if status != 200:
            say(f"[trace] /debug/profile answered {status}: {body}")
    except OSError as e:
        say(f"[trace] /debug/profile failed: {e}")


def newest_xplane(profile_dir: str) -> str | None:
    found = []
    for base, _dirs, files in os.walk(profile_dir):
        found += [os.path.join(base, f) for f in files
                  if f.endswith(".xplane.pb")]
    return max(found, key=os.path.getmtime) if found else None


def pick_sample(finished: list, n: int, seed: int) -> list:
    """The longest finished request and ``n - 1`` others drawn from the
    seed: what the reference is run over."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (r.prompt_len + r.max_tokens, r.i))
    rest = [r for r in finished if r is not longest]
    random.Random(seed ^ 0xC0FFEE).shuffle(rest)
    return [longest] + rest[:max(0, n - 1)]


def run_child(cmd: list, env: dict, log_path: str, timeout: float) -> int:
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, env=env, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            return proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)
            return 124


def measure(args, bench: dict, work_dir: str, launcher=None,
            control: bool = False) -> dict:
    """One run -> the result object (see the module's docstring).  With
    ``control`` the comparison is given, in the served tokens' place, the
    tokens that the reference in int8 puts first at the same positions:
    the run has to come out not correct."""
    run = Run()
    cell, entry = find_cell(bench, args.workload)
    config = load_json(os.path.join(args.bench_root, entry["file"]))
    try:  # before any server: a cell nothing can judge measures nothing
        arch_file = work.arch_path(config, os.path.join(
            args.bench_root, "perfbench", "arch"))
    except ValueError as e:
        raise RunFailure(str(e)) from None
    mix = traffic.load(traffic.traffic_path(args.bench_root, cell["traffic"]))
    run.cell, run.config, run.mix = cell, config, mix
    run.seed, run.seconds = args.seed, float(args.seconds)
    run.trace_on, run.chips = bool(args.trace), int(cell["chips"])
    run.platform = args.platform
    serve = config["serve"]
    model = serve["model"]

    shutil.rmtree(work_dir, ignore_errors=True)
    os.makedirs(work_dir)
    cache_dir = compile_cache_dir(args.bench_root)
    os.makedirs(cache_dir, exist_ok=True)
    profile_dir = os.path.join(work_dir, "profile") if run.trace_on else None
    flags = list(serve["flags"])
    env_extra = {}
    if run.platform != "tpu" and run.chips > 1:  # the tests' virtual devices
        env_extra["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={run.chips}").strip()
    server = serverproc.Server(
        args.program_root, model, flags, args.seed % SERVER_SEED_MOD,
        run.platform, env_extra, os.path.join(work_dir, "server.log"),
        cache_dir, profile_dir, launcher=launcher)
    sched = traffic.schedule(mix, run.seconds, serve["max_batch_size"])
    mean_in, mean_out = traffic.realised_means(sched["requests"])
    say(f"[traffic] mix={cell['traffic']} loop={sched['loop']} "
        f"requests={len(sched['requests'])} clients={sched['clients']} "
        f"ramp_s={sched['ramp_s']} mean_prompt={mean_in:.1f} "
        f"mean_output={mean_out:.1f}")
    say("[server] " + " ".join(server.cmd[1:]))
    poll_stop = threading.Event()
    loop = None
    server.start()
    try:
        info, ready_s = server.wait_ready(READY_TIMEOUT_S)
        run.health_ready = info
        run.timing["launch_to_ready_s"] = ready_s
        say("[engine] " + json.dumps({k: v for k, v in info.items()
                                      if k != "devices"}))
        if info.get("platform") != run.platform:
            raise RunFailure(f"the server runs on {info.get('platform')!r}, "
                             f"not on {run.platform!r}")
        if run.platform == "tpu":
            run.peaks = peaks.peaks_for(info["device_kind"])
        check_expectations(info, config.get(
            "expect" if run.platform == "tpu" else "expect_cpu", {}),
            run.chips)
        run.metrics_ready = server.metrics()
        run.timing["cache_files_ready"] = cache_file_count(cache_dir)

        t = time.monotonic()
        warm_tour(server, model, mix, args.seed)
        run.timing["warm_tour_s"] = time.monotonic() - t

        ramp = sched["ramp_s"]
        t_load = time.monotonic()
        t_open = t_load + ramp
        if sched["loop"] == "closed":
            loop = load.ClosedLoop(server.base, model, args.seed,
                                   sched["requests"], sched["clients"])
        else:
            loop = load.OpenLoop(server.base, model, args.seed,
                                 sched["requests"], sched["tail"], t_open)
        loop.start()
        time.sleep(max(0.0, t_open - time.monotonic()))
        # ---- the window opens ------------------------------------------
        run.t_open = time.monotonic()
        run.timing["t_open_unix"] = time.time()
        run.timing["ramp_s"] = run.t_open - t_load
        run.timing["setup_s"] = run.t_open - T_PROCESS_START
        run.counters_open = server.metrics()
        run.cache_files_open = cache_file_count(cache_dir)
        run.timing["cache_files_open"] = run.cache_files_open
        poller = threading.Thread(target=poll_loop,
                                  args=(server, run, poll_stop), daemon=True)
        poller.start()
        profiler = None
        if run.trace_on:
            at = run.t_open + max(0.0, (run.seconds - TRACE_SECONDS) / 2.0 - 1.0)
            profiler = threading.Thread(target=take_profile, args=(
                server, run, at, min(TRACE_SECONDS, run.seconds)), daemon=True)
            profiler.start()
        time.sleep(max(0.0, run.t_open + run.seconds - time.monotonic()))
        # ---- the window closes -----------------------------------------
        run.t_close = run.t_open + run.seconds
        run.counters_close = server.metrics()
        run.cache_files_close = cache_file_count(cache_dir)
        poll_stop.set()
        if sched["loop"] == "closed":
            loop.cut()
            run.records = list(loop.records)
            # the requests that ENDED inside the window
            run.counted = [r for r in run.records
                           if (r.ended is not None and
                               run.t_open <= r.ended <= run.t_close)
                           or (r.error and not r.cut)]
        else:
            counted = [r for r in loop.records
                       if run.t_open <= r.due <= run.t_close]
            loop.wait_counted(counted, run.t_close + float(
                mix.get("tail_max_s", 40.0)))
            loop.cut()
            run.records = list(loop.records) + list(loop.tail_records)
            run.counted = counted
        if profiler is not None:
            profiler.join(TRACE_SECONDS + 150.0)
        run.health_end = server.health()
    finally:
        poll_stop.set()
        if loop is not None:
            loop.stop.set()
            load.hang_up(loop.conns)
        server.stop()

    # ---- after the server has gone: memory is read, the chip is free ----
    devices = run.health_end.get("devices") or []
    peak = max([d.get("peak_bytes_in_use") or 0 for d in devices] or [0])
    finished = [r for r in run.counted if r.ok]
    failed = [r for r in run.counted if not r.ok]
    sample = pick_sample(finished, int(config["correct"]["sample_requests"]),
                         args.seed)
    job = {
        "config": config, "arch": arch_file,
        "seed": args.seed % SERVER_SEED_MOD,
        "chips": run.chips, "platform": run.platform,
        "cache_dir": cache_dir, "control": control,
        "requests": [{"i": r.i, "prompt_ids": traffic.token_ids(r.prompt),
                      "tokens": r.tokens} for r in sample],
    }
    job_path = os.path.join(work_dir, "reference_job.json")
    out_path = os.path.join(work_dir, "reference_out.json")
    with open(job_path, "w") as f:
        json.dump(job, f)
    child_env = dict(os.environ)
    child_env["JAX_PLATFORMS"] = run.platform
    child_env.setdefault("TPU_LOG_DIR", "disabled")  # else /tmp/tpu_logs
    child_env.update(env_extra)
    children = []
    t_ref = time.monotonic()
    ref_rc = {}

    def reference_child():
        ref_rc["rc"] = run_child(
            [sys.executable, os.path.join(HERE, "reference.py"), job_path,
             out_path], child_env, os.path.join(work_dir, "reference.log"),
            timeout=900.0)

    if sample:
        children.append(threading.Thread(target=reference_child))
    trace_out = os.path.join(work_dir, "trace_reduced.json")
    if run.trace_on:
        # the benchmark's own tests reduce the small trace kept with them:
        # the CPU's profile has no device plane
        xplane = args.trace_fixture or newest_xplane(profile_dir)
        if xplane is None:
            raise RunFailure("the traced run left no .xplane.pb under "
                             + profile_dir)
        env_cpu = dict(os.environ, JAX_PLATFORMS="cpu")

        def trace_child():
            ref_rc["trace_rc"] = run_child(
                [sys.executable, os.path.join(HERE, "xtrace.py"), xplane,
                 trace_out], env_cpu, os.path.join(work_dir, "trace.log"), timeout=600.0)

        children.append(threading.Thread(target=trace_child))
    for c in children:
        c.start()
    for c in children:
        c.join()
    run.timing["reference_s"] = time.monotonic() - t_ref
    if run.trace_on:
        if ref_rc.get("trace_rc") != 0:
            raise RunFailure("the trace reduction failed: " + open(
                os.path.join(work_dir, "trace.log")).read()[-1500:])
        run.trace = load_json(trace_out)
        shutil.rmtree(profile_dir, ignore_errors=True)  # keep the disk small
        if not run.trace.get("chips") or run.trace.get("busy_s", 0) <= 0:
            raise RunFailure("no operation ran on the device in the trace")

    # ---- correct: each number beside its limit --------------------------
    limits = config["correct"]
    compared: dict[str, list] = {}
    ref_out = None
    if sample and ref_rc.get("rc") == 0:
        ref_out = load_json(out_path)
        reqs = ref_out["requests"]
        n_tokens = sum(r["n"] for r in reqs)
        # the widest gap by which a served token's logit lies below the
        # reference's best, and the mean of that gap over every compared
        # token (steadier; it is the one the int8 control fails)
        of = "ctl_" if control else ""
        compared["gap_max"] = [max(r[of + "gap_max"] for r in reqs),
                               float(limits["gap_max_limit"])]
        compared["gap_mean"] = [sum(r[of + "gap_sum"] for r in reqs) / n_tokens,
                                float(limits["gap_mean_limit"])]
        compared["served_tokens_compared"] = [n_tokens, None]
        if control:  # what the served tokens themselves read, beside it
            compared["served_gap_max"] = [max(r["gap_max"] for r in reqs), None]
            compared["served_gap_mean"] = [
                sum(r["gap_sum"] for r in reqs) / n_tokens, None]
    else:
        say("[reference] no comparison: " + (
            "no request finished in the window" if not sample else
            "reference child exited %s: %s" % (
                ref_rc.get("rc"),
                open(os.path.join(work_dir, "reference.log")).read()[-1500:])))
        compared["gap_max"] = [None, float(limits["gap_max_limit"])]
    compared["requests_wrong_or_never_answered"] = [len(failed), 0]
    for r in failed[:3]:
        say(f"[failed] request {r.i}: error={r.error!r} tokens="
            f"{len(r.tokens)}/{r.max_tokens} cut={r.cut}")
    correct = not failed and all(
        value is not None and value <= limit
        for value, limit in compared.values() if limit is not None)

    # ---- the metrics -----------------------------------------------------
    e2e = metrics_of(bench, "end_to_end", cell["name"])
    reported = {m["name"] for m in e2e}
    chosen = (metrics_of(bench, "per_layer", cell["name"], reported)
              if run.trace_on else e2e)
    values: dict[str, dict] = {}
    for m in chosen:
        v = metric_reader(args.bench_root, m["name"])(run)
        if v is None or (isinstance(v, float) and not math.isfinite(v)):
            say(f"[metric] {m['name']}: nothing to read ({v})")
            continue
        values[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": run.health_ready.get("platform"),
              "kind": run.health_ready.get("device_kind"),
              "count": run.health_ready.get("device_count"),
              "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(run.counted),
              "failed": len(failed), "metrics": values, "device": device}
    if run.trace_on:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {
            "device_ops": xtrace.top(run.trace["ops"]),
            "idle_gaps": xtrace.top(run.trace["gaps"])}
    # for the builder's eyes: the window's output rate in fifths
    fifth = run.seconds / 5.0
    run.timing["tokens_per_s_by_fifth"] = [
        stats.tokens_in(run.records, run.t_open + k * fifth,
                        run.t_open + (k + 1) * fifth) / fifth
        for k in range(5)]
    run.timing["silences"] = stats.silences(
        run.records, run.t_open, run.t_close)[:5]
    result["timing"] = run.timing
    result["engine"] = {k: run.health_ready.get(k) for k in (
        "token_budget", "n_pages", "grid", "kv_splits", "sharded_attention",
        "attention", "interpret")}
    if ref_out is not None:
        result["reference"] = ref_out
    result["compared"] = compared
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # for rehearsals and the benchmark's own tests; the driver gives none
    ap.add_argument("--platform", default="tpu",
                    help="the backend the server must report (tests: cpu)")
    ap.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("--bench-root", default=ROOT,
                    help="the checkout that holds perfbench/")
    ap.add_argument("--program-root", default=ROOT,
                    help="the checkout that holds the program")
    ap.add_argument("--trace-fixture", default="",
                    help="tests: reduce this .xplane.pb, not the recorded one")
    # the control of "correct" (PERF.md): never given by the driver
    ap.add_argument("--control", action="store_true",
                    help="compare, in the served tokens' place, the tokens "
                         "the int8 reference puts first: correct reads false")
    args = ap.parse_args(argv)
    # a run that is told to end stops its server first (the finally
    # blocks run): nothing this process started may outlive it
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        bench = load_json(args.benchmark)
        result = measure(args, bench,
                         os.path.join(args.bench_root, ".perfbench_run"),
                         control=args.control)
    except (RunFailure, serverproc.ServerFailure, KeyError, OSError,
            ValueError) as e:
        say(f"perfbench FAILED: {type(e).__name__}: {e}")
        return 1
    for name, (value, limit) in result["compared"].items():
        say(f"[compared] {name} = {value}  limit {limit}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
