#!/usr/bin/env python3
"""chip_smoke.py — start ``engine serve`` on the chip and make it answer.

The quickest proof that the system still starts on the accelerator.  It
launches ``python -m fusioninfer_tpu.cli engine serve qwen3-1.7b`` with
the flags' defaults (burst 8 + dispatch-ahead, AOT warm-up, prefix
caching, ``--max-model-len 4096`` so the KV-split grid is live) as a
CHILD, waits for ``/health``, sends a small fixed set of seeded requests
that between them reach every forward the engine has, reads
``/metrics``, and requires the child to drain and exit 0 on SIGTERM.

This parent never imports jax: a chip belongs to one process, so only
the server child touches it, and children run one after another.

    python chip_smoke.py                 # one TPU chip (via the chip tool)
    python chip_smoke.py --tp 4          # one host with four chips
    python chip_smoke.py --cpu-dry-run   # same control flow, qwen3-tiny,
                                         # interpret kernels, platform=cpu

Exit code 0 and a last stdout line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``
only when every phase passed; any other outcome exits non-zero with the
reason in the last lines and prints no result line.  Weights are random
from a seed and the tokenizer is the byte tokenizer: no network.

After the default server, while the time limit allows, the same
requests go to a ``--decode-burst 1`` server (the fused mixed-batch
step) and a ``--kv-cache-dtype int8`` server (the quantized operand
layout of every kernel).  Server logs land in ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

# the contract allows 1200 s, compilation included: no server is started
# that is not predicted to end by RUN_DEADLINE_S, and every wait —
# readiness, a request, the drain — is clipped to HARD_STOP_S
RUN_DEADLINE_S = 1100.0
HARD_STOP_S = 1170.0
DRAIN_TIMEOUT_S = 150.0
# tp: per-device bytes_in_use may differ by this share of the largest
# (replicated norms, sampling state and allocator rounding are uneven)
TP_BYTES_TOLERANCE = 0.10

# the servers after the default one, started only while time allows
VARIANTS = (("burst1", ["--decode-burst", "1"]),
            ("int8kv", ["--kv-cache-dtype", "int8"]))


@dataclasses.dataclass(frozen=True)
class Mode:
    """Sizes of one run.  Everything else — the control flow, the
    checks, the request kinds — is shared between the chip and the dry
    mode, so what tier-1 exercises on the CPU is what the chip runs."""

    name: str
    platform: str  # the backend the child must report, asked for by name
    model: str
    serve_flags: tuple  # beyond the defaults (the chip mode adds none)
    env: dict
    ready_timeout_s: float
    request_timeout_s: float
    variants: tuple
    anchor_tokens: int  # the long-running streamed decode
    short_tokens: int
    long_prompt_min: int  # byte-tokens; raised above the token budget
    shared_prefix: int  # byte-tokens, >= 2 pages


CHIP = Mode(
    name="chip", platform="tpu", model="qwen3-1.7b", serve_flags=(),
    env={}, ready_timeout_s=900.0, request_timeout_s=300.0,
    variants=VARIANTS, anchor_tokens=256, short_tokens=16,
    long_prompt_min=2048, shared_prefix=256)

# cut to what the CPU and the Pallas interpreter finish in well under a
# minute: a short context (so --kv-splits must name the grid the chip
# mode gets from its 4096-token default) and a small batch (fewer AOT
# entries); FUSIONINFER_ATTN=flash keeps the kernels — interpreted — on
# the path instead of the jnp reference the CPU would resolve to
DRY = Mode(
    name="cpu-dry-run", platform="cpu", model="qwen3-tiny",
    serve_flags=("--max-model-len", "512", "--page-size", "32",
                 "--max-batch-size", "4", "--kv-splits", "8"),
    env={"FUSIONINFER_ATTN": "flash"}, ready_timeout_s=300.0,
    request_timeout_s=120.0, variants=(), anchor_tokens=48,
    short_tokens=8, long_prompt_min=200, shared_prefix=64)


class SmokeFailure(Exception):
    pass


def say(tag: str, **fields) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def left(stop: float, cap: float) -> float:
    """Seconds a wait may take: ``cap``, clipped to the hard stop."""
    return max(1.0, min(cap, stop - time.monotonic()))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(base: str, path: str, body: dict | None = None,
         timeout: float = 30.0):
    """(status, parsed JSON or text).  Connection errors raise."""
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        base + path, data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            raw, status = resp.read(), resp.status
    except urllib.error.HTTPError as e:
        raw, status = e.read(), e.code
    text = raw.decode("utf-8", "replace")
    try:
        return status, json.loads(text)
    except ValueError:
        return status, text


def stream_completion(base: str, body: dict, timeout: float,
                      first_chunk: threading.Event):
    """One SSE completion → (status, usage of the final chunk, saw
    [DONE]).  ``first_chunk`` is set when the first token arrives."""
    req = urllib.request.Request(
        base + "/v1/completions", json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    usage, done = None, False
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            for raw in resp:
                line = raw.decode("utf-8", "replace").strip()
                if not line.startswith("data:"):
                    continue
                first_chunk.set()
                payload = line[5:].strip()
                if payload == "[DONE]":
                    done = True
                    break
                usage = json.loads(payload).get("usage") or usage
            return resp.status, usage, done
    except urllib.error.HTTPError as e:
        return e.code, None, False
    finally:
        first_chunk.set()  # never leave the waiters hanging


def text_of(n_tokens: int, salt: str) -> str:
    """A deterministic ASCII prompt of exactly ``n_tokens`` byte-tokens
    (the byte tokenizer adds one BOS)."""
    words = (f"{salt} the quick brown fox jumps over the lazy dog "
             "while the kv pages stream from hbm ")
    return (words * (n_tokens // len(words) + 1))[: max(1, n_tokens - 1)]


def metric(text: str, family: str) -> float:
    """Sum of a family's samples on a /metrics page (labels ignored)."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith(family) and line[len(family):][:1] in ("{", " "):
            total += float(line.rsplit(" ", 1)[1])
            seen = True
    if not seen:
        raise SmokeFailure(f"/metrics has no {family}")
    return total


class Server:
    """One ``engine serve`` child: launch, wait ready, stop.  The child
    runs in its own session so the whole group can be killed."""

    def __init__(self, mode: Mode, tag: str, extra_flags: list, tp: int):
        self.mode, self.tag = mode, tag
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.cmd = [sys.executable, "-m", "fusioninfer_tpu.cli", "engine",
                    "serve", mode.model, "--host", "127.0.0.1",
                    "--port", str(self.port), *mode.serve_flags,
                    *extra_flags]
        if tp > 1:
            self.cmd += ["--tensor-parallel-size", str(tp)]
        env = dict(os.environ)
        # the backend is asked for BY NAME either way: the chip mode
        # must fail where there is no TPU, never fall back
        env["JAX_PLATFORMS"] = mode.platform
        env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
        env.update(mode.env)
        if mode.platform == "cpu" and tp > 1:
            env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                                f" --xla_force_host_platform_device_count={tp}"
                                ).strip()
        self.env = env
        os.makedirs(OUT_DIR, exist_ok=True)
        self.log_path = os.path.join(OUT_DIR, f"{mode.name}-{tag}.log")
        self.proc: subprocess.Popen | None = None
        self.t_launch = 0.0

    def log_tail(self, n: int = 30) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError as e:
            return f"<no server log: {e}>"

    def start(self) -> None:
        self.t_launch = time.monotonic()
        with open(self.log_path, "w") as log:
            self.proc = subprocess.Popen(
                self.cmd, cwd=HERE, env=self.env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True)

    def wait_ready(self, deadline: float) -> tuple[dict, float]:
        """Poll /health until 200 → (engine info, launch-to-ready s)."""
        while True:
            rc = self.proc.poll()
            if rc is not None:
                raise SmokeFailure(
                    f"server child exited {rc} before /health answered; "
                    f"its last lines:\n{self.log_tail()}")
            if time.monotonic() > deadline:
                raise SmokeFailure(
                    "timeout waiting for /health; server's last lines:\n"
                    + self.log_tail())
            try:
                status, body = http(self.base, "/health", timeout=5.0)
            except (OSError, urllib.error.URLError):
                time.sleep(0.5)
                continue
            if status == 200 and isinstance(body, dict):
                return (body.get("engine") or {},
                        time.monotonic() - self.t_launch)
            time.sleep(0.5)

    def terminate(self, stop: float) -> int:
        """SIGTERM → the child must drain and exit by itself."""
        self.proc.send_signal(signal.SIGTERM)
        timeout = left(stop, DRAIN_TIMEOUT_S)
        try:
            return self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"server did not exit within {timeout:.0f}s of SIGTERM; "
                f"its last lines:\n{self.log_tail()}") from None

    def kill(self) -> None:
        """Unconditional cleanup: nothing this script started survives it."""
        if self.proc is None or self.proc.poll() is not None:
            return
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait(timeout=30)


def check_engine_info(mode: Mode, tag: str, info: dict, tp: int) -> None:
    """What the server says it resolved must be what this mode expects:
    the named platform, the Pallas kernels (compiled on the chip,
    interpreted only in the dry mode), the coalesced grid with the
    KV-split grid engaged, and under --tp the kernel mesh."""
    want = {
        "platform": mode.platform,
        "attention": "flash",
        "interpret": mode.platform != "tpu",
        "grid": "coalesced",
        "sharded_attention": "kernel-mesh" if tp > 1 else None,
    }
    for key, expected in want.items():
        if info.get(key) != expected:
            raise SmokeFailure(
                f"server reports {key}={info.get(key)!r}, expected "
                f"{expected!r} (engine info: {json.dumps(info)})")
    if not info.get("kv_splits"):
        raise SmokeFailure("the KV-split grid is not engaged: "
                           f"kv_splits={info.get('kv_splits')!r}")
    aot = info.get("aot") or {}
    if not aot.get("entries") or aot.get("errors"):
        raise SmokeFailure(f"AOT warm-up did not build cleanly: {aot}")
    if len(info.get("devices") or []) != max(1, tp):
        raise SmokeFailure(f"expected {max(1, tp)} engine devices, got "
                           f"{info.get('devices')}")
    if mode.platform == "tpu":
        for d in info["devices"]:
            if not d.get("bytes_limit"):
                raise SmokeFailure(f"device {d} reports no bytes_limit")
    say(tag, platform=info["platform"],
        device_kind=json.dumps(info["device_kind"]),
        device_count=info["device_count"], attention=info["attention"],
        interpret=info["interpret"], grid=info["grid"],
        kv_splits=info["kv_splits"],
        sharded_attention=info["sharded_attention"])
    say(tag, pool_pages=info["n_pages"], page_size=info["page_size"],
        kv_dtype=info["kv_dtype"], token_budget=info["token_budget"],
        decode_burst=info["decode_burst"],
        compile_cache_dir=info["compile_cache_dir"])
    say(tag, aot_entries=aot["entries"], aot_hits=aot["hits"],
        aot_misses=aot["misses"], aot_errors=len(aot["errors"]),
        aot_build_seconds=aot["build_seconds"])


def run_requests(mode: Mode, tag: str, base: str, info: dict,
                 stop: float) -> int:
    """The fixed request set → tokens asked for.  Every request pins
    ``min_tokens == max_tokens`` (a random-weight EOS cannot end it
    early) and is checked on ``usage.completion_tokens``, never on text
    (random weights decode to empty strings under the byte tokenizer)."""
    model = mode.model
    max_len = info["page_size"] * info["max_pages_per_seq"]
    budget = info["token_budget"] or 0
    # long enough to exceed the step's token budget (chunk rows), as
    # far as the context allows — with decode rows charged first even a
    # prompt just under the budget is over what is left of it
    long_len = min(max_len - mode.short_tokens - 8,
                   max(mode.long_prompt_min, budget + 256))
    results: list[tuple[str, int, object, int]] = []  # name,status,usage,want
    lock = threading.Lock()

    def completion(name: str, prompt: str, n: int, **extra) -> None:
        body = {"model": model, "prompt": prompt, "max_tokens": n,
                "min_tokens": n, **extra}
        try:
            status, out = http(base, "/v1/completions", body,
                               timeout=left(stop, mode.request_timeout_s))
        except (OSError, urllib.error.URLError) as e:
            status, out = -1, f"{type(e).__name__}: {e}"
        usage = out.get("usage") if isinstance(out, dict) else out
        if name == "logprobs" and isinstance(out, dict) and status == 200:
            lps = ((out["choices"][0].get("logprobs") or {})
                   .get("token_logprobs") or [])
            if len(lps) != n or not all(
                    isinstance(v, (int, float)) and v <= 0.0 for v in lps):
                status, usage = -2, f"bad token_logprobs: {lps}"
        with lock:
            results.append((name, status, usage, n))

    def streamed(name: str, prompt: str, n: int,
                 first_chunk: threading.Event) -> None:
        body = {"model": model, "prompt": prompt, "max_tokens": n,
                "min_tokens": n, "stream": True, "temperature": 0.7,
                "seed": 11, "stream_options": {"include_usage": True}}
        try:
            status, usage, done = stream_completion(
                base, body, left(stop, mode.request_timeout_s), first_chunk)
        except (OSError, urllib.error.URLError) as e:
            status, usage, done = -1, f"{type(e).__name__}: {e}", False
        if status == 200 and not done:
            status, usage = -3, "stream ended without [DONE]"
        with lock:
            results.append((name, status, usage, n))

    # 1. the streamed anchor: a long decode that keeps rows running ...
    first = threading.Event()
    threads = [threading.Thread(target=streamed, args=(
        "stream", text_of(40, "anchor"), mode.anchor_tokens, first))]
    threads[0].start()
    if not first.wait(left(stop, mode.request_timeout_s)):
        raise SmokeFailure("the streamed request produced no first chunk")
    # 2. ... several short prompts AT ONCE (batched fresh prefill through
    # the flash kernel, then decode bursts) ...
    for i, n_prompt in enumerate((24, 37, 61, 90)):
        threads.append(threading.Thread(target=completion, args=(
            f"short{i}", text_of(n_prompt, f"short{i}"), mode.short_tokens),
            kwargs={"temperature": 0.8, "seed": i}))
    # 3. ... and the long prompt beside them: budgeted chunk rows in the
    # ragged kernel, mixed with the running decode rows
    threads.append(threading.Thread(target=completion, args=(
        "long", text_of(long_len, "long"), mode.short_tokens),
        kwargs={"temperature": 0.8, "seed": 7}))
    for t in threads[1:]:
        t.start()
    for t in threads:
        t.join(left(stop, mode.request_timeout_s + 30))
        if t.is_alive():
            raise SmokeFailure("a request thread did not finish")
    # 4. two requests sharing a prefix, the second AFTER the first
    # finished: the cache-hit suffix path
    prefix = text_of(mode.shared_prefix + 1, "shared")
    completion("prefix-a", prefix + " first tail", mode.short_tokens,
               temperature=0.0)
    completion("prefix-b", prefix + " another tail, longer",
               mode.short_tokens, temperature=0.0)
    # 5. greedy with logprobs: the unfused sampling path
    completion("logprobs", text_of(30, "logprobs"), mode.short_tokens,
               temperature=0.0, logprobs=2)

    asked = 0
    for name, status, usage, n in sorted(results):
        got = usage.get("completion_tokens") if isinstance(usage, dict) else None
        say(tag, request=name, status=status, completion_tokens=got,
            asked=n, prompt_tokens=(usage.get("prompt_tokens")
                                    if isinstance(usage, dict) else None))
        if status != 200 or got != n:
            raise SmokeFailure(
                f"request {name}: status {status}, completion_tokens "
                f"{got} of {n} asked ({usage})")
        asked += n
    sent = len(threads) + 3  # the prefix pair and the logprobs request
    if len(results) != sent:
        raise SmokeFailure(f"{len(results)} of {sent} requests reported")
    say(tag, long_prompt_tokens=long_len, token_budget=budget)
    return asked


def check_metrics(mode: Mode, tag: str, base: str, asked: int) -> None:
    """HTTP 200s alone prove nothing: the engine turns a forward that
    fails to compile into a failed request and keeps serving."""
    status, text = http(base, "/metrics")
    if status != 200 or not isinstance(text, str):
        raise SmokeFailure(f"/metrics answered {status}")
    failures = metric(text, "vllm:request_failure_total")
    generated = metric(text, "vllm:generation_tokens_total")
    hit_tokens = metric(text, "fusioninfer:prefix_hit_tokens_total")
    chunks = metric(text, "fusioninfer:sched_chunks_total")
    say(tag, request_failure_total=int(failures),
        generation_tokens_total=int(generated), asked=asked,
        prefix_hit_tokens_total=int(hit_tokens),
        sched_chunks_total=int(chunks),
        aot_cache_hits=int(metric(text, "fusioninfer:aot_cache_hits")),
        aot_cache_misses=int(metric(text, "fusioninfer:aot_cache_misses")))
    if failures:
        raise SmokeFailure(f"the engine counted {int(failures)} failed "
                           "requests (vllm:request_failure_total)")
    if generated != asked:
        raise SmokeFailure(f"vllm:generation_tokens_total is "
                           f"{int(generated)}, {asked} tokens were asked for")
    if hit_tokens < mode.shared_prefix // 2:
        raise SmokeFailure("the shared-prefix pair never hit the prefix "
                           f"cache (prefix_hit_tokens_total={int(hit_tokens)})")
    if chunks < 1:
        raise SmokeFailure("the long prompt never rode budgeted chunk "
                           "rows (sched_chunks_total=0)")


def check_memory(mode: Mode, tag: str, base: str, tp: int) -> None:
    """bytes_in_use per engine device, after the requests; under --tp
    nothing may sit quietly on device 0."""
    _, body = http(base, "/health")
    devices = (body.get("engine") or {}).get("devices") or []
    for d in devices:
        say(tag, device=d["id"], bytes_in_use=d["bytes_in_use"],
            peak_bytes_in_use=d["peak_bytes_in_use"],
            bytes_limit=d["bytes_limit"])
    used = [d["bytes_in_use"] for d in devices]
    if mode.platform != "tpu" and not any(used):
        return  # the CPU backend reports no memory counters
    if not all(used):
        raise SmokeFailure(f"a device reports no bytes_in_use: {devices}")
    if tp > 1 and (max(used) - min(used)) > TP_BYTES_TOLERANCE * max(used):
        raise SmokeFailure(
            f"bytes_in_use differs by more than {TP_BYTES_TOLERANCE:.0%} "
            f"across the mesh: {used}")


def cache_files(path: str | None) -> int:
    try:
        return len(os.listdir(path)) if path else 0
    except OSError:
        return 0


def run_server(mode: Mode, tag: str, extra_flags: list, tp: int,
               stop: float) -> dict:
    """One server through every phase → its engine info.  Raises
    SmokeFailure on the first failed phase; no wait outlasts ``stop``
    and the child never outlives this call."""
    server = Server(mode, tag, extra_flags, tp)
    say(tag, launching=" ".join(server.cmd[1:]), log=server.log_path)
    server.start()
    try:
        info, ready_s = server.wait_ready(
            time.monotonic() + left(stop, mode.ready_timeout_s))
        say(tag, launch_to_ready_s=round(ready_s, 1))
        check_engine_info(mode, tag, info, tp)
        files_before = cache_files(info["compile_cache_dir"])
        asked = run_requests(mode, tag, server.base, info, stop)
        check_metrics(mode, tag, server.base, asked)
        check_memory(mode, tag, server.base, tp)
        # a warm run (AOT hits == entries) that still adds files
        # compiled something its warm-up does not cover
        files_after = cache_files(info["compile_cache_dir"])
        say(tag, compile_cache_files=files_after,
            new_since_ready=files_after - files_before)
        rc = server.terminate(stop)
        say(tag, sigterm_exit_code=rc)
        if rc != 0:
            raise SmokeFailure(
                f"server exited {rc} after SIGTERM; its last lines:\n"
                + server.log_tail())
        return info
    finally:
        server.kill()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=1,
                    help="serve with --tensor-parallel-size N over the "
                         "first N devices of this host")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="the identical control flow on the CPU: "
                         "qwen3-tiny, interpret kernels, platform=cpu")
    args = ap.parse_args(argv)
    mode = DRY if args.cpu_dry_run else CHIP
    t0 = time.monotonic()
    stop = t0 + HARD_STOP_S
    try:
        info = run_server(mode, "default", [], args.tp, stop)
        took = time.monotonic() - t0
        for tag, flags in mode.variants:
            # a variant compiles its own programs from cold: start it
            # only if twice the default server's time still fits
            if time.monotonic() + 2.0 * took > t0 + RUN_DEADLINE_S:
                say(tag, skipped="not enough of the time limit left",
                    default_server_s=round(took, 1))
                continue
            run_server(mode, tag, flags, args.tp, stop)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED after {time.monotonic() - t0:.0f}s: {e}",
              flush=True)
        return 1
    say("done", seconds=round(time.monotonic() - t0, 1), mode=mode.name)
    print(json.dumps({"ok": True, "device": {
        "platform": info["platform"], "kind": info["device_kind"],
        "count": info["device_count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
