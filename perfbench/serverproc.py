"""The system under test as a child process: ``python -m
fusioninfer_tpu.cli engine serve`` started, watched and stopped.

The parent never imports jax (a chip belongs to one process).  The
platform is asked for by name, so a machine without the accelerator
fails instead of falling back.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request


class ServerFailure(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(base: str, path: str, body: dict | None = None,
              timeout: float = 30.0):
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


def parse_metrics(text: str) -> dict[str, float]:
    """A /metrics page -> {family: sum of its samples} (labels ignored,
    histogram buckets skipped)."""
    out: dict[str, float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        name, _, value = line.rpartition(" ")
        family = name.split("{", 1)[0]
        if family.endswith("_bucket"):
            continue
        try:
            out[family] = out.get(family, 0.0) + float(value)
        except ValueError:
            continue
    return out


class Server:
    def __init__(self, program_root: str, model: str, serve_flags: list,
                 seed: int, platform: str, env_extra: dict, log_path: str,
                 cache_dir: str, profile_dir: str | None,
                 launcher: list | None = None):
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        launch = launcher or [sys.executable, "-m", "fusioninfer_tpu.cli"]
        self.cmd = [*launch, "engine", "serve", model, "--host", "127.0.0.1",
                    "--port", str(self.port), "--seed", str(seed),
                    *[str(f) for f in serve_flags]]
        if profile_dir:
            self.cmd.append("--enable-profiling")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = platform
        env["PYTHONPATH"] = program_root + os.pathsep + env.get("PYTHONPATH", "")
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        env.setdefault("TPU_LOG_DIR", "disabled")  # else /tmp/tpu_logs
        if profile_dir:
            env["FUSIONINFER_PROFILE_DIR"] = profile_dir
        env.update(env_extra)
        self.env, self.cwd, self.log_path = env, program_root, log_path
        self.proc: subprocess.Popen | None = None
        self.t_launch = 0.0

    def start(self) -> None:
        self.t_launch = time.monotonic()
        with open(self.log_path, "w") as log:
            self.proc = subprocess.Popen(
                self.cmd, cwd=self.cwd, env=self.env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True)

    def log_tail(self, n: int = 25) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError as e:
            return f"<no server log: {e}>"

    def wait_ready(self, timeout: float) -> tuple[dict, float]:
        deadline = time.monotonic() + timeout
        while True:
            rc = self.proc.poll()
            if rc is not None:
                raise ServerFailure(
                    f"server exited {rc} before /health answered:\n"
                    + self.log_tail())
            if time.monotonic() > deadline:
                raise ServerFailure("timeout waiting for /health:\n"
                                    + self.log_tail())
            try:
                status, body = http_json(self.base, "/health", timeout=5.0)
            except (OSError, urllib.error.URLError):
                time.sleep(0.25)
                continue
            if status == 200 and isinstance(body, dict):
                return (body.get("engine") or {},
                        time.monotonic() - self.t_launch)
            time.sleep(0.25)

    def health(self) -> dict:
        status, body = http_json(self.base, "/health", timeout=30.0)
        if status != 200 or not isinstance(body, dict):
            raise ServerFailure(f"/health answered {status}: {body}")
        return body.get("engine") or {}

    def metrics(self) -> dict[str, float]:
        status, text = http_json(self.base, "/metrics", timeout=30.0)
        if status != 200 or not isinstance(text, str):
            raise ServerFailure(f"/metrics answered {status}")
        return parse_metrics(text)

    def stop(self, grace_s: float = 20.0) -> None:
        """SIGTERM, then the whole process group is killed: nothing this
        harness started survives it."""
        if self.proc is None:
            return
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=grace_s)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        self.proc.wait(timeout=30)
