"""Clients: one streamed greedy completion per request, every chunk
stamped with the instant the client received it.

The open loop times each request from the instant it was DUE, not from
the instant it was sent, so a stalled server's delay is charged to the
later requests it held up; how late the generator itself ran is kept
beside it (``sent - due``).  The closed loop keeps ``clients`` requests
outstanding until it is cut.
"""

from __future__ import annotations

import http.client
import json
import socket
import threading
import time
from urllib.parse import urlsplit

import traffic

_TOKEN_KEY = b'"token_id": '


class Record:
    """What one request did, on the client's own clock (monotonic)."""

    __slots__ = ("i", "prompt_len", "max_tokens", "due", "sent", "stamps",
                 "tokens", "ended", "error", "cut", "prompt")

    def __init__(self, req: dict, due_abs: float | None):
        self.i = req["i"]
        self.prompt_len = req["prompt_len"]
        self.max_tokens = req["max_tokens"]
        self.due = due_abs
        self.sent = None
        self.stamps: list[float] = []
        self.tokens: list[int] = []
        self.ended = None  # instant the stream closed with [DONE]
        self.error = None
        self.cut = False  # the harness hung up (closed loop, window closed)
        self.prompt = None

    @property
    def ok(self) -> bool:
        return (self.ended is not None and self.error is None
                and len(self.tokens) == self.max_tokens)


def request_body(model: str, prompt: str, max_tokens: int) -> bytes:
    return json.dumps({
        "model": model, "prompt": prompt, "stream": True,
        "temperature": 0, "max_tokens": max_tokens,
        "min_tokens": max_tokens}).encode()


def stream_one(base: str, model: str, rec: Record, stop: threading.Event,
               conns: set, timeout: float = 120.0,
               clock=time.monotonic) -> None:
    """Send ``rec``'s request and fill in its stamps and token ids.  A
    set ``stop`` hangs up at the next chunk and marks the record cut;
    ``conns`` holds the open connections so that :func:`hang_up` can
    end a client that is waiting for its first token."""
    u = urlsplit(base)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout)
    conns.add(conn)
    try:
        body = request_body(model, rec.prompt, rec.max_tokens)
        rec.sent = clock()
        conn.request("POST", "/v1/completions", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec.error = f"http_{resp.status}: {resp.read(300)!r}"
            return
        while True:
            line = resp.readline()
            if stop.is_set():
                rec.cut = True
                return
            if not line:
                rec.error = rec.error or "stream ended without [DONE]"
                return
            if not line.startswith(b"data:"):
                continue
            now = clock()
            if line[5:].strip() == b"[DONE]":
                rec.ended = now
                return
            at = line.find(_TOKEN_KEY)
            if at < 0:
                if b'"error' in line:
                    rec.error = line[:300].decode("utf-8", "replace")
                continue
            end = at + len(_TOKEN_KEY)
            stop_at = end
            while line[stop_at:stop_at + 1].isdigit():
                stop_at += 1
            rec.tokens.append(int(line[end:stop_at]))
            rec.stamps.append(now)
    except (OSError, http.client.HTTPException) as e:
        if stop.is_set():
            rec.cut = True
        else:
            rec.error = f"{type(e).__name__}: {e}"
    finally:
        conns.discard(conn)
        conn.close()


def hang_up(conns: set) -> None:
    for conn in list(conns):
        sock = conn.sock
        if sock is not None:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass


class ClosedLoop:
    """``clients`` threads, each drawing the next request from the front
    of the schedule the moment its last one ends."""

    def __init__(self, base, model, seed, reqs, clients):
        self.base, self.model, self.seed = base, model, seed
        self.reqs, self.clients = reqs, clients
        self.records: list[Record] = []
        self.stop = threading.Event()
        self.conns: set = set()
        self._next = 0
        self._lock = threading.Lock()
        self._threads: list[threading.Thread] = []

    def _client(self) -> None:
        while not self.stop.is_set():
            with self._lock:
                if self._next >= len(self.reqs):
                    return
                req = self.reqs[self._next]
                self._next += 1
                rec = Record(req, None)
                self.records.append(rec)
            rec.prompt = traffic.prompt_for(self.seed, req["i"],
                                            req["prompt_len"])
            stream_one(self.base, self.model, rec, self.stop, self.conns)

    def start(self) -> None:
        for _ in range(self.clients):
            t = threading.Thread(target=self._client, daemon=True)
            t.start()
            self._threads.append(t)

    def cut(self, timeout: float = 20.0) -> None:
        """Window closed: hang up every client; nothing drains."""
        self.stop.set()
        hang_up(self.conns)
        deadline = time.monotonic() + timeout
        for t in self._threads:
            t.join(max(0.1, deadline - time.monotonic()))


class OpenLoop:
    """Requests sent at instants fixed beforehand, one thread each."""

    def __init__(self, base, model, seed, reqs, tail, t_open):
        self.base, self.model, self.seed = base, model, seed
        self.reqs, self.tail, self.t_open = reqs, tail, t_open
        self.records = [Record(r, t_open + r["due"]) for r in reqs]
        self.tail_records: list[Record] = []
        self.stop = threading.Event()       # hang up everything
        self.stop_tail = threading.Event()  # offer no more uncounted load
        self.conns: set = set()
        self._threads: list[threading.Thread] = []
        self._pump = threading.Thread(target=self._run, daemon=True)

    def _fire(self, rec: Record) -> None:
        t = threading.Thread(target=stream_one, args=(
            self.base, self.model, rec, self.stop, self.conns), daemon=True)
        t.start()
        self._threads.append(t)

    def _run(self) -> None:
        for rec, req in zip(self.records, self.reqs):
            rec.prompt = traffic.prompt_for(self.seed, req["i"],
                                            req["prompt_len"])
            delay = rec.due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            if self.stop.is_set():
                return
            self._fire(rec)
        for req in self.tail:
            rec = Record(req, self.t_open + req["due"])
            rec.prompt = traffic.prompt_for(self.seed, req["i"],
                                            req["prompt_len"])
            delay = rec.due - time.monotonic()
            if delay > 0 and self.stop_tail.wait(delay):
                return
            if self.stop.is_set() or self.stop_tail.is_set():
                return
            self.tail_records.append(rec)
            self._fire(rec)

    def start(self) -> None:
        self._pump.start()

    def wait_counted(self, counted: list[Record], deadline: float) -> None:
        """Follow every counted request to its end (or the deadline: an
        answer that never comes is for ``correct``), under load."""
        while time.monotonic() < deadline:
            if all(r.sent is not None and (r.ended is not None or r.error)
                   for r in counted):
                break
            time.sleep(0.05)
        self.stop_tail.set()

    def cut(self, timeout: float = 20.0) -> None:
        self.stop_tail.set()
        self.stop.set()
        hang_up(self.conns)
        self._pump.join(timeout)
        deadline = time.monotonic() + timeout
        for t in list(self._threads):
            t.join(max(0.1, deadline - time.monotonic()))
