"""Host spans: one context manager, two outputs.

``SpanClock.span(name)`` adds each span's SELF time (its duration minus
its children's) to that name's totals, on two clocks: the wall
(``now``) and the thread's CPU (``cpu``).  ``/metrics`` renders them as
``fusioninfer:host_<name>_seconds_total`` and
``fusioninfer:engine_cpu_<name>_seconds_total``; the self times of one
thread add up to the time it spent inside spans, and wall less CPU is
the time the thread waited (for the device, a lock, the interpreter
lock).  While a profile capture runs (``capturing``, set by
``EngineServer.handle_profile``), and only then, a span also opens a
``jax.profiler.TraceAnnotation``: it lands in the ``.xplane.pb``'s host
plane on the profiler's clock, the one the device planes use.

A ``SpanClock`` belongs to ONE thread (the engine's): no lock is taken,
the ``/metrics`` thread only reads the pre-seeded dicts.  The stream
handler threads' side is a ``StreamClock``, lighter: a socket write is a
few wall-clock reads, and ``annotation``, which decides for every span,
opens the same ``TraceAnnotation`` while capturing.  A thread's CPU clock is a system call (5.8 µs a read on
a TPU v5e host against 0.1 µs for the wall), so handlers read theirs at
most once a second.

Process-wide, as jax's listeners and the collector are: ``jit_totals``
and ``gc_totals``.
"""

from __future__ import annotations

import contextlib
import gc
import threading
import time
from typing import NamedTuple

import jax
from jax.profiler import TraceAnnotation

SPAN_NAMES = ("loop.idle", "step", "step.admit", "step.prefill", "step.pack",
              "step.dispatch", "step.fetch", "step.emit", "loop.publish")
# a streaming handler thread reads its CPU clock at most this often
CPU_READ_NS = 1_000_000_000
# an engine-loop iteration this long is a stall: the least silence the
# benchmark's client side reports (perfbench/stats.silences)
STALL_NS = 250_000_000
JIT_EVENTS = frozenset("/jax/core/compile/" + e for e in (
    "jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
    "backend_compile_duration"))

capturing = False
# trace + lower + compile seconds of every jit-cache miss (a warm hot
# path never enters the listener)
jit_totals = {"seconds": 0.0}
# wall seconds inside the cyclic collector, whichever thread triggered it
# (it stops every thread: it holds the interpreter lock throughout)
gc_totals = {"seconds": 0.0}
_watching_jit = _watching_gc = False
_gc_t0 = 0


def _on_jit_event(event: str, duration: float, **_) -> None:
    if event in JIT_EVENTS:
        jit_totals["seconds"] += duration


def watch_jit() -> None:
    """Register the listener behind ``jit_totals``, once per process."""
    global _watching_jit
    if not _watching_jit:
        _watching_jit = True
        jax.monitoring.register_event_duration_secs_listener(_on_jit_event)


def _on_gc(phase: str, _info: dict) -> None:
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter_ns()
    else:
        gc_totals["seconds"] += (time.perf_counter_ns() - _gc_t0) / 1e9


def watch_gc() -> None:
    """Register the collector callbacks behind ``gc_totals``, once per
    process (collections never overlap: one start/stop pair each)."""
    global _watching_gc
    if not _watching_gc:
        _watching_gc = True
        gc.callbacks.append(_on_gc)


class _Span:
    __slots__ = ("clock", "name", "attrs", "t0", "c0", "children_ns",
                 "children_cpu", "ann")

    def __init__(self, clock: "SpanClock", name: str, attrs: dict):
        self.clock, self.name, self.attrs = clock, name, attrs
        self.children_ns = self.children_cpu = 0

    def __enter__(self) -> "_Span":
        self.ann = annotation(self.name, **self.attrs)
        self.ann.__enter__()
        c = self.clock
        c.stack.append(self)
        # wall first, CPU last: the CPU interval sits inside the wall's
        self.t0 = c.now()
        self.c0 = c.cpu()
        return self

    def note(self, **attrs) -> None:
        """Attributes known only at the end; kept only while capturing."""
        if self.ann is not _NO_ANNOTATION:
            self.ann.set_metadata(**attrs)

    def __exit__(self, *exc) -> None:
        c = self.clock
        cpu = c.cpu() - self.c0
        dur = c.now() - self.t0
        c.stack.pop()
        if c.stack:
            c.stack[-1].children_ns += dur
            c.stack[-1].children_cpu += cpu
        self_ns = dur - self.children_ns
        c.ns[self.name] += self_ns
        c.cpu_ns[self.name] += cpu - self.children_cpu
        if self_ns > c.longest_ns:  # the stall line's longest span
            c.longest_ns, c.longest = self_ns, self
        self.ann.__exit__(*exc)


class Stall(NamedTuple):
    """One engine-loop iteration of ``STALL_NS`` or more."""
    wall_ns: int
    cpu_ns: int        # the engine thread's CPU time in it
    span: str | None   # the longest span (by self time) closed in it
    span_ns: int
    program: str | None  # that span's ``program`` attribute


class SpanClock:
    def __init__(self, now=time.perf_counter_ns, cpu=time.thread_time_ns):
        self.now, self.cpu = now, cpu
        self.ns = dict.fromkeys(SPAN_NAMES, 0)      # self wall time by span
        self.cpu_ns = dict.fromkeys(SPAN_NAMES, 0)  # self CPU time by span
        self.stack: list[_Span] = []
        self.loop_ns = self.loop_cpu_ns = 0
        self.stalls = self.stall_ns = 0
        self.longest: _Span | None = None  # since the last tick
        self.longest_ns = 0
        self._loop_t0 = self._cpu_t0 = self._tick_t = self._tick_cpu = None

    def span(self, name: str, **attrs) -> _Span:
        return _Span(self, name, attrs)

    def tick(self) -> Stall | None:
        """Once per engine-loop iteration, ON the engine thread: wall time
        since the loop's first tick and the thread's CPU time over it; the
        iteration that ends here, if it lasted ``STALL_NS`` or more, is
        counted and returned as a stall."""
        now, cpu = self.now(), self.cpu()
        stall = None
        if self._loop_t0 is None:
            self._loop_t0, self._cpu_t0 = now, cpu
        elif now - self._tick_t >= STALL_NS:
            longest = self.longest
            stall = Stall(now - self._tick_t, cpu - self._tick_cpu,
                          longest and longest.name, self.longest_ns,
                          longest and longest.attrs.get("program"))
            self.stalls += 1
            self.stall_ns += stall.wall_ns
        self._tick_t, self._tick_cpu = now, cpu
        self.longest, self.longest_ns = None, 0
        self.loop_ns, self.loop_cpu_ns = now - self._loop_t0, cpu - self._cpu_t0
        return stall


def annotation(name: str, **attrs):
    """A ``TraceAnnotation`` while a capture runs, else nothing: the one
    place that decides, for a span and for the stream handlers' render
    and write alike."""
    return TraceAnnotation(name, **attrs) if capturing else _NO_ANNOTATION


_NO_ANNOTATION = contextlib.nullcontext()


class StreamClock:
    """The stream handlers' side, one set of totals for every handler
    thread of a server (one per connection), added under one small lock
    once a socket write: the wall time rendering chunks (a token's text,
    stop check, chunk and its JSON) and writing them, the chunks that
    carry a token, the sum of their delays since their tokens were
    published, and the writes that carried them.  No ``_Span`` here: a
    handler does a write's accounting with a few wall-clock reads, and
    reads its thread's CPU clock (a system call) as it starts streaming,
    at most once a ``CPU_READ_NS`` and as it stops (``streaming``)."""

    def __init__(self, now=time.perf_counter_ns, cpu=time.thread_time_ns):
        self.now, self.thread_cpu = now, cpu
        self.render_ns = self.write_ns = self.cpu_total_ns = 0
        self.chunks = self.delay_ns = self.writes = 0
        self._lock = threading.Lock()

    def written(self, render_ns: int, write_ns: int,
                delay_ns: int | None = None, chunks: int = 1) -> None:
        """Chunks were rendered and written in one write; ``delay_ns``:
        the sum, over the ``chunks`` of them that carry a token, of each
        one's time since its token was published (None where none
        does)."""
        with self._lock:
            self.render_ns += render_ns
            self.write_ns += write_ns
            if delay_ns is not None:
                self.chunks += chunks
                self.delay_ns += delay_ns
                self.writes += 1

    def streaming(self) -> "_ThreadCpu":
        """Around one thread's streaming loop: its CPU time from entry to
        exit, added at most once a ``CPU_READ_NS`` (``tick()``, once a
        chunk) and at the exit."""
        return _ThreadCpu(self)

    def cpu_seconds(self) -> float:
        return self.cpu_total_ns / 1e9  # one int read: no lock


class _ThreadCpu:
    __slots__ = ("clock", "c0", "t0")

    def __init__(self, clock: StreamClock):
        self.clock = clock

    def __enter__(self) -> "_ThreadCpu":
        self.t0, self.c0 = self.clock.now(), self.clock.thread_cpu()
        return self

    def tick(self) -> None:
        now = self.clock.now()
        if now - self.t0 >= CPU_READ_NS:
            self._add(now)

    def _add(self, now: int) -> None:
        c = self.clock
        cpu = c.thread_cpu()
        with c._lock:
            c.cpu_total_ns += cpu - self.c0
        self.t0, self.c0 = now, cpu

    def __exit__(self, *exc) -> None:
        self._add(self.clock.now())
