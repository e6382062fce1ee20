"""Host spans of the engine thread: one context manager, two outputs.

``SpanClock.span(name)`` adds each span's SELF time (its duration minus
its children's) and 1 to that name's totals, which ``/metrics`` renders
as ``fusioninfer:host_<name>_seconds_total`` / ``_count_total``; the
self times of one thread add up to the time it spent inside spans.
While a profile capture runs (``capturing``, set by
``EngineServer.handle_profile``), and only then, a span also opens a
``jax.profiler.TraceAnnotation``: it lands in the ``.xplane.pb``'s host
plane on the profiler's clock, the one the device planes use.

A clock belongs to ONE thread (the engine's): no lock is taken, the
``/metrics`` thread only reads the pre-seeded dicts.
"""

from __future__ import annotations

import time

import jax
from jax.profiler import TraceAnnotation

SPAN_NAMES = ("loop.idle", "step", "step.admit", "step.prefill", "step.pack",
              "step.dispatch", "step.fetch", "step.emit", "loop.publish")
JIT_EVENTS = frozenset("/jax/core/compile/" + e for e in (
    "jaxpr_trace_duration", "jaxpr_to_mlir_module_duration",
    "backend_compile_duration"))

capturing = False
# process-wide, as jax's listeners are: trace + lower + compile seconds of
# every jit-cache miss (a warm hot path never enters the listener)
jit_totals = {"seconds": 0.0, "events": 0}
_watching = False


def _on_jit_event(event: str, duration: float, **_) -> None:
    if event in JIT_EVENTS:
        jit_totals["seconds"] += duration
        jit_totals["events"] += 1


def watch_jit() -> None:
    """Register the listener behind ``jit_totals``, once per process."""
    global _watching
    if not _watching:
        _watching = True
        jax.monitoring.register_event_duration_secs_listener(_on_jit_event)


class _Span:
    __slots__ = ("clock", "name", "attrs", "t0", "children_ns", "ann")

    def __init__(self, clock: "SpanClock", name: str, attrs: dict):
        self.clock, self.name, self.attrs = clock, name, attrs
        self.children_ns, self.ann = 0, None

    def __enter__(self) -> "_Span":
        if capturing:
            self.ann = TraceAnnotation(self.name, **self.attrs)
            self.ann.__enter__()
        self.clock.stack.append(self)
        self.t0 = self.clock.now()
        return self

    def note(self, **attrs) -> None:
        """Attributes known only at the end; kept only while capturing."""
        if self.ann is not None:
            self.ann.set_metadata(**attrs)

    def __exit__(self, *exc) -> None:
        c = self.clock
        dur = c.now() - self.t0
        c.stack.pop()
        c.ns[self.name] += dur - self.children_ns
        c.count[self.name] += 1
        if c.stack:
            c.stack[-1].children_ns += dur
        if self.ann is not None:
            self.ann.__exit__(*exc)


class SpanClock:
    def __init__(self, now=time.perf_counter_ns):
        self.now = now
        self.ns = dict.fromkeys(SPAN_NAMES, 0)
        self.count = dict.fromkeys(SPAN_NAMES, 0)
        self.stack: list[_Span] = []
        self.loop_ns = self.cpu_ns = 0
        self._loop_t0 = self._cpu_t0 = None

    def span(self, name: str, **attrs) -> _Span:
        return _Span(self, name, attrs)

    def tick(self) -> None:
        """Once per engine-loop iteration, ON the engine thread: wall time
        since the loop's first tick, and the thread's CPU time over it."""
        now, cpu = self.now(), time.thread_time_ns()
        if self._loop_t0 is None:
            self._loop_t0, self._cpu_t0 = now, cpu
        self.loop_ns, self.cpu_ns = now - self._loop_t0, cpu - self._cpu_t0
