"""Host spans and phase clocks of the engine thread (utils/spans.py):
self-time arithmetic on the wall and CPU clocks, the families on
/metrics, the stream handlers' spans and delays, stalled loop
iterations, no annotation outside a capture, a capture that holds the
spans and no Python call trace, and the jit and collector listeners."""

import gc
import glob
import json
import logging
import re
import time
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from fusioninfer_tpu.engine.engine import NativeEngine
from fusioninfer_tpu.engine.kv_cache import CacheConfig
from fusioninfer_tpu.engine.server import EngineServer
from fusioninfer_tpu.models.config import get_preset
from fusioninfer_tpu.utils import spans

CFG = get_preset("qwen3-tiny")
CACHE = CacheConfig(n_pages=64, page_size=8, max_pages_per_seq=8)
HOST_FAMILIES = [f"fusioninfer:host_{name.replace('.', '_')}_seconds_total"
                 for name in spans.SPAN_NAMES]
CPU_FAMILIES = [f"fusioninfer:engine_cpu_{name.replace('.', '_')}_seconds_total"
                for name in spans.SPAN_NAMES]
STREAM_FAMILIES = [
    "fusioninfer:stream_render_seconds_total",
    "fusioninfer:stream_write_seconds_total",
    "fusioninfer:stream_cpu_seconds_total", "fusioninfer:stream_chunks_total",
    "fusioninfer:stream_writes_total", "fusioninfer:stream_delay_seconds_sum",
    "fusioninfer:stream_delay_seconds_count"]
FAMILIES = HOST_FAMILIES + CPU_FAMILIES + STREAM_FAMILIES + [
    "fusioninfer:engine_loop_seconds_total",
    "fusioninfer:engine_thread_cpu_seconds_total",
    "fusioninfer:engine_stalls_total", "fusioninfer:engine_stall_seconds_total",
    "fusioninfer:jit_seconds_total", "fusioninfer:gc_seconds_total",
    "vllm:request_queue_time_seconds_sum",
    "vllm:request_queue_time_seconds_count",
    "vllm:request_prefill_time_seconds_sum",
    "vllm:request_prefill_time_seconds_count"]


class FakeClock:
    def __init__(self):
        self.t = 0

    def __call__(self):
        return self.t


def test_nested_spans_add_self_times():
    now = FakeClock()
    clock = spans.SpanClock(now)
    with clock.span("step"):                   # [0, 100)
        now.t = 10
        with clock.span("step.pack"):          # [10, 60)
            now.t = 20
            with clock.span("step.dispatch"):  # [20, 35)
                now.t = 35
            now.t = 40
            with clock.span("step.fetch"):     # [40, 55)
                now.t = 55
            now.t = 60
        now.t = 70
        with clock.span("step.dispatch"):      # [70, 75), a second one
            now.t = 75
        now.t = 100
    assert clock.stack == []
    assert clock.ns["step.dispatch"] == 15 + 5
    assert clock.ns["step.fetch"] == 15
    assert clock.ns["step.pack"] == 50 - 15 - 15
    assert clock.ns["step"] == 100 - 50 - 5
    assert sum(clock.ns.values()) == 100  # self times add up
    assert clock.ns["loop.idle"] == 0  # pre-seeded, never opened


def test_nested_spans_add_self_cpu_times():
    """The CPU clock is read beside the wall at each edge, and a span's
    self CPU time is its CPU time less its children's, as on the wall."""
    now, cpu = FakeClock(), FakeClock()
    clock = spans.SpanClock(now, cpu)
    with clock.span("step"):                   # CPU [0, 15), wall [0, 100)
        cpu.t = 5
        with clock.span("step.dispatch"):      # CPU [5, 12), wall [0, 30)
            now.t, cpu.t = 30, 12
        with clock.span("step.fetch"):         # CPU [12, 13), wall [30, 90)
            now.t, cpu.t = 90, 13
        now.t, cpu.t = 100, 15
    assert clock.stack == []
    assert clock.cpu_ns["step.dispatch"] == 7
    assert clock.cpu_ns["step.fetch"] == 1
    assert clock.cpu_ns["step"] == 15 - 7 - 1
    assert sum(clock.cpu_ns.values()) == 15  # self CPU times add up
    assert clock.ns["step.fetch"] == 60 and clock.ns["step"] == 10
    # wall less CPU: what each span waited
    assert {k: clock.ns[k] - clock.cpu_ns[k]
            for k in ("step", "step.dispatch", "step.fetch")} == {
        "step": 3, "step.dispatch": 23, "step.fetch": 59}


def test_a_span_that_raises_still_closes():
    now = FakeClock()
    clock = spans.SpanClock(now)
    with pytest.raises(ValueError):
        with clock.span("step"):
            with clock.span("step.emit"):
                now.t = 7
                raise ValueError("boom")
    assert clock.stack == []
    assert clock.ns["step.emit"] == 7 and clock.ns["step"] == 0


def parse(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line and line[0] != "#" and "_bucket{" not in line:
            name, _, value = line.rpartition(" ")
            out[name.split("{", 1)[0]] = float(value)
    return out


def complete(srv, prompt: str, max_tokens: int) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/v1/completions",
        data=json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                         "temperature": 0.0}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.load(r)


def stream(srv, prompt: str, max_tokens: int) -> int:
    """A streamed completion; the number of its chunks that carried a
    token (every ``data:`` event but the closing ``[DONE]``)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/v1/completions",
        data=json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                         "temperature": 0.0, "stream": True}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        events = [line[len("data: "):] for line in r.read().decode().splitlines()
                  if line.startswith("data: ")]
    assert events[-1] == "[DONE]"
    return len(events) - 1


def metrics(srv) -> dict:
    return parse(srv.metrics.render(srv.engine))


def settled(srv, before: dict, streamed: int) -> dict:
    """The metrics once the stream handlers have counted the ``streamed``
    token chunks a client has read since ``before``: a handler records a
    write after the write returns, so its client can finish reading
    first (a bounded wait)."""
    family = "fusioninfer:stream_chunks_total"
    deadline = time.monotonic() + 10.0
    while True:
        now = metrics(srv)
        if (now[family] - before[family] >= streamed
                or time.monotonic() > deadline):
            return now
        time.sleep(0.005)


@pytest.fixture(scope="module")
def served():
    """A tiny engine served for a few dozen steps, then stopped: the
    engine thread has made its last tick, so the totals stand still.
    Three requests stream (their token chunks are counted), the fourth
    does not."""
    srv = EngineServer(model="qwen3-tiny", host="127.0.0.1", port=0,
                       max_batch_size=4, cache_cfg=CACHE)
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=30) as r:
            first = parse(r.read().decode())
        streamed = sum(stream(srv, "span %d " % i * (2 + i), 12)
                       for i in range(3))
        mid = settled(srv, first, streamed)
        complete(srv, "one more", 8)
    finally:
        srv.stop()
    return srv, first, mid, metrics(srv), streamed


def test_every_family_is_rendered_and_monotone(served):
    _, first, mid, last, _ = served
    for family in FAMILIES:
        assert family in first, family  # pre-seeded: there before any work
        assert first[family] <= mid[family] <= last[family], family
    for name in ("step", "step.admit", "step.pack", "step.dispatch",
                 "step.fetch", "step.emit", "loop.publish", "loop.idle"):
        name = name.replace(".", "_")
        assert last[f"fusioninfer:engine_cpu_{name}_seconds_total"] > 0, name
        assert last[f"fusioninfer:host_{name}_seconds_total"] > 0, name
    assert last["fusioninfer:sched_steps_total"] >= 20
    assert 0 < last["fusioninfer:engine_thread_cpu_seconds_total"] \
        <= last["fusioninfer:engine_loop_seconds_total"]


def test_span_seconds_cover_the_loop(served):
    last = served[3]
    loop = last["fusioninfer:engine_loop_seconds_total"]
    covered = sum(last[f] for f in HOST_FAMILIES)
    assert 0.95 * loop <= covered <= loop, (covered, loop)


# the CPU clock and the wall are read one after the other at each edge
# of a span, so a span that never left the CPU can read a little more CPU
# than wall; this much, over a few dozen steps, is far more than that
CLOCK_READS_S = 1e-3


def test_engine_cpu_by_span_is_within_its_wall(served):
    last = served[3]
    for host, cpu in zip(HOST_FAMILIES, CPU_FAMILIES):
        assert 0 <= last[cpu] <= last[host] + CLOCK_READS_S, (cpu, last[cpu],
                                                              last[host])
    # the thread's CPU in spans is within its CPU over the loop
    assert sum(last[f] for f in CPU_FAMILIES) <= \
        last["fusioninfer:engine_thread_cpu_seconds_total"] + CLOCK_READS_S


def test_stream_chunks_and_delays_count_the_items_taken(served):
    """One chunk, and one delay, per output taken from a stream's
    channel; a request that does not stream writes none."""
    _, first, mid, last, streamed = served
    assert streamed >= 3
    assert first["fusioninfer:stream_chunks_total"] == 0
    assert mid["fusioninfer:stream_chunks_total"] == streamed
    assert last["fusioninfer:stream_chunks_total"] == streamed
    assert last["fusioninfer:stream_delay_seconds_count"] == streamed
    assert last["fusioninfer:stream_delay_seconds_sum"] > 0


def test_a_write_carries_a_step_of_a_stream(served):
    """One socket write a hand-off: never more writes than token chunks
    (a one-token engine writes each chunk alone)."""
    _, first, _, last, streamed = served
    assert first["fusioninfer:stream_writes_total"] == 0
    assert 0 < last["fusioninfer:stream_writes_total"] <= streamed


def test_a_burst_streams_in_fewer_writes_than_chunks():
    """On a burst engine a step hands a stream its span of tokens, and
    the stream writes them in one write: fewer writes than chunks, and
    still one chunk and one delay per token."""
    engine = NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2, seed=0,
                          decode_burst_steps=4)
    srv = EngineServer(model="qwen3-tiny", host="127.0.0.1", port=0,
                       engine=engine)
    srv.start()
    try:
        before = metrics(srv)
        streamed = stream(srv, "a burst ", 12)
        after = settled(srv, before, streamed)
    finally:
        srv.stop()
    chunks, writes, delays = (
        after[f] - before[f] for f in ("fusioninfer:stream_chunks_total",
                                       "fusioninfer:stream_writes_total",
                                       "fusioninfer:stream_delay_seconds_count"))
    assert chunks == delays == streamed == 12
    assert 1 <= writes < chunks


def test_stream_render_write_and_cpu_grow(served):
    _, first, mid, last, _ = served
    for name in ("render", "write", "cpu"):
        family = f"fusioninfer:stream_{name}_seconds_total"
        assert first[family] == 0 < mid[family] <= last[family], family


def test_a_streaming_thread_reads_its_cpu_clock_once_a_second():
    """A chunk's accounting reads no CPU clock; a handler's CPU is read
    as it starts streaming, at the first chunk a ``CPU_READ_NS`` or more
    after its last read, and as it stops."""
    now, reads = FakeClock(), []

    def cpu():
        reads.append(now.t)
        return now.t // 2  # on the CPU half the time

    stream = spans.StreamClock(now, cpu)
    now.t = 7
    stream.written(3, 4, 5_000_000)  # a token's chunk
    stream.written(1, 1)             # a chunk that carries none
    assert (stream.render_ns, stream.write_ns, stream.chunks) == (4, 5, 1)
    assert stream.delay_ns == 5_000_000
    assert reads == []
    second = spans.CPU_READ_NS
    with stream.streaming() as meter:
        for _ in range(5):  # chunks 0.4 s apart
            now.t += 2 * second // 5
            meter.tick()
        assert stream.cpu_seconds() == 0.6  # read once, at 1.2 s
        now.t += second // 10
    assert reads == [7, 7 + 6 * second // 5, 7 + 21 * second // 10]
    assert stream.cpu_seconds() == 2.1 / 2


def test_the_only_host_seconds_families_are_the_span_walls(served):
    """``perfbench/spanread.all_spans_seconds`` sums every
    ``fusioninfer:host_*_seconds_total`` as the engine thread's wall time
    in spans: no other family may take that form."""
    last = served[3]
    got = {f for f in last
           if re.fullmatch(r"fusioninfer:host_.*_seconds_total", f)}
    assert got == set(HOST_FAMILIES) and len(got) == len(spans.SPAN_NAMES) == 9


def test_a_collection_raises_gc_seconds(served):
    srv = served[0]
    before = metrics(srv)["fusioninfer:gc_seconds_total"]
    gc.collect()
    assert metrics(srv)["fusioninfer:gc_seconds_total"] > before
    assert spans._on_gc in gc.callbacks
    spans.watch_gc()  # registered once however often it is asked for
    assert gc.callbacks.count(spans._on_gc) == 1


def test_request_waits_count_first_tokens(served):
    srv, _, _, last, _ = served
    assert last["vllm:request_queue_time_seconds_count"] == 4
    assert last["vllm:request_prefill_time_seconds_count"] == 4
    assert last["vllm:request_queue_time_seconds_count"] == \
        last["vllm:time_to_first_token_seconds_count"]
    assert last["vllm:request_prefill_time_seconds_sum"] > 0
    # the deque the histograms are fed beside stays (bench.py reads it)
    assert len(srv.engine.admission_timings) == 4


def test_no_annotation_outside_a_capture(monkeypatch):
    opened = []

    class Counting:
        def __init__(self, name, **attrs):
            opened.append((name, attrs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

        def set_metadata(self, **attrs):
            opened.append(("note", attrs))

    monkeypatch.setattr(spans, "TraceAnnotation", Counting)
    clock = spans.SpanClock()
    assert spans.capturing is False
    with clock.span("step", step=1) as sp:
        sp.note(tokens=3)
    with spans.annotation("stream.write"):  # a stream handler's
        pass
    assert opened == [] and clock.stack == []
    monkeypatch.setattr(spans, "capturing", True)
    with clock.span("step", step=2) as sp:
        sp.note(tokens=3)
    with spans.annotation("stream.write"):
        pass
    assert opened == [("step", {"step": 2}), ("note", {"tokens": 3}),
                      ("stream.write", {})]


def test_a_capture_holds_the_spans_and_no_python_calls(tmp_path):
    from jax.profiler import ProfileData

    engine = NativeEngine(cfg=CFG, cache_cfg=CACHE, max_batch_size=4, seed=0)
    srv = EngineServer(model="qwen3-tiny", host="127.0.0.1", port=0,
                       engine=engine)
    srv.enable_profiling = True
    srv.profile_dir = str(tmp_path)
    # the capture window: requests served while the trace runs
    srv._profile_sleep = lambda _s: [complete(srv, "traced", 6),
                                     stream(srv, "traced", 6)]
    srv.start()
    try:
        complete(srv, "warm", 6)  # compile outside the capture
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/debug/profile",
            data=json.dumps({"seconds": 0.5}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300) as r:
            assert json.load(r)["status"] == "ok"
        assert spans.capturing is False
    finally:
        srv.stop()
    (xplane,) = glob.glob(str(tmp_path) + "/**/*.xplane.pb", recursive=True)
    host = [p for p in ProfileData.from_file(xplane).planes
            if p.name == "/host:CPU"]
    names: dict[str, int] = {}
    total = 0
    for plane in host:
        for line in plane.lines:
            for ev in line.events:
                total += 1
                names[ev.name] = names.get(ev.name, 0) + 1
    for name in ("step", "step.fetch", "loop.publish", "stream.render",
                 "stream.write"):
        assert names.get(name, 0) > 0, sorted(names)[:40]
    # the default options trace every Python call of every thread:
    # hundreds of thousands of events for this much work
    assert total < 50_000, total
    assert not [n for n in names if re.match(r"^\$.*\.py:\d+ ", n)], \
        "per-Python-call events in the host plane"


def test_jit_listener_counts_a_miss_and_nothing_on_a_hit():
    from jax._src import monitoring

    spans.watch_jit()
    spans.watch_jit()  # registered once however often it is asked for
    assert monitoring.get_event_duration_listeners().count(
        spans._on_jit_event) == 1

    @jax.jit
    def fresh(x):
        return x * 3 + 1

    x = jnp.arange(5.0)
    before = spans.jit_totals["seconds"]
    fresh(x).block_until_ready()
    first = spans.jit_totals["seconds"]
    assert first > before  # trace, lower(, compile)
    fresh(x).block_until_ready()
    assert spans.jit_totals["seconds"] == first


# -- an engine-loop iteration of 250 ms or more is a stall --------------------

class _SlowStepEngine:
    """An engine double whose second step takes 300 ms on the clock it
    hands the loop, inside a ``step.fetch`` span."""

    class _Cfg:
        vocab_size = 512

    cfg = _Cfg()
    guided_enabled = True  # skips the guided-vocab bootstrap

    def __init__(self):
        self.now = FakeClock()
        self.spans = spans.SpanClock(self.now)
        self.steps = [1_000_000, 300_000_000]

    def has_work(self):
        return bool(self.steps)

    def forward_in_flight(self):
        return False

    def cancel(self, request_id):
        pass

    def fail_all(self, reason, retry_after_s=None):
        return []

    def step(self):
        with self.spans.span("step"):
            with self.spans.span("step.fetch", program="decode_burst"):
                self.now.t += self.steps[0]
        self.steps.pop(0)
        return []


def test_a_long_iteration_is_one_stall_and_one_line(caplog):
    from fusioninfer_tpu.engine.tokenizer import ByteTokenizer

    engine = _SlowStepEngine()
    srv = EngineServer(model="stub", host="127.0.0.1", port=0, engine=engine,
                       tokenizer=ByteTokenizer())
    with caplog.at_level(logging.WARNING, logger="fusioninfer.server"):
        srv.start()
        try:
            deadline = time.monotonic() + 30
            while engine.steps and time.monotonic() < deadline:
                time.sleep(0.01)
        finally:
            srv.stop()
    assert not engine.steps
    clock = engine.spans
    assert clock.stalls == 1 and clock.stall_ns == 300_000_000
    from fusioninfer_tpu.engine.metrics import TTFT_BUCKETS, Histogram

    engine.queue_time = engine.prefill_time = Histogram(TTFT_BUCKETS)
    page = parse("\n".join(srv.metrics._render_host(engine, 'model_name="m"')))
    assert page["fusioninfer:engine_stalls_total"] == 1
    assert page["fusioninfer:engine_stall_seconds_total"] == 0.3
    lines = [r.getMessage() for r in caplog.records
             if r.getMessage().startswith("engine stall")]
    assert len(lines) == 1, lines
    line = lines[0]
    assert line.startswith("engine stall 0.300 s: start=")
    for field in ("longest_span=step.fetch", "longest_span_s=0.300",
                  "program=decode_burst", "capture=off/off", "gc_s=",
                  "jit_s=", "stream_cpu_s=", "engine_cpu_s="):
        assert field in line, (field, line)
    start, end = (float(re.search(rf"{k}=([0-9.]+)", line).group(1))
                  for k in ("start", "end"))
    assert end - start == pytest.approx(0.3, abs=2e-3)


# -- the loop holds a step's tokens until the device has work ----------------

class _ScriptedEngine:
    """An engine double that plays a script of steps on the loop's own
    thread and records, at each point, what the server has published."""

    class _Cfg:
        vocab_size = 512

    cfg = _Cfg()
    guided_enabled = True  # skips the guided-vocab bootstrap

    def __init__(self, script, hook: bool = True):
        self.script = list(script)
        self.seen: list = []  # (label, tokens published so far)
        self.chan = None
        self.in_flight = False
        if hook:
            self.on_forward_enqueued = None

    def add_request(self, request):
        pass

    def cancel(self, request_id):
        pass

    def has_work(self):
        return bool(self.script)

    def forward_in_flight(self):
        return self.in_flight

    def fail_all(self, reason, retry_after_s=None):
        return []

    def _published(self):
        return self.chan.q.qsize() if self.chan is not None else 0

    def step(self):
        token, enqueues, self.in_flight = self.script.pop(0)
        self.seen.append(("enter", self._published()))
        hook = getattr(self, "on_forward_enqueued", None)
        if enqueues and hook is not None:
            hook()
        self.seen.append(("forward enqueued" if enqueues else "no forward",
                          self._published()))
        from fusioninfer_tpu.engine.engine import StepOutput

        return [StepOutput(request_id=self.rid, token=token,
                           finished=not self.script)]


def _play(script, hook=True):
    from fusioninfer_tpu.engine.sampler import SamplingParams
    from fusioninfer_tpu.engine.tokenizer import ByteTokenizer

    engine = _ScriptedEngine([], hook=hook)
    srv = EngineServer(model="stub", host="127.0.0.1", port=0, engine=engine,
                       tokenizer=ByteTokenizer())
    engine.chan = srv.submit([1, 2, 3], SamplingParams(max_tokens=len(script)))
    with srv._lock:
        engine.rid = next(iter(srv._channels))
    engine.script = list(script)  # has_work() turns true: the loop steps
    srv.start()
    try:
        got = [engine.chan.q.get(timeout=10.0)[0].token for _ in script]
    finally:
        srv.stop()
    return engine.seen, got


@pytest.mark.parametrize("case", [
    # (token, does the step enqueue a forward, is one left in flight)
    pytest.param(([(7, True, False), (8, True, False), (9, True, False)],
                  [0, 0, 0, 1, 1, 2]), id="held-until-the-next-forward"),
    pytest.param(([(7, True, True), (8, True, True), (9, True, False)],
                  [0, 0, 1, 1, 2, 2]), id="at-once-with-a-burst-in-flight"),
    pytest.param(([(7, True, False), (8, False, False), (9, True, False)],
                  [0, 0, 0, 0, 1, 2]), id="a-step-without-a-forward"),
])
def test_tokens_wait_for_the_next_forward_only_while_the_device_is_idle(case):
    script, want = case
    seen, got = _play(script)
    assert got == [t for t, _, _ in script]  # all delivered, in order
    assert [n for _, n in seen] == want, seen


def test_an_engine_without_the_hook_is_published_at_once():
    seen, got = _play([(7, True, False), (8, True, False)], hook=False)
    assert got == [7, 8]
    assert [n for _, n in seen] == [0, 0, 1, 1]


def test_the_identity_experts_have_a_scope_and_a_counter():
    """LongCat-Flash's identity ("zero-compute") experts: their term is
    traced under ``moe_zero`` (device time by named scope reads it beside
    ``moe_experts`` and ``mlp``), and what it did is counted on the device
    and rendered as ``fusioninfer:moe_assignments_zero_total``."""
    import dataclasses

    from fusioninfer_tpu.engine import model_runner as mr
    from fusioninfer_tpu.engine.kv_cache import init_kv_cache
    from fusioninfer_tpu.engine.metrics import EngineMetrics
    from fusioninfer_tpu.models import transformer as tf

    cfg = dataclasses.replace(get_preset("longcat-flash-tiny"),
                              attn_impl="reference")
    cc = CacheConfig(n_pages=8, page_size=16, max_pages_per_seq=4)
    params = jax.eval_shape(lambda: tf.init_params(cfg, jax.random.key(0)))
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, cc))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    text = mr.fused_step.lower(
        cfg, cc, params, cache, i32(16), i32(8), i32(8), i32(8), i32(8, 4),
        i32(2, 1), i32(2), coalesce=True, kv_splits=0).as_text(debug_info=True)
    for scope in ("moe_zero", "moe_experts", "moe_route", "mlp", "mla_q",
                  "mla_kv", "mla_out", "kv_write"):
        assert re.search(r'loc\("([a-z_]+/)*%s[/"]' % scope, text), scope
    # a model without identity experts traces no such scope
    ds = dataclasses.replace(get_preset("deepseek-v2-tiny"),
                             attn_impl="reference")
    text = mr.fused_step.lower(
        ds, cc, jax.eval_shape(lambda: tf.init_params(ds, jax.random.key(0))),
        jax.eval_shape(lambda: init_kv_cache(ds, cc)), i32(16), i32(8),
        i32(8), i32(8), i32(8, 4), i32(2, 1), i32(2), coalesce=True,
        kv_splits=0).as_text(debug_info=True)
    assert "moe_zero" not in text and "moe_experts/" in text

    eng = NativeEngine(cfg, cache_cfg=CACHE, max_batch_size=2, seed=0,
                       token_budget=16)
    page = EngineMetrics("m").render(eng)
    assert "# TYPE fusioninfer:moe_assignments_zero_total counter" in page
    assert parse(page)["fusioninfer:moe_assignments_zero_total"] == 0
    from fusioninfer_tpu.engine.engine import Request
    from fusioninfer_tpu.engine.sampler import SamplingParams

    eng.add_request(Request("a", [1] + list(range(3, 30)),
                            SamplingParams(max_tokens=6, temperature=0.0)))
    while eng.has_work():
        eng.step()
    eng._drain_moe_stats()
    after = parse(EngineMetrics("m").render(eng))
    zero = after["fusioninfer:moe_assignments_zero_total"]
    assert 0 < zero < after["fusioninfer:moe_assignments_total"]
    assert zero + after["fusioninfer:moe_assignments_local_total"] < after[
        "fusioninfer:moe_assignments_total"]


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_the_layer_kinds_have_scopes_and_the_router_runs_first(impl):
    """SmallThinker's two layer kinds: each attention is traced under
    ``attn_full`` or ``attn_window`` inside ``attn`` (device time by
    named scope tells a full layer's walk from a window's), and
    ``moe_route`` is traced BEFORE the layer's attention: it reads the
    layer's input.  A model of one kind traces only its own."""
    import dataclasses

    from fusioninfer_tpu.engine import model_runner as mr
    from fusioninfer_tpu.engine.kv_cache import (
        auto_cache_config,
        init_kv_cache,
    )
    from fusioninfer_tpu.models import transformer as tf

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def lowered(cfg, cc, tables):
        params = jax.eval_shape(lambda: tf.init_params(cfg, jax.random.key(0)))
        cache = jax.eval_shape(lambda: init_kv_cache(cfg, cc))
        return mr.fused_step.lower(
            cfg, cc, params, cache, i32(16), i32(8), i32(8), i32(8), tables,
            i32(2, 1), i32(2), coalesce=True,
            kv_splits=(0, 0) if cfg.cache_by_kind else 0).as_text(
                debug_info=True)

    cfg = dataclasses.replace(get_preset("smallthinker-tiny"), attn_impl=impl)
    cc = auto_cache_config(cfg, page_size=8, max_model_len=64,
                           max_batch_size=2, step_span=16)
    text = lowered(cfg, cc, i32(8, 2, cc.max_pages_per_seq))
    for scope in ("attn/attn_full", "attn/attn_window", "moe_route",
                  "moe_experts", "attn_qkv", "kv_write"):
        assert re.search(r'loc\("([a-z_]+/)*%s[/"]' % scope, text), scope
    if impl == "flash":  # the window kind's calls carry their own name
        assert "ragged_paged_attention_window" in text
    first = {scope: text.index(f'loc("{scope}')
             for scope in ("moe_route", "attn_qkv", "moe_experts")}
    assert first["moe_route"] < first["attn_qkv"] < first["moe_experts"]
    one = dataclasses.replace(CFG, attn_impl=impl)
    text = lowered(one, CACHE, i32(8, CACHE.max_pages_per_seq))
    assert "attn/attn_full" in text and "attn_window" not in text


def test_pages_by_kind_are_rendered_and_counted():
    """``fusioninfer:kv_pages_in_use{kind}``, ``fusioninfer:
    kv_pages_allocated_total{kind}`` and ``fusioninfer:
    kv_window_pages_trimmed_total``: a model of one kind renders
    ``kind="full"`` alone and trims nothing unless it is windowed; a
    cache kept by layer kind counts both and trims the window kind's."""
    from fusioninfer_tpu.engine.engine import Request
    from fusioninfer_tpu.engine.kv_cache import auto_cache_config
    from fusioninfer_tpu.engine.metrics import EngineMetrics
    from fusioninfer_tpu.engine.sampler import SamplingParams

    def serve(eng, n_prompt, n_out):
        eng.add_request(Request("a", [1] + list(range(3, 2 + n_prompt)),
                                SamplingParams(max_tokens=n_out,
                                               temperature=0.0)))
        peak = {}
        while eng.has_work():
            eng.step()
            page = EngineMetrics("m").render(eng)
            for kind in ("full", "window"):
                m = re.search(r'kv_pages_in_use\{[^}]*kind="%s"\} (\d+)'
                              % kind, page)
                if m:
                    peak[kind] = max(peak.get(kind, 0), int(m.group(1)))
        return EngineMetrics("m").render(eng), peak

    page, peak = serve(NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2,
                                    token_budget=16), 20, 30)
    for family, kind in (("kv_pages_in_use", "gauge"),
                         ("kv_pages_allocated_total", "counter"),
                         ("kv_window_pages_allocated_total", "counter"),
                         ("kv_window_pages_trimmed_total", "counter")):
        assert f"# TYPE fusioninfer:{family} {kind}" in page
        assert f"# HELP fusioninfer:{family} " in page
    assert 'model_name="m",kind="window"' not in page
    # 49 cached positions at the end (read a step before the last page)
    assert list(peak) == ["full"] and peak["full"] in (6, 7)
    got = parse(page)
    assert got["fusioninfer:kv_window_pages_trimmed_total"] == 0
    assert got["fusioninfer:kv_pages_allocated_total"] == 7

    cfg = get_preset("smallthinker-tiny")
    cc = auto_cache_config(cfg, page_size=8, max_model_len=96,
                           max_batch_size=2, step_span=16)
    page, peak = serve(NativeEngine(cfg, cache_cfg=cc, max_batch_size=2,
                                    token_budget=16), 20, 60)
    got = parse(page)
    assert peak["full"] in (9, 10) and peak["window"] <= 24 // 8 + 16 // 8 + 1
    trimmed = got["fusioninfer:kv_window_pages_trimmed_total"]
    handed = got["fusioninfer:kv_window_pages_allocated_total"]
    assert 0 < trimmed < handed == 10
    # the labelled family holds both kinds' samples
    for kind in ("full", "window"):
        assert ('fusioninfer:kv_pages_allocated_total{model_name="m",'
                f'kind="{kind}"}} 10') in page
    assert got["vllm:kv_cache_usage_perc"] == 0.0


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_sparse_attention_has_its_scopes_and_counters(impl):
    """A sparse-attention layer's three steps are traced under
    ``attn/indexer``, ``attn/select`` and ``attn/sparse`` (device time by
    named scope reads each), the kernels carry their own names
    (``indexer_paged_scores``, ``sparse_paged_attention``), and what the
    indexer scored and the attention chose is counted on the device and
    rendered as ``fusioninfer:dsa_positions_{scored,selected}_total``; a
    dense model traces none of it and renders zeros."""
    import dataclasses

    from fusioninfer_tpu.engine import model_runner as mr
    from fusioninfer_tpu.engine.engine import Request
    from fusioninfer_tpu.engine.kv_cache import init_kv_cache
    from fusioninfer_tpu.engine.metrics import EngineMetrics
    from fusioninfer_tpu.engine.sampler import SamplingParams
    from fusioninfer_tpu.models import transformer as tf

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    def lowered(cfg, debug_info=True):
        params = jax.eval_shape(lambda: tf.init_params(cfg, jax.random.key(0)))
        cache = jax.eval_shape(lambda: init_kv_cache(cfg, CACHE))
        return mr.fused_step.lower(
            cfg, CACHE, params, cache, i32(16), i32(8), i32(8), i32(8),
            i32(8, CACHE.max_pages_per_seq), i32(2, 1), i32(2),
            coalesce=True, kv_splits=0).as_text(debug_info=debug_info)

    cfg = dataclasses.replace(get_preset("keye-vl2-tiny"), attn_impl=impl)
    text = lowered(cfg)
    for scope in ("attn/indexer", "attn/select", "attn/sparse", "kv_write",
                  "moe_experts"):
        assert re.search(r'loc\("([a-z_]+/)*%s[/"]' % scope, text), scope
    if impl == "flash":
        assert "indexer_paged_scores" in text
        assert "sparse_select" in text
        assert "sparse_paged_attention" in text
    dense = lowered(dataclasses.replace(CFG, attn_impl=impl), False)
    assert "indexer_paged_scores" not in dense
    assert "sparse_paged_attention" not in dense and "k_idx" not in dense

    eng = NativeEngine(cfg, cache_cfg=CACHE, max_batch_size=2, seed=0,
                       token_budget=16)
    page = EngineMetrics("m").render(eng)
    for name in ("scored", "selected"):
        assert f"# TYPE fusioninfer:dsa_positions_{name}_total counter" in page
        assert parse(page)[f"fusioninfer:dsa_positions_{name}_total"] == 0
    eng.add_request(Request("a", [1] + list(range(3, 60)),
                            SamplingParams(max_tokens=4, temperature=0.0)))
    while eng.has_work():
        eng.step()
    eng._drain_dsa_stats()
    after = parse(EngineMetrics("m").render(eng))
    scored = after["fusioninfer:dsa_positions_scored_total"]
    # 58 prompt positions and 3 decoded ones, two layers, top 24
    assert scored >= 2 * sum(range(1, 62))
    assert 0 < after["fusioninfer:dsa_positions_selected_total"] < scored
    plain = NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2, seed=0)
    assert parse(EngineMetrics("m").render(plain))[
        "fusioninfer:dsa_positions_scored_total"] == 0
