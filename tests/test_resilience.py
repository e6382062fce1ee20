"""Resilience layer + chaos suite.

Unit tier: RetryPolicy (seeded jitter, deadline budget), CircuitBreaker
(closed/open/half-open), FaultInjector (deterministic decisions), and
the KV slab wire format's CRC32.

Chaos tier (``@pytest.mark.chaos``, also in tier-1; ``make chaos`` runs
it alone): deterministic fault injection through real components —
KV-transfer drop/delay/corrupt with token-identical completion (retry or
local re-prefill fallback), router endpoint ejection + half-open
recovery, operator exponential requeue + Degraded condition, and the
engine server's deadline/stall watchdog.
"""

import json
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from fusioninfer_tpu.resilience import (
    CircuitBreaker,
    FaultInjector,
    InjectedFault,
    RetryBudgetExhausted,
    RetryPolicy,
)

# -- RetryPolicy --------------------------------------------------------------


class TestRetryPolicy:
    def test_backoff_caps_grow_exponentially_to_ceiling(self):
        p = RetryPolicy(max_attempts=10, base_delay_s=0.5, max_delay_s=4.0,
                        multiplier=2.0, jitter="none")
        assert [p.delay(a) for a in range(1, 6)] == [0.5, 1.0, 2.0, 4.0, 4.0]

    def test_full_jitter_is_seeded_and_bounded(self):
        a = RetryPolicy(max_attempts=5, base_delay_s=1.0, max_delay_s=8.0, seed=42)
        b = RetryPolicy(max_attempts=5, base_delay_s=1.0, max_delay_s=8.0, seed=42)
        da = [a.delay(i) for i in range(1, 5)]
        db = [b.delay(i) for i in range(1, 5)]
        assert da == db, "same seed must replay the same schedule"
        for i, d in enumerate(da, start=1):
            assert 0.0 <= d <= a.backoff_cap(i)

    def test_run_retries_then_succeeds(self):
        calls = []
        sleeps = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        p = RetryPolicy(max_attempts=4, base_delay_s=0.01, jitter="none")
        assert p.run(flaky, sleep=sleeps.append) == "ok"
        assert len(calls) == 3 and sleeps == [0.01, 0.02]

    def test_run_exhausts_attempts(self):
        p = RetryPolicy(max_attempts=3, base_delay_s=0.0, jitter="none")
        with pytest.raises(RetryBudgetExhausted) as ei:
            p.run(lambda: (_ for _ in ()).throw(OSError("down")),
                  sleep=lambda d: None)
        assert isinstance(ei.value.last_error, OSError)

    def test_non_retryable_errors_propagate_immediately(self):
        calls = []

        def bad_request():
            calls.append(1)
            raise ValueError("your fault, not mine")

        p = RetryPolicy(max_attempts=5, base_delay_s=0.0)
        with pytest.raises(ValueError):
            p.run(bad_request, retry_on=(OSError,), sleep=lambda d: None)
        assert len(calls) == 1

    def test_deadline_budget_stops_retrying(self):
        clock = [0.0]

        def sleep(d):
            clock[0] += d

        p = RetryPolicy(max_attempts=100, base_delay_s=1.0, max_delay_s=1.0,
                        jitter="none", deadline_s=2.5)
        with pytest.raises(RetryBudgetExhausted, match="deadline budget"):
            p.run(lambda: (_ for _ in ()).throw(OSError("down")),
                  sleep=sleep, clock=lambda: clock[0])
        assert clock[0] <= 2.5

    def test_validation(self):
        with pytest.raises(ValueError):
            RetryPolicy(max_attempts=0)
        with pytest.raises(ValueError):
            RetryPolicy(jitter="equal")
        with pytest.raises(ValueError):
            RetryPolicy(multiplier=0.5)


# -- CircuitBreaker -----------------------------------------------------------


class TestCircuitBreaker:
    def _breaker(self, **kw):
        self.clock = [0.0]
        kw.setdefault("failure_threshold", 3)
        kw.setdefault("recovery_timeout_s", 10.0)
        return CircuitBreaker(clock=lambda: self.clock[0], **kw)

    def test_trips_open_after_consecutive_failures(self):
        b = self._breaker()
        for _ in range(2):
            b.record_failure()
        assert b.state == "closed" and b.allow()
        b.record_failure()
        assert b.state == "open" and not b.allow()

    def test_success_resets_the_consecutive_count(self):
        b = self._breaker()
        for _ in range(2):
            b.record_failure()
        b.record_success()
        b.record_failure()
        b.record_failure()
        assert b.state == "closed", "non-consecutive failures must not trip"

    def test_half_open_probe_success_closes(self):
        b = self._breaker(half_open_max_probes=1)
        for _ in range(3):
            b.record_failure()
        assert not b.allow()
        self.clock[0] = 10.0
        assert b.state == "half-open"
        assert b.allow(), "recovery window elapsed: one probe allowed"
        assert not b.allow(), "probe quota is rationed"
        b.record_success()
        assert b.state == "closed" and b.allow()

    def test_stale_success_while_open_is_ignored(self):
        """A request sent before the trip that completes late must not
        close the breaker — only a half-open probe verdict may."""
        b = self._breaker()
        for _ in range(3):
            b.record_failure()
        b.record_success()  # pre-trip request finally completed
        assert b.state == "open" and not b.allow(), \
            "stale success must not bypass the recovery window"
        self.clock[0] = 10.0
        b.record_success()  # window elapsed but no probe admitted yet
        assert b.state == "half-open", "still stale: no probe in flight"
        assert b.allow()
        b.record_success()  # the probe's verdict
        assert b.state == "closed"

    def test_half_open_probe_failure_reopens(self):
        b = self._breaker()
        for _ in range(3):
            b.record_failure()
        self.clock[0] = 10.0
        assert b.allow()
        b.record_failure()
        assert b.state == "open" and not b.allow()
        self.clock[0] = 19.9
        assert not b.allow(), "re-open starts a FRESH recovery window"
        self.clock[0] = 20.0
        assert b.allow()


# -- FaultInjector ------------------------------------------------------------


class TestFaultInjector:
    def test_unarmed_sites_are_noops(self):
        inj = FaultInjector()
        inj.fire("kv.pull")  # nothing armed: must not raise
        assert inj.corrupt("kv.pull.response", b"abc") == b"abc"
        assert not inj.active

    def test_drop_and_error_raise_injected_fault(self):
        inj = FaultInjector().arm("site", "drop")
        with pytest.raises(InjectedFault) as ei:
            inj.fire("site")
        assert ei.value.mode == "drop" and ei.value.site == "site"

    def test_times_bounds_firings(self):
        inj = FaultInjector().arm("site", "error", times=2)
        for _ in range(2):
            with pytest.raises(InjectedFault):
                inj.fire("site")
        inj.fire("site")  # healed
        assert inj.fired_count("site") == 2

    def test_after_skips_leading_calls(self):
        inj = FaultInjector().arm("site", "error", after=2)
        inj.fire("site")
        inj.fire("site")
        with pytest.raises(InjectedFault):
            inj.fire("site")

    def test_probability_draws_are_seeded(self):
        def firings(seed):
            inj = FaultInjector(seed=seed).arm("s", "error", probability=0.5)
            out = []
            for _ in range(20):
                try:
                    inj.fire("s")
                    out.append(0)
                except InjectedFault:
                    out.append(1)
            return out

        assert firings(7) == firings(7), "same seed, same schedule"
        assert firings(7) != firings(8), "different seed, different schedule"
        assert 0 < sum(firings(7)) < 20

    def test_delay_sleeps_then_proceeds(self):
        slept = []
        inj = FaultInjector().arm("s", "delay", delay_s=0.25)
        inj.fire("s", sleep=slept.append)
        assert slept == [0.25]

    def test_corrupt_flips_payload_byte(self):
        inj = FaultInjector().arm("s", "corrupt", times=1)
        data = b"\x01\x02\x03"
        assert inj.corrupt("s", data) == b"\x01\x02\xfc"
        assert inj.corrupt("s", data) == data, "times=1: second call clean"

    def test_mode_validation(self):
        with pytest.raises(ValueError):
            FaultInjector().arm("s", "explode")


# -- KV slab wire integrity ---------------------------------------------------


class TestSlabWireIntegrity:
    def _slab(self):
        from fusioninfer_tpu.engine.kv_cache import CacheConfig, init_kv_cache
        from fusioninfer_tpu.engine.kv_transfer import extract_slab
        from fusioninfer_tpu.models.config import get_preset

        cache = init_kv_cache(get_preset("qwen3-tiny"),
                              CacheConfig(n_pages=9, page_size=8,
                                          max_pages_per_seq=4))
        return extract_slab(cache, [1, 3], [5, 6, 7], first_token=11,
                            page_size=8)

    def test_crc_roundtrip(self):
        from fusioninfer_tpu.engine.kv_transfer import (
            slab_from_bytes,
            slab_to_bytes,
        )

        frame = slab_to_bytes(self._slab())
        back = slab_from_bytes(frame)
        assert back.prompt_tokens == [5, 6, 7] and back.first_token == 11

    def test_flipped_payload_byte_is_caught(self):
        from fusioninfer_tpu.engine.kv_transfer import (
            KVSlabCorrupt,
            slab_from_bytes,
            slab_to_bytes,
        )

        frame = bytearray(slab_to_bytes(self._slab()))
        frame[-1] ^= 0xFF
        with pytest.raises(KVSlabCorrupt, match="crc32"):
            slab_from_bytes(bytes(frame))

    def test_truncated_frame_is_caught(self):
        from fusioninfer_tpu.engine.kv_transfer import (
            KVSlabCorrupt,
            slab_from_bytes,
            slab_to_bytes,
        )

        frame = slab_to_bytes(self._slab())
        with pytest.raises(KVSlabCorrupt, match="truncated"):
            slab_from_bytes(frame[:-10])


# -- typed transfer errors ----------------------------------------------------


class _CannedHTTP:
    """Tiny real HTTP server answering every POST with one canned
    (status, body) — the prefiller-shaped peer for error-path tests."""

    def __init__(self, status: int, body: bytes):
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self):
                self.rfile.read(int(self.headers.get("Content-Length", 0)))
                self.send_response(outer.status)
                self.send_header("Content-Length", str(len(outer.body)))
                self.end_headers()
                self.wfile.write(outer.body)

            def log_message(self, *args):
                pass

        self.status, self.body = status, body
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.httpd.server_address[1]
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


class TestKVTransferErrors:
    def test_non_200_raises_typed_error_with_context(self):
        from fusioninfer_tpu.engine.kv_transfer import (
            HTTPPullConnector,
            KVTransferError,
        )

        srv = _CannedHTTP(500, b"prefiller exploded")
        try:
            conn = HTTPPullConnector(f"http://127.0.0.1:{srv.port}")
            with pytest.raises(KVTransferError) as ei:
                conn.request_prefill("r1", [1, 2, 3], timeout=5.0)
            assert ei.value.status == 500
            assert "exploded" in ei.value.body
        finally:
            srv.close()

    def test_garbage_200_raises_corrupt(self):
        from fusioninfer_tpu.engine.kv_transfer import (
            HTTPPullConnector,
            KVSlabCorrupt,
        )

        srv = _CannedHTTP(200, b"this is not a slab frame")
        try:
            conn = HTTPPullConnector(f"http://127.0.0.1:{srv.port}")
            with pytest.raises(KVSlabCorrupt):
                conn.request_prefill("r1", [1, 2, 3], timeout=5.0)
        finally:
            srv.close()

    def test_connection_refused_raises_typed_error(self):
        from fusioninfer_tpu.engine.kv_transfer import (
            HTTPPullConnector,
            KVTransferError,
        )

        conn = HTTPPullConnector("http://127.0.0.1:1")
        with pytest.raises(KVTransferError) as ei:
            conn.request_prefill("r1", [1], timeout=2.0)
        assert ei.value.status is None  # transport-level, no HTTP status

    def test_4xx_is_not_retried(self):
        """A 4xx is the prefiller deterministically rejecting THIS
        request — re-pulling it can never succeed, so it must propagate
        on the first attempt instead of burning the backoff budget."""
        from fusioninfer_tpu.engine.kv_transfer import (
            HTTPPullConnector,
            KVTransferError,
        )

        srv = _CannedHTTP(400, b"unknown lora")
        try:
            conn = HTTPPullConnector(
                f"http://127.0.0.1:{srv.port}",
                retry=RetryPolicy(max_attempts=5, base_delay_s=0.0,
                                  jitter="none"),
            )
            attempts = []
            real = conn._pull_once

            def counting_pull(*a):
                attempts.append(1)
                return real(*a)

            conn._pull_once = counting_pull
            with pytest.raises(KVTransferError) as ei:
                conn.request_prefill("r1", [1], timeout=5.0)
            assert ei.value.status == 400
            assert not ei.value.retryable
            assert len(attempts) == 1, "4xx must not be retried"
        finally:
            srv.close()

    def test_retry_policy_heals_transient_failures(self):
        from fusioninfer_tpu.engine.kv_transfer import (
            HTTPPullConnector,
            KVTransferError,
        )

        inj = FaultInjector().arm("kv.pull", "drop", times=2)
        srv = _CannedHTTP(500, b"unused")  # never reached: drops fire first
        try:
            conn = HTTPPullConnector(
                f"http://127.0.0.1:{srv.port}",
                retry=RetryPolicy(max_attempts=3, base_delay_s=0.0,
                                  jitter="none"),
                fault_injector=inj,
            )
            # two drops burn two attempts; the third reaches the server
            # and gets its 500 — typed, not budget-exhausted
            with pytest.raises(RetryBudgetExhausted) as ei:
                conn.request_prefill("r1", [1], timeout=5.0)
            assert isinstance(ei.value.last_error, KVTransferError)
            assert ei.value.last_error.status == 500
            assert inj.fired_count("kv.pull") == 2
        finally:
            srv.close()


# -- chaos: PD transfer over HTTP ---------------------------------------------

CFG_CACHE = dict(n_pages=33, page_size=8, max_pages_per_seq=8)


@pytest.fixture(scope="module")
def pd_rig():
    """Prefiller + fault-injected decoder + monolithic reference server."""
    from fusioninfer_tpu.engine.engine import NativeEngine
    from fusioninfer_tpu.engine.kv_cache import CacheConfig
    from fusioninfer_tpu.engine.server import EngineServer
    from fusioninfer_tpu.models.config import get_preset

    cfg = get_preset("qwen3-tiny")
    injector = FaultInjector(seed=0)

    def engine():
        return NativeEngine(cfg, cache_cfg=CacheConfig(**CFG_CACHE),
                            max_batch_size=2, seed=0)

    prefill = EngineServer(model="qwen3-tiny", host="127.0.0.1", port=0,
                           engine=engine())
    prefill.start()
    decode = EngineServer(
        model="qwen3-tiny", host="127.0.0.1", port=0, engine=engine(),
        prefill_upstream=f"http://127.0.0.1:{prefill.port}",
        kv_retry=RetryPolicy(max_attempts=3, base_delay_s=0.01,
                             max_delay_s=0.05, seed=1),
        kv_fault_injector=injector,
        # Pin the legacy whole-slab pull: this class chaoses the kv.pull
        # sites.  The layer-streamed path has its own chaos coverage
        # (tests/test_kv_fabric.py::TestStreamChaos).
        kv_stream=False,
    )
    decode.start()
    mono = EngineServer(model="qwen3-tiny", host="127.0.0.1", port=0,
                        engine=engine())
    mono.start()
    try:
        yield prefill, decode, mono, injector
    finally:
        injector.disarm()
        prefill.stop()
        decode.stop()
        mono.stop()


def _completion(port: int, prompt: str) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps({"model": "qwen3-tiny", "prompt": prompt,
                         "max_tokens": 6, "temperature": 0.0}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.load(r)


@pytest.mark.chaos
class TestKVTransferChaos:
    """Injected transfer faults must never lose a request: transient ones
    heal through retries, persistent ones degrade to a local re-prefill —
    either way the output is token-identical to a monolithic server."""

    def _assert_identical(self, decode_port, mono_port, prompt):
        pd = _completion(decode_port, prompt)
        mono = _completion(mono_port, prompt)
        assert pd["choices"][0]["text"] == mono["choices"][0]["text"]
        assert pd["usage"] == mono["usage"]
        assert pd["choices"][0]["finish_reason"] == \
            mono["choices"][0]["finish_reason"]

    def test_injected_delay_completes_identically(self, pd_rig):
        prefill, decode, mono, inj = pd_rig
        inj.arm("kv.pull", "delay", delay_s=0.05, times=1)
        try:
            self._assert_identical(decode.port, mono.port, "delay leg")
            assert inj.fired_count("kv.pull") == 1
            assert decode.metrics.kv_transfer_fallbacks == 0
        finally:
            inj.disarm()

    def test_transient_drop_heals_through_retry(self, pd_rig):
        prefill, decode, mono, inj = pd_rig
        inj.arm("kv.pull", "drop", times=2)  # budget is 3 attempts
        try:
            self._assert_identical(decode.port, mono.port, "dropped leg")
            assert inj.fired_count("kv.pull") == 2
            assert decode.metrics.kv_transfer_fallbacks == 0
            # the transfer (not a local prefill) served this request
            assert decode.engine.prompt_tokens_total == 0
        finally:
            inj.disarm()

    def test_corrupt_frame_is_caught_and_repulled(self, pd_rig):
        prefill, decode, mono, inj = pd_rig
        inj.arm("kv.pull.response", "corrupt", times=1)
        try:
            self._assert_identical(decode.port, mono.port, "corrupt leg")
            assert inj.fired_count("kv.pull.response") == 1
            assert decode.metrics.kv_transfer_fallbacks == 0
        finally:
            inj.disarm()

    def test_persistent_drop_falls_back_to_local_prefill(self, pd_rig):
        prefill, decode, mono, inj = pd_rig
        inj.arm("kv.pull", "drop")  # unlimited: every attempt fails
        try:
            before = decode.metrics.kv_transfer_fallbacks
            self._assert_identical(decode.port, mono.port, "fallback leg")
            assert decode.metrics.kv_transfer_fallbacks == before + 1
            # the decoder prefilled locally — slower, but it completed
            assert decode.engine.prompt_tokens_total > 0
        finally:
            inj.disarm()


# -- chaos: router circuit breaking -------------------------------------------

ROUTER_CONFIG = """
apiVersion: inference.networking.x-k8s.io/v1alpha1
kind: EndpointPickerConfig
plugins:
- type: queue-scorer
- type: max-score-picker
schedulingProfiles:
- name: default
  plugins:
  - pluginRef: queue-scorer
    weight: 100
  - pluginRef: max-score-picker
"""


@pytest.mark.chaos
class TestRouterChaos:
    def _picker(self, clock, **health_kw):
        from fusioninfer_tpu.router.picker import (
            Endpoint,
            EndpointHealth,
            EndpointPicker,
        )

        good = Endpoint("good", "http://127.0.0.1:1", {})
        bad = Endpoint("bad", "http://127.0.0.1:2", {})

        def metrics(ep):
            # "bad" advertises the EMPTIEST queue: absent breakers the
            # picker would route there forever
            return {"vllm:num_requests_waiting":
                    0.0 if ep.name == "bad" else 2.0}

        health_kw.setdefault("failure_threshold", 3)
        health_kw.setdefault("recovery_timeout_s", 10.0)
        picker = EndpointPicker(
            ROUTER_CONFIG, lambda: [good, bad], metrics,
            health=EndpointHealth(clock=lambda: clock[0], **health_kw))
        return picker

    def test_failing_endpoint_ejected_then_recovered_half_open(self):
        clock = [0.0]
        picker = self._picker(clock)
        picked = []
        for _ in range(8):
            ep = picker.pick("prompt")
            picked.append(ep.name)
            # the data plane reports: "bad" fails every request it gets
            picker.report_result(ep, ok=(ep.name != "bad"))
        # ejected within the failure threshold, then never routed again
        assert picked[:3] == ["bad", "bad", "bad"]
        assert set(picked[3:]) == {"good"}
        assert picker.health.state("bad") == "open"

        # recovery window elapses: the next pick probes it half-open
        clock[0] = 10.0
        ep = picker.pick("prompt")
        assert ep.name == "bad", "half-open probe must re-admit the endpoint"
        picker.report_result(ep, ok=True)
        assert picker.health.state("bad") == "closed"
        assert picker.pick("prompt").name == "bad"

    def test_failed_probe_reejects_for_a_fresh_window(self):
        clock = [0.0]
        picker = self._picker(clock)
        for _ in range(3):
            picker.report_result("bad", ok=False)
        clock[0] = 10.0
        ep = picker.pick("prompt")
        assert ep.name == "bad"
        picker.report_result(ep, ok=False)  # probe fails
        assert picker.health.state("bad") == "open"
        assert picker.pick("prompt").name == "good"

    def test_all_endpoints_broken_routes_last_resort(self):
        clock = [0.0]
        picker = self._picker(clock)
        for name in ("good", "bad"):
            for _ in range(3):
                picker.report_result(name, ok=False)
        assert picker.pick("prompt") is not None, (
            "total outage must degrade to best-effort routing, not None")

    def test_losing_half_open_candidate_keeps_its_probe(self):
        """A half-open endpoint that LOSES the scoring must not burn its
        probe token: no request carries its outcome, so a consumed probe
        would wedge the breaker half-open forever (ejected with nothing
        left to close or re-open it)."""
        from fusioninfer_tpu.router.picker import (
            Endpoint,
            EndpointHealth,
            EndpointPicker,
        )

        clock = [0.0]
        depth = {"good": 2.0, "bad": 9.0}  # mutable: controls who wins

        def metrics(ep):
            return {"vllm:num_requests_waiting": depth[ep.name]}

        picker = EndpointPicker(
            ROUTER_CONFIG,
            lambda: [Endpoint("good", "http://127.0.0.1:1", {}),
                     Endpoint("bad", "http://127.0.0.1:2", {})],
            metrics,
            health=EndpointHealth(failure_threshold=3,
                                  recovery_timeout_s=10.0,
                                  clock=lambda: clock[0]))
        for _ in range(3):
            picker.report_result("bad", ok=False)
        clock[0] = 10.0  # recovery window elapses: "bad" is half-open
        depth["bad"] = 9.0  # ...but scores worse than "good"
        for _ in range(5):
            assert picker.pick("p").name == "good"
        assert picker.health.state("bad") == "half-open"
        # when it finally wins, the probe is still available and a
        # success recovers the endpoint
        depth["bad"] = 0.0
        ep = picker.pick("p")
        assert ep.name == "bad", "unconsumed probe must still admit"
        picker.report_result(ep, ok=True)
        assert picker.health.state("bad") == "closed"

    def test_raising_scrape_counts_as_breaker_failure(self):
        from fusioninfer_tpu.router.picker import (
            Endpoint,
            EndpointHealth,
            EndpointPicker,
        )

        clock = [0.0]
        inj = FaultInjector().arm("router.metrics.flaky", "error")
        picker = EndpointPicker(
            ROUTER_CONFIG,
            lambda: [Endpoint("flaky", "http://127.0.0.1:2", {}),
                     Endpoint("ok", "http://127.0.0.1:1", {})],
            lambda ep: {"vllm:num_requests_waiting": 1.0},
            health=EndpointHealth(failure_threshold=3,
                                  clock=lambda: clock[0]),
            fault_injector=inj,
        )
        for _ in range(3):
            assert picker.pick("p").name == "ok"
        assert picker.health.state("flaky") == "open"


# -- chaos: operator requeue backoff + Degraded -------------------------------


def _sample_service(name="svc"):
    return {
        "apiVersion": "fusioninfer.io/v1alpha1",
        "kind": "InferenceService",
        "metadata": {"name": name, "namespace": "default", "generation": 1},
        "spec": {
            "roles": [{
                "name": "worker", "componentType": "worker", "replicas": 1,
                "template": {"spec": {"containers": [
                    {"name": "engine", "image": "img"}
                ]}},
            }]
        },
    }


def _wait_for(pred, timeout=10.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


@pytest.mark.chaos
class TestOperatorChaos:
    def _degraded(self, fake, name="svc"):
        svc = fake.get_or_none("InferenceService", "default", name) or {}
        for c in (svc.get("status") or {}).get("conditions") or []:
            if c.get("type") == "Degraded":
                return c
        return None

    def test_persistent_reconcile_error_backs_off_and_degrades(self):
        from fusioninfer_tpu.operator import FakeK8s, Manager

        fake = FakeK8s()
        fake.create(_sample_service())
        inj = FaultInjector(seed=3).arm(
            "operator.reconcile.InferenceService", "error")
        policy = RetryPolicy(max_attempts=4, base_delay_s=0.02,
                             max_delay_s=0.3, multiplier=2.0, jitter="none")
        mgr = Manager(fake, namespace="default", probe_port=0, metrics_port=0,
                      requeue_backoff=policy, fault_injector=inj)
        mgr.start()
        try:
            key = ("InferenceService", "default", "svc")
            assert _wait_for(
                lambda: (self._degraded(fake) or {}).get("status") == "True"
            ), "retry budget exhaustion must surface a Degraded condition"
            delays = list(mgr.requeue_delays[key])
            # exponential, not a hot loop: 0.02 → 0.04 → 0.08 → ceiling
            assert delays[:3] == [
                pytest.approx(0.02), pytest.approx(0.04), pytest.approx(0.08)]
            assert all(d == pytest.approx(0.3) for d in delays[3:])
            assert self._degraded(fake)["reason"] == "RetryBudgetExhausted"
            # nothing was reconciled while the injector held the fault
            assert fake.get_or_none(
                "LeaderWorkerSet", "default", "svc-worker-0") is None

            # heal the fault: the ceiling-cadence retry converges and
            # the Degraded condition clears
            inj.disarm()
            assert _wait_for(
                lambda: fake.get_or_none(
                    "LeaderWorkerSet", "default", "svc-worker-0") is not None,
                timeout=15.0,
            ), "post-recovery requeue must reconcile the service"
            assert _wait_for(
                lambda: (self._degraded(fake) or {}).get("status") == "False",
                timeout=15.0,
            ), "a successful reconcile must clear Degraded"
        finally:
            mgr.stop()

    def test_degraded_mark_retries_after_failed_status_write(self):
        """The FIRST Degraded status write racing an apiserver outage
        must not lose the condition forever — the next ceiling requeue
        tries again."""
        from fusioninfer_tpu.operator import FakeK8s, Manager

        fake = FakeK8s()
        fake.create(_sample_service())
        inj = FaultInjector(seed=5).arm(
            "operator.reconcile.InferenceService", "error")
        policy = RetryPolicy(max_attempts=2, base_delay_s=0.02,
                             max_delay_s=0.05, jitter="none")
        mgr = Manager(fake, namespace="default", probe_port=0, metrics_port=0,
                      requeue_backoff=policy, fault_injector=inj)
        real_mark = mgr.reconciler.mark_degraded
        write_attempts = []

        def flaky_mark(ns, name, message):
            write_attempts.append(message)
            if len(write_attempts) == 1:
                raise OSError("apiserver connection reset")
            return real_mark(ns, name, message)

        mgr.reconciler.mark_degraded = flaky_mark
        mgr.start()
        try:
            assert _wait_for(
                lambda: (self._degraded(fake) or {}).get("status") == "True"
            ), "a failed status write must be retried, not dropped"
            assert len(write_attempts) >= 2
        finally:
            mgr.stop()


# -- chaos: server deadlines + watchdog ---------------------------------------


class _HungEngine:
    """Engine double whose decode loop never produces output — the shape
    of a wedged device step, without the device."""

    class _Cfg:
        vocab_size = 512

    cfg = _Cfg()
    guided_enabled = True  # skips the guided-vocab bootstrap

    def __init__(self):
        self.cancelled = []

    def add_request(self, request):
        pass

    def cancel(self, request_id):
        self.cancelled.append(request_id)

    def has_work(self):
        return False

    def step(self):
        return []

    def fail_all(self, reason):
        return []


@pytest.mark.chaos
class TestDeadlineWatchdog:
    def _server(self, **kw):
        from fusioninfer_tpu.engine.server import EngineServer
        from fusioninfer_tpu.engine.tokenizer import ByteTokenizer

        engine = _HungEngine()
        server = EngineServer(model="stub", host="127.0.0.1", port=0,
                              engine=engine, tokenizer=ByteTokenizer(),
                              watchdog_interval_s=0.02, **kw)
        server.start()
        return server, engine

    def test_request_deadline_aborts_hung_sequence(self):
        from fusioninfer_tpu.engine.sampler import SamplingParams

        server, engine = self._server()
        try:
            chan = server.submit([1, 2, 3], SamplingParams(max_tokens=4),
                                 deadline_s=0.15)
            out, _ = chan.q.get(timeout=5.0)
            assert out.finished
            assert out.finish_reason == "error:deadline exceeded"
            assert engine.cancelled == [out.request_id], (
                "the watchdog must also cancel engine-side")
            assert server.metrics.watchdog_aborts == 1
        finally:
            server.stop()

    def test_server_default_deadline_applies(self):
        from fusioninfer_tpu.engine.sampler import SamplingParams

        server, engine = self._server(default_deadline_s=0.15)
        try:
            chan = server.submit([1], SamplingParams(max_tokens=4))
            out, _ = chan.q.get(timeout=5.0)
            assert out.finished
            assert out.finish_reason == "error:deadline exceeded"
        finally:
            server.stop()

    def test_stall_watchdog_aborts_without_deadline(self):
        from fusioninfer_tpu.engine.sampler import SamplingParams

        server, engine = self._server(watchdog_stall_s=0.15)
        try:
            chan = server.submit([1], SamplingParams(max_tokens=4))
            out, _ = chan.q.get(timeout=5.0)
            assert out.finished
            assert out.finish_reason.startswith("error:watchdog")
            assert engine.cancelled == [out.request_id]
        finally:
            server.stop()

    def test_deadline_over_http_returns_error_finish(self):
        server, engine = self._server()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/v1/completions",
                data=json.dumps({"prompt": "hi", "max_tokens": 4,
                                 "deadline_s": 0.15}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=10) as r:
                body = json.load(r)
            assert body["choices"][0]["finish_reason"] == \
                "error:deadline exceeded"
        finally:
            server.stop()

    def test_finished_request_is_not_watchdog_aborted(self):
        """A finished request whose channel is still registered (slow
        SSE client) must not be counted as stalled or expired."""
        import queue as queue_mod

        from fusioninfer_tpu.engine.sampler import SamplingParams

        server, engine = self._server(watchdog_stall_s=0.1)
        try:
            chan = server.submit([1], SamplingParams(max_tokens=4),
                                 deadline_s=0.1)
            with server._lock:
                rid = next(iter(server._req_meta))
                # what the engine loop records on the final token
                server._req_meta[rid]["finished"] = True
            time.sleep(0.4)  # several scans past deadline AND stall limit
            assert server.metrics.watchdog_aborts == 0
            assert engine.cancelled == []
            with pytest.raises(queue_mod.Empty):
                chan.q.get_nowait()
        finally:
            server.stop()

    def test_invalid_deadline_is_a_400(self):
        server, engine = self._server()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/v1/completions",
                data=json.dumps({"prompt": "hi", "deadline_s": -1}).encode(),
                headers={"Content-Type": "application/json"},
            )
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 400
        finally:
            server.stop()
