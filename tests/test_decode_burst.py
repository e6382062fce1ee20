"""Multi-step decode burst: one jitted scan decodes+samples N tokens per
host round trip (``model_runner.decode_burst``).  The contract under test
is bit-identity: a burst engine must emit exactly the token streams the
classic per-token engine emits — greedy and sampled, penalized and
min-tokens-suppressed — because the scan body inlines the very same
sampler math with the same key derivation.

Reference capability: vLLM multi-step scheduling / TPU server step
batching (the reference delegates serving to vLLM,
/root/reference/docs/fusioninfer/docs/design/core-design.md:29); here it is
the lever that amortizes the host<->device round trip that dominates
per-token latency on remote-attached TPU chips.
"""

import pytest

from fusioninfer_tpu.engine.engine import NativeEngine, Request
from fusioninfer_tpu.engine.kv_cache import CacheConfig
from fusioninfer_tpu.engine.sampler import SamplingParams
from fusioninfer_tpu.models.config import get_preset

CFG = get_preset("qwen3-tiny")
CACHE = CacheConfig(n_pages=64, page_size=8, max_pages_per_seq=8)


def make_engine(burst=1, cache=CACHE, cfg=CFG, **over):
    kw = dict(cfg=cfg, cache_cfg=cache, max_batch_size=4, seed=0,
              decode_burst_steps=burst)
    kw.update(over)
    return NativeEngine(**kw)


def run_to_completion(engine, max_steps=300, before_step=None):
    outputs, finished = {}, {}
    for step in range(max_steps):
        if not engine.has_work():
            break
        if before_step is not None:
            before_step(engine, step)
        for out in engine.step():
            outputs.setdefault(out.request_id, []).append(out.token)
            if out.finished:
                finished[out.request_id] = out.finish_reason
    return outputs, finished


def collect(burst, requests, cache=CACHE, before_step=None, **over):
    engine = make_engine(burst, cache=cache, **over)
    for r in requests:
        engine.add_request(r)
    outs, fins = run_to_completion(engine, before_step=before_step)
    assert engine.num_running == 0
    return outs, fins


class TestBurstIdentity:
    def test_greedy_identity_mid_burst_finish(self):
        """max_tokens=10 with span 4: the last burst overruns by 2 and
        the overrun must be discarded, not emitted."""
        reqs = lambda: [Request("g", [2, 4, 6, 8],
                                SamplingParams(temperature=0.0, max_tokens=10))]
        base, fin_base = collect(1, reqs())
        burst, fin_burst = collect(4, reqs())
        assert burst == base
        assert fin_burst == fin_base == {"g": "length"}
        assert len(burst["g"]) == 10

    def test_sampled_identity_with_penalties(self):
        """Seeded sampling + presence/frequency/repetition penalties and
        min_tokens: the scan's penalty ordering and key derivation must
        reproduce the sequential stream exactly."""
        def reqs():
            return [
                Request("s0", [1, 3, 5], SamplingParams(
                    temperature=0.9, top_k=20, top_p=0.95, seed=7,
                    presence_penalty=0.4, frequency_penalty=0.2,
                    repetition_penalty=1.2, max_tokens=12)),
                Request("s1", [9, 9, 2], SamplingParams(
                    temperature=0.7, min_p=0.02, seed=11,
                    min_tokens=6, stop_token_ids=[0],
                    max_tokens=12)),
            ]
        base, fb = collect(1, reqs())
        burst, fbu = collect(4, reqs())
        assert burst == base
        assert fbu == fb

    def test_batched_identity(self):
        reqs = lambda: [
            Request(f"r{i}", [2 + i, 4, 6],
                    SamplingParams(temperature=0.0, max_tokens=8))
            for i in range(4)
        ]
        base, _ = collect(1, reqs())
        burst, fins = collect(4, reqs())
        assert burst == base
        assert all(r == "length" for r in fins.values())

    def test_stop_token_mid_burst_truncates(self):
        """A stop token landing mid-burst must end the stream there —
        trailing burst tokens are garbage and never reach the client."""
        probe, _ = collect(1, [Request("p", [2, 4, 6], SamplingParams(
            temperature=0.0, max_tokens=8))])
        stop_tok = probe["p"][3]  # force a stop on the 4th token
        reqs = lambda: [Request("x", [2, 4, 6], SamplingParams(
            temperature=0.0, max_tokens=8, stop_token_ids=[stop_tok]))]
        base, fb = collect(1, reqs())
        burst, fbu = collect(8, reqs())
        assert burst == base
        assert fbu == fb == {"x": "stop"}
        assert burst["x"][-1] == stop_tok

    def test_burst_with_prefix_caching_and_page_growth(self):
        """Bursts cross page boundaries (page_size=8, span=8): the
        pre-extension must cover the whole burst, including for the
        prefix-caching allocator."""
        reqs = lambda: [Request("long", list(range(2, 12)), SamplingParams(
            temperature=0.0, max_tokens=24))]
        base, _ = collect(1, reqs(), enable_prefix_caching=True)
        burst, fins = collect(8, reqs(), enable_prefix_caching=True)
        assert burst == base
        assert fins == {"long": "length"}


class TestBurstFallbacks:
    def test_logprobs_rows_fall_back(self):
        """A logprobs request needs host-side extraction per token: it
        single-steps (and, alone in the batch, the span decision drops
        to 1) while logprobs still arrive."""
        engine = make_engine(8)
        engine.add_request(Request("lp", [2, 4], SamplingParams(
            temperature=0.0, max_tokens=5, logprobs=3)))
        assert engine._burst_span() == 1 or not engine.running  # pre-admission
        outs, fins = run_to_completion(engine)
        assert fins == {"lp": "length"}
        assert len(outs["lp"]) == 5

    def test_mixed_batch_fallback_is_row_granular(self):
        """One logprobs request must NOT collapse the batch to classic
        stepping: greedy neighbours keep bursting (multiple tokens per
        engine step) and stay token-identical, while the logprobs row
        advances one audited token per step."""
        greedy_reqs = lambda: [
            Request(f"g{i}", [2 + i, 4, 6],
                    SamplingParams(temperature=0.0, max_tokens=16))
            for i in range(2)
        ]
        base, _ = collect(1, greedy_reqs())

        engine = make_engine(8)
        for r in greedy_reqs():
            engine.add_request(r)
        engine.add_request(Request("lp", [9, 8, 7], SamplingParams(
            temperature=0.0, max_tokens=16, logprobs=2)))
        outs: dict[str, list] = {}
        lp_vals: list = []
        burst_steps_seen = 0
        for _ in range(300):
            if not engine.has_work():
                break
            per_step: dict[str, int] = {}
            for o in engine.step():
                outs.setdefault(o.request_id, []).append(o.token)
                per_step[o.request_id] = per_step.get(o.request_id, 0) + 1
                if o.request_id == "lp" and o.logprob is not None:
                    lp_vals.append(o.logprob)
            if any(v > 2 for k, v in per_step.items() if k.startswith("g")):
                burst_steps_seen += 1
            # the slow row advances one decode token per step (two on
            # its admission step: prefill first-token + same-step decode)
            lp_first = "lp" not in outs or len(outs["lp"]) == per_step.get("lp", 0)
            assert per_step.get("lp", 0) <= (2 if lp_first else 1)
        assert burst_steps_seen > 0, "greedy rows never bursted"
        assert {k: v for k, v in outs.items() if k.startswith("g")} == base
        assert len(outs["lp"]) == 16 and len(lp_vals) == 16

    def test_memory_pressure_decays_span(self):
        """A pool too small for burst headroom must decay to classic
        stepping rather than preempt — and still finish everyone."""
        tiny = CacheConfig(n_pages=10, page_size=8, max_pages_per_seq=8)
        reqs = lambda: [
            Request(f"m{i}", [3 + i, 5], SamplingParams(
                temperature=0.0, max_tokens=20))
            for i in range(3)
        ]
        base, fb = collect(1, reqs(), cache=tiny)
        burst, fbu = collect(8, reqs(), cache=tiny)
        assert burst == base
        assert fbu == fb

    def test_span_stays_one_when_remaining_short(self):
        """All rows within k of their budget: bursting would only waste
        steps, so the span decision must return 1."""
        engine = make_engine(8)
        engine.add_request(Request("short", [2, 4], SamplingParams(
            temperature=0.0, max_tokens=3)))
        outs, fins = run_to_completion(engine)
        assert len(outs["short"]) == 3
        assert fins == {"short": "length"}

    def test_burst_rejects_bad_config(self):
        with pytest.raises(ValueError):
            make_engine(0)


class TestBurstPipelining:
    """Double-buffered bursts: the successor burst dispatches from the
    device-side control carry BEFORE the current burst's blocking fetch.
    Chaining must break on any scheduler change (finish, cancel,
    admission, preemption), and every emitted stream must be identical
    to the unpipelined engine's."""

    def test_steady_state_identity(self):
        reqs = lambda: [
            Request(f"r{i}", [2 + i, 4, 6],
                    SamplingParams(temperature=0.0, max_tokens=40))
            for i in range(3)
        ]
        base, fb = collect(4, reqs(), pipeline_bursts=False)
        piped, fp = collect(4, reqs(), pipeline_bursts=True)
        assert piped == base
        assert fp == fb

    def test_pipeline_engages(self):
        """In steady state the inflight handoff must actually happen —
        observable as a pending _inflight between steps."""
        engine = make_engine(4, pipeline_bursts=True)
        engine.add_request(Request("r", [2, 4, 6], SamplingParams(
            temperature=0.0, max_tokens=56)))
        saw_inflight = False
        for _ in range(40):
            if not engine.has_work():
                break
            engine.step()
            saw_inflight = saw_inflight or engine._inflight is not None
        assert saw_inflight, "pipeline never engaged in steady state"
        assert engine._inflight is None or not engine.has_work()

    def test_stop_mid_stream_identity(self):
        probe, _ = collect(1, [Request("p", [2, 4, 6], SamplingParams(
            temperature=0.0, max_tokens=30))])
        stop_tok = probe["p"][17]
        reqs = lambda: [Request("x", [2, 4, 6], SamplingParams(
            temperature=0.0, max_tokens=30, stop_token_ids=[stop_tok]))]
        base, fb = collect(4, reqs(), pipeline_bursts=False)
        piped, fp = collect(4, reqs(), pipeline_bursts=True)
        assert piped == base
        assert fp == fb

    def test_staggered_admission_breaks_chain_correctly(self):
        """A request arriving mid-pipeline must admit promptly and both
        streams must match the unpipelined engine run with the same
        arrival schedule (same step index)."""
        def run(pipelined: bool):
            engine = make_engine(4, pipeline_bursts=pipelined)
            engine.add_request(Request("a", [2, 4, 6], SamplingParams(
                temperature=0.0, max_tokens=32)))
            outs: dict[str, list] = {}
            steps = 0
            while engine.has_work() and steps < 200:
                if steps == 5:
                    engine.add_request(Request("b", [9, 8, 7],
                                               SamplingParams(
                                                   temperature=0.0,
                                                   max_tokens=24)))
                for o in engine.step():
                    outs.setdefault(o.request_id, []).append(o.token)
                steps += 1
            assert engine.num_running == 0
            return outs

        base = run(False)
        piped = run(True)
        # rows are independent: each request's stream must be identical
        # regardless of pipelining-induced scheduling differences
        assert piped["a"] == base["a"]
        assert piped["b"] == base["b"]

    @pytest.mark.parametrize("case", [
        "length", "sampled", "eos_mid_chain", "cancel_mid_chain",
        "logprobs_row", "sliding_window"])
    def test_standing_queue_identity(self, case):
        """Two slots, six equal-priority requests: the queue stands on
        full slots for most of the run and nothing in it is admissible,
        so the chain runs behind it.  Every stream must match the
        unpipelined engine's, whichever way a row leaves mid-chain."""
        lengths = (21, 34, 27, 18, 25, 30)
        temp = 0.8 if case == "sampled" else 0.0
        stop = []
        if case == "eos_mid_chain":
            probe, _ = collect(1, [Request("p", [3, 4, 6], SamplingParams(
                temperature=0.0, max_tokens=34))])
            stop = [probe["p"][13]]
        cfg = get_preset("mistral-tiny") if case == "sliding_window" else CFG

        def reqs():
            return [Request(f"q{i}", [2 + i, 4, 6], SamplingParams(
                temperature=temp, seed=7 + i, max_tokens=n,
                stop_token_ids=stop if i == 1 else [],
                logprobs=1 if case == "logprobs_row" and i == 0 else None))
                for i, n in enumerate(lengths)]

        ahead_behind_queue = []

        def before_step(engine, step):
            if case == "cancel_mid_chain" and step == 7:
                engine.cancel("q1")
            if engine.num_waiting and not engine._admission_pending():
                ahead_behind_queue.append(engine.sched.dispatch_ahead_total)

        kw = dict(max_batch_size=2, cfg=cfg, before_step=before_step)
        base, fb = collect(4, reqs(), pipeline_bursts=False, **kw)
        assert not any(ahead_behind_queue)
        piped, fp = collect(4, reqs(), pipeline_bursts=True, **kw)
        assert ahead_behind_queue[-1] > 0, "the queue stopped the chain"
        if case == "cancel_mid_chain":
            assert len(piped.pop("q1")) < lengths[1]
            base.pop("q1")
        if case == "eos_mid_chain":
            assert fp["q1"] == "stop" and len(piped["q1"]) < lengths[1]
        assert piped == base
        assert fp == fb

    def test_cancel_mid_flight(self):
        engine = make_engine(4, pipeline_bursts=True)
        engine.add_request(Request("keep", [2, 4, 6], SamplingParams(
            temperature=0.0, max_tokens=32)))
        engine.add_request(Request("gone", [9, 8, 7], SamplingParams(
            temperature=0.0, max_tokens=32)))
        outs: dict[str, list] = {}
        steps = 0
        while engine.has_work() and steps < 200:
            if steps == 4:
                engine.cancel("gone")
            for o in engine.step():
                outs.setdefault(o.request_id, []).append(o.token)
            steps += 1
        assert engine.num_running == 0
        base, _ = collect(4, [Request("keep", [2, 4, 6], SamplingParams(
            temperature=0.0, max_tokens=32))], pipeline_bursts=False)
        assert outs["keep"] == base["keep"]
        assert len(outs.get("gone", [])) < 32

    def test_memory_pressure_skips_pipelining(self):
        tiny = CacheConfig(n_pages=12, page_size=8, max_pages_per_seq=8)
        reqs = lambda: [
            Request(f"m{i}", [3 + i, 5], SamplingParams(
                temperature=0.0, max_tokens=24))
            for i in range(2)
        ]
        base, fb = collect(4, reqs(), cache=tiny, pipeline_bursts=False)
        piped, fp = collect(4, reqs(), cache=tiny, pipeline_bursts=True)
        assert piped == base
        assert fp == fb

    def test_sliding_window_pipelined_identity(self):
        """Windowed models reclaim below-window pages inside the chained
        fast path (_extend_for_successor trims) — streams must match the
        unpipelined engine and the pool must fully drain."""
        mistral = get_preset("mistral-tiny")  # sliding_window=24
        reqs = lambda: [Request("w", [2, 4, 6], SamplingParams(
            temperature=0.0, max_tokens=48))]
        base, fb = collect(4, reqs(), cache=CACHE, cfg=mistral,
                           pipeline_bursts=False)
        piped, fp = collect(4, reqs(), cache=CACHE, cfg=mistral,
                            pipeline_bursts=True)
        assert piped == base
        assert fp == fb

    def test_kv_released_after_pipelined_run(self):
        engine = make_engine(8, pipeline_bursts=True)
        for i in range(3):
            engine.add_request(Request(f"r{i}", [2 + i, 4], SamplingParams(
                temperature=0.0, max_tokens=30)))
        run_to_completion(engine)
        assert engine.num_running == 0
        assert engine.kv_cache_usage() == 0.0


class TestActivationTransactionality:
    def test_finish_failure_releases_slot_and_pages(self):
        """A failure past the slot claim (inside _emit) must roll the
        slot and running entry back before the group path releases the
        request's pages — otherwise the released pages would be handed
        to a later admission while a zombie running entry still decodes
        into them, and the slot would leak forever."""
        engine = make_engine(1)
        orig_emit = engine._emit
        boom = {"armed": True}

        def flaky(state, token, **kw):
            if boom["armed"] and state.request.request_id == "bad":
                boom["armed"] = False
                raise RuntimeError("injected emit failure")
            return orig_emit(state, token, **kw)

        engine._emit = flaky
        free0 = engine.alloc.free_pages
        engine.add_request(Request("bad", [1, 2, 3], SamplingParams(
            temperature=0.0, max_tokens=4)))
        engine.add_request(Request("ok", [4, 5, 6], SamplingParams(
            temperature=0.0, max_tokens=4)))
        outs, fins = run_to_completion(engine)
        assert fins["bad"].startswith("error")
        assert fins["ok"] == "length" and len(outs["ok"]) == 4
        assert engine.alloc.free_pages == free0
        # no slot leak: a full batch still admits and completes
        for i in range(4):
            engine.add_request(Request(f"r{i}", [7 + i], SamplingParams(
                temperature=0.0, max_tokens=2)))
        _, fins2 = run_to_completion(engine)
        assert len(fins2) == 4
        assert all(r == "length" for r in fins2.values())


class TestBurstComposition:
    """Bursting must compose with the rest of the serving matrix: LoRA
    adapter rows (adapter_ids ride the packed ctl) and int8 KV pages
    (quantized scatter/gather inside the scan) — token-identical to the
    classic engine in every combination, pipelined included."""

    def test_burst_lora_identity(self):
        import dataclasses

        from tests.conftest import nonzero_adapter

        cfg = dataclasses.replace(CFG, dtype="float32",
                                  attn_impl="reference")
        adapter = nonzero_adapter(cfg)

        def reqs():
            return [
                Request("base", [2, 4, 6], SamplingParams(
                    temperature=0.0, max_tokens=12)),
                Request("tuned", [2, 4, 6], SamplingParams(
                    temperature=0.0, max_tokens=12), lora="ft"),
            ]

        base, _ = collect(1, reqs(), cfg=cfg,
                          lora_adapters={"ft": adapter})
        burst, fins = collect(8, reqs(), cfg=cfg,
                              lora_adapters={"ft": adapter})
        assert burst == base
        assert set(fins) == {"base", "tuned"}
        # the adapter must actually change the tuned stream
        assert burst["base"] != burst["tuned"]

    def test_burst_int8_kv_identity(self):
        int8 = CacheConfig(n_pages=64, page_size=8, max_pages_per_seq=8,
                           kv_dtype="int8")
        reqs = lambda: [Request("q", [2, 4, 6, 8], SamplingParams(
            temperature=0.0, max_tokens=20))]
        base, fb = collect(1, reqs(), cache=int8)
        burst, fbu = collect(8, reqs(), cache=int8)
        assert burst == base
        assert fbu == fb


class TestAdmissionFastPath:
    """The fused first-token call (sampler.sample_first) must be
    bit-identical to the legacy ~14-op admission sequence.  A zero
    logit_bias entry is mathematically a no-op but routes a request
    down the legacy path — giving both paths on identical inputs."""

    @pytest.mark.parametrize("params", [
        dict(temperature=0.0, max_tokens=6),
        dict(temperature=0.8, seed=13, max_tokens=6),
        dict(temperature=0.8, seed=13, top_k=12, top_p=0.9, max_tokens=6),
        dict(temperature=0.7, seed=3, presence_penalty=0.5,
             frequency_penalty=0.3, repetition_penalty=1.3, max_tokens=6),
        dict(temperature=0.0, min_tokens=4, stop_token_ids=[2, 9],
             max_tokens=6),
    ])
    def test_fused_matches_legacy(self, params):
        fused, ff = collect(1, [Request("r", [4, 2, 7],
                                        SamplingParams(**params))])
        legacy, lf = collect(1, [Request("r", [4, 2, 7], SamplingParams(
            logit_bias=[(1, 0.0)], **params))])
        assert fused == legacy
        assert ff == lf
