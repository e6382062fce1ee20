"""Token-budgeted unified scheduling (docs/design/scheduler.md).

The invariants under test, in acceptance-criteria order:

* a mid-prefill long prompt never blocks decode for more than one
  budgeted chunk (stall-free batching);
* a decode burst never delays an admission the host can foresee, and
  dispatch-ahead runs whenever nothing is admissible — a full batch
  with a standing queue included (admission-aware spans);
* priority / preemption ordering is identical to the unbudgeted engine
  on the same schedule (the budget decides WHEN prefill tokens are
  spent, never who wins pages or slots);
* chunk size adapts: grows to the full budget when the batch is idle,
  shrinks under decode load;
* the legacy ``prefill_chunk_size`` / ``prefill_chunks_per_step`` pair
  seeds the budget (compat aliases);
* token identity with the monolithic engine, with bursts and
  dispatch-ahead pipelining composed in.
"""

import numpy as np
import pytest

from fusioninfer_tpu.engine.engine import NativeEngine, Request
from fusioninfer_tpu.engine.kv_cache import CacheConfig
from fusioninfer_tpu.engine.sampler import SamplingParams
from fusioninfer_tpu.engine.sched import TokenBudget, derive_token_budget
from fusioninfer_tpu.models.config import get_preset

CFG = get_preset("qwen3-tiny")


def _cache_cfg() -> CacheConfig:
    return CacheConfig(n_pages=65, page_size=16, max_pages_per_seq=16)


def _run_all(engine, requests, max_steps=400):
    for r in requests:
        engine.add_request(r)
    tokens: dict[str, list[int]] = {r.request_id: [] for r in requests}
    for _ in range(max_steps):
        if not engine.has_work():
            break
        for out in engine.step():
            assert not (out.finish_reason or "").startswith("error"), out
            tokens.setdefault(out.request_id, []).append(out.token)
    assert not engine.has_work(), "engine did not drain"
    return tokens


class TestLedger:
    def test_compat_aliases_seed_budget(self):
        engine = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=2,
                              prefill_chunk_size=16,
                              prefill_chunks_per_step=3)
        assert engine.token_budget == 48
        assert engine.prefill_chunk == 16

    def test_explicit_budget_sets_chunk_threshold(self):
        engine = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=2,
                              token_budget=32)
        assert engine.token_budget == 32
        assert engine.prefill_chunk == 32

    def test_no_budget_by_default(self):
        engine = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=2)
        assert engine.token_budget is None
        assert engine.prefill_chunk is None

    def test_budget_validation(self):
        with pytest.raises(ValueError):
            NativeEngine(CFG, cache_cfg=_cache_cfg(), token_budget=0)
        engine = NativeEngine(CFG, cache_cfg=_cache_cfg())
        with pytest.raises(ValueError):
            engine.set_token_budget(0)

    def test_ledger_math(self):
        b = TokenBudget(100)
        assert b.begin_step(decode_charge=30) == 70
        b.charge_decode(30)
        b.charge_prefill(60, chunks=2)
        assert b.utilization() == pytest.approx(0.9)
        snap = b.snapshot()
        assert snap["token_budget"] == 100
        assert snap["decode_tokens"] == 30
        assert snap["prefill_tokens"] == 60
        assert snap["chunks"] == 2

    def test_unbudgeted_ledger_is_unbounded(self):
        b = TokenBudget(None)
        assert b.begin_step(decode_charge=10**6) >= 10**6
        assert b.utilization() == 0.0

    def test_derive_token_budget(self):
        # 1 ms/token at a 50 ms target -> 50 tokens/step
        assert derive_token_budget(0.001, target_step_s=0.05) == 50
        assert derive_token_budget(1.0) == 32  # floor
        assert derive_token_budget(1e-9) == 4096  # cap
        assert derive_token_budget(0.0) == 4096


class TestTokenIdentity:
    @pytest.mark.parametrize("budget", [16, 48])
    def test_same_tokens_as_monolithic(self, budget):
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, CFG.vocab_size, n).tolist()
                   for n in (100, 9, 37)]

        def reqs():
            return [Request(f"r{i}", list(p),
                            SamplingParams(max_tokens=8, temperature=0.8,
                                           seed=100 + i))
                    for i, p in enumerate(prompts)]

        base = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=4)
        budgeted = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=4,
                                token_budget=budget)
        assert _run_all(base, reqs()) == _run_all(budgeted, reqs())

    def test_budget_with_bursts_and_pipelining(self):
        rng = np.random.default_rng(9)
        prompts = [rng.integers(1, CFG.vocab_size, n).tolist()
                   for n in (80, 12)]

        def reqs():
            return [Request(f"b{i}", list(p),
                            SamplingParams(max_tokens=12, temperature=0.0))
                    for i, p in enumerate(prompts)]

        base = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=4)
        combo = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=4,
                             token_budget=24, decode_burst_steps=4,
                             pipeline_bursts=True)
        assert _run_all(base, reqs()) == _run_all(combo, reqs())


class TestStallFreeDecode:
    def test_decode_never_stalls_longer_than_one_chunk(self):
        """While a long prompt chunks, the running stream receives a
        token EVERY step — the budgeted chunk is the worst-case decode
        gap, never the whole prefill."""
        engine = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=2,
                              token_budget=16)
        engine.add_request(Request("stream", [1, 2, 3],
                                   SamplingParams(max_tokens=40,
                                                  temperature=0.0)))
        engine.step()  # stream running
        engine.add_request(Request(
            "long", list(range(1, 129)),  # 128 tokens >> budget
            SamplingParams(max_tokens=2, temperature=0.0)))
        while engine.num_prefilling or engine.waiting:
            outs = engine.step()
            if engine.num_prefilling:
                # the invariant: a budgeted chunk step still decodes
                assert any(o.request_id == "stream" for o in outs), \
                    "decode stalled during a budgeted chunk"

    def test_chunk_grows_to_full_budget_when_idle(self):
        engine = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=2,
                              token_budget=32)
        engine.add_request(Request("solo", list(range(1, 97)),  # 96 tokens
                                   SamplingParams(max_tokens=1,
                                                  temperature=0.0)))
        firsts = []
        for step in range(10):
            for o in engine.step():
                if o.is_first_token:
                    firsts.append(step)
            if not engine.has_work():
                break
        # idle batch -> 32-token chunks -> 3 steps, first token on step 2
        assert firsts == [2]

    def test_chunk_shrinks_under_decode_load(self):
        engine = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=3,
                              token_budget=16)
        for i in range(2):
            engine.add_request(Request(f"d{i}", [1 + i, 2, 3],
                                       SamplingParams(max_tokens=30,
                                                      temperature=0.0)))
        engine.step()  # both running
        engine.add_request(Request("long", list(range(1, 100)),
                                   SamplingParams(max_tokens=1,
                                                  temperature=0.0)))
        engine.step()  # admission -> prefilling + first chunk
        assert engine.num_prefilling == 1
        pos0 = engine.prefilling[0].pos
        # 2 decode tokens charged first: the chunk is 16 - 2 = 14
        assert 0 < pos0 <= 14
        engine.step()
        if engine.num_prefilling:
            assert engine.prefilling[0].pos - pos0 <= 14

    def test_short_prompt_defers_when_budget_spent(self):
        """Even a short prompt routes through the chunk queue once the
        step's remainder is spent — admission work is bounded by the
        budget, and the deferral is counted."""
        engine = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=4,
                              token_budget=16)
        rng = np.random.default_rng(3)
        for i, n in enumerate((14, 14)):  # 2nd exceeds the remainder
            engine.add_request(Request(
                f"s{i}", rng.integers(1, CFG.vocab_size, n).tolist(),
                SamplingParams(max_tokens=1, temperature=0.0)))
        engine.step()
        assert engine.sched.admission_deferred_total >= 1
        _run = []
        for _ in range(20):
            if not engine.has_work():
                break
            _run += engine.step()
        assert not engine.has_work()


class TestAdmissionAwareBurst:
    CACHE = CacheConfig(n_pages=64, page_size=8, max_pages_per_seq=8)

    def _full_slot(self, **over):
        """One slot, taken by a runner mid-stream with the queue dry."""
        kw = dict(cache_cfg=self.CACHE, max_batch_size=1,
                  decode_burst_steps=8)
        kw.update(over)
        engine = NativeEngine(CFG, **kw)
        engine.add_request(Request("run", [2, 4, 6],
                                   SamplingParams(max_tokens=60,
                                                  temperature=0.0),
                                   priority=5))
        engine.step()
        return engine

    def test_equal_priority_waiter_pipelines_and_is_not_delayed(self):
        """A full batch with an equal-priority waiter has nothing
        admissible: dispatch-ahead keeps running behind the queue, spans
        fuse while no finish is in sight, and the waiter still gets in
        on the step right after the runner's last token."""
        engine = self._full_slot()
        engine.add_request(Request("wait", [9, 8],
                                   SamplingParams(max_tokens=4,
                                                  temperature=0.0),
                                   priority=5))
        assert not engine._admission_pending()
        ahead0 = engine.sched.dispatch_ahead_total
        finished_at = first_at = None
        spans = []
        for step in range(200):
            if not engine.has_work():
                break
            per_step = 0
            for o in engine.step():
                assert not (o.finish_reason or "").startswith("error"), o
                per_step += o.request_id == "run"
                if o.request_id == "run" and o.finished:
                    finished_at = step
                    ahead_while_blocked = (engine.sched.dispatch_ahead_total
                                           - ahead0)
                if o.request_id == "wait" and o.is_first_token:
                    first_at = step
            if finished_at in (None, step):
                spans.append(per_step)
        assert not engine.has_work()
        assert ahead_while_blocked > 0, "a standing queue stopped the chain"
        assert 8 in spans, "no span fused while no finish was in sight"
        # the foreseeable finish: once fewer than 8 tokens of budget are
        # left, they go out one a step
        assert spans[-4:] == [8, 1, 1, 1], spans
        assert first_at == finished_at + 1
        assert engine.preemptions_total == 0

    @pytest.mark.parametrize("tiers", [False, True])
    def test_more_urgent_waiter_clamps_and_preempts_next_step(self, tiers):
        over = dict(token_budget=16) if tiers else {}
        engine = self._full_slot(**over)
        if tiers:
            engine.set_slo_tiers({0: 0.7, 5: 0.3})
        engine.step()  # a span-8 chain is running
        assert engine._inflight is not None
        engine.add_request(Request("urgent", [9, 8],
                                   SamplingParams(max_tokens=4,
                                                  temperature=0.0),
                                   priority=0))
        assert engine._admission_pending()
        outs = engine.step()
        assert engine.preemptions_total == 1
        assert any(o.request_id == "urgent" and o.is_first_token
                   for o in outs)
        # the in-flight burst carried only the victim: nothing of it is
        # emitted after the preemption
        assert not any(o.request_id == "run" for o in outs)
        assert len(_run_all(engine, [])["urgent"]) == 3

    def test_free_slot_waiter_clamps_as_before(self):
        """A waiter that finds a slot but no pages stays admissible work
        (pages are not priced by the predicate): every step clamps to
        one token and nothing is dispatched ahead."""
        engine = self._full_slot(
            max_batch_size=2,
            cache_cfg=CacheConfig(n_pages=9, page_size=8,
                                  max_pages_per_seq=8))
        engine.add_request(Request("wait", list(range(1, 57)),
                                   SamplingParams(max_tokens=4,
                                                  temperature=0.0),
                                   priority=5))
        engine.step()  # lands a burst dispatched while the queue was dry
        ahead0 = engine.sched.dispatch_ahead_total
        clamped0 = engine.sched.burst_clamped_total
        blocked_steps = 0
        while engine.num_waiting and blocked_steps < 100:
            assert engine._admission_pending()
            outs = engine.step()
            if engine.num_waiting:
                blocked_steps += 1
                assert sum(o.request_id == "run" for o in outs) <= 1
                assert engine.sched.dispatch_ahead_total == ahead0
        assert blocked_steps > 0, "the waiter was never blocked on pages"
        assert engine.sched.burst_clamped_total > clamped0
        _run_all(engine, [])

    @pytest.mark.parametrize("source", [
        "waiting_prefilled", "prefilling", "_cancelled", "_pd_pending",
        "_embed_pending", "_slab_q", "_embed_q"])
    def test_other_admission_sources_count_on_full_slots(self, source):
        """Only the wait queue is judged by admissibility: PD arrivals,
        mid-chunk prefills, cancels and the slab / embedding queues
        clamp and stop the chain by being there."""
        engine = self._full_slot()
        assert not engine._admission_pending()
        held = getattr(engine, source)
        if hasattr(held, "put"):
            held.put(object())
        elif hasattr(held, "append"):
            held.append(object())
        elif isinstance(held, dict):
            held["x"] = None
        else:
            held.add("x")
        assert engine._admission_pending()
        assert engine._burst_span() == 1
        snapshot = dict(engine.running)
        assert not engine._pipeline_ready(snapshot, 1)

    @pytest.mark.parametrize("kind", ["multihost", "speculative", "burst1",
                                      "pipeline_off"])
    def test_paths_that_never_pipeline_stay_unpipelined(self, kind):
        """Multi-host lockstep, speculative decoding, --decode-burst 1
        and pipelining switched off keep stepping unpipelined behind a
        standing queue; only the predicate's inputs changed."""
        over = {"speculative": dict(speculative_k=2),
                "burst1": dict(decode_burst_steps=1),
                "pipeline_off": dict(pipeline_bursts=False)}.get(kind, {})
        engine = self._full_slot(**over)
        engine.add_request(Request("wait", [9, 8],
                                   SamplingParams(max_tokens=4,
                                                  temperature=0.0),
                                   priority=5))
        assert not engine._admission_pending()
        if kind == "multihost":
            engine._mh = object()  # pose as a multi-process engine
            assert not engine._pipeline_ready(dict(engine.running), 1)
            # replicated state only: nothing leader-side was read
            assert not engine._admission_pending()
            return
        _run_all(engine, [])
        assert engine.sched.dispatch_ahead_total == 0

    def test_spans_recorded_in_histogram(self):
        engine = NativeEngine(CFG, cache_cfg=self.CACHE, max_batch_size=2,
                              decode_burst_steps=4)
        _run_all(engine, [Request("h", [2, 4],
                                  SamplingParams(max_tokens=16,
                                                 temperature=0.0))])
        hist = engine.sched.burst_span_steps
        assert 4 in hist and hist[4] >= 1
        snap = engine.sched.snapshot()
        assert snap["burst_span_steps"].get("4", 0) >= 1

    def test_dispatch_ahead_counted(self):
        engine = NativeEngine(CFG, cache_cfg=self.CACHE, max_batch_size=2,
                              decode_burst_steps=4, pipeline_bursts=True)
        _run_all(engine, [Request("p", [2, 4, 6],
                                  SamplingParams(max_tokens=40,
                                                 temperature=0.0))])
        assert engine.sched.dispatch_ahead_total > 0

    def test_span1_fused_path_identity(self):
        """Burst engines use the fused decode+sample path at span 1 too
        (dispatch-ahead under admission pressure): streams must match
        the classic engine exactly when spans are forced to 1 by a
        perpetually short remaining budget."""
        def reqs():
            return [Request("x", [2, 4, 6], SamplingParams(
                max_tokens=3, temperature=0.8, seed=11))]  # < span 8

        classic = NativeEngine(CFG, cache_cfg=self.CACHE, max_batch_size=2)
        burst = NativeEngine(CFG, cache_cfg=self.CACHE, max_batch_size=2,
                             decode_burst_steps=8)
        assert _run_all(classic, reqs()) == _run_all(burst, reqs())
        # the whole run decayed to span-1 dispatches (span keys are
        # pre-seeded at 0 for race-free /metrics iteration — check
        # counts, not key presence)
        assert {s for s, c in burst.sched.burst_span_steps.items()
                if c} == {1}


class TestPreemptionOrderingUnchanged:
    def test_priority_preemption_identical_to_unbudgeted(self):
        """Same arrival schedule, same priorities: the budgeted engine
        must evict the same victim and produce the same streams as the
        unbudgeted chunked engine (the existing preemption fixtures pin
        the unbudgeted behavior; this pins budget == alias seeding)."""
        cache = CacheConfig(n_pages=9, page_size=16, max_pages_per_seq=8)

        def run(**kw):
            engine = NativeEngine(CFG, cache_cfg=cache, max_batch_size=2,
                                  enable_prefix_caching=False, **kw)
            engine.add_request(Request(
                "old", list(range(1, 16)),
                SamplingParams(max_tokens=20, temperature=0.0)))
            engine.step()
            engine.add_request(Request(
                "long", list(range(1, 112)),
                SamplingParams(max_tokens=2, temperature=0.0)))
            results: dict[str, list] = {"old": [], "long": []}
            for _ in range(80):
                if not engine.has_work():
                    break
                for o in engine.step():
                    results[o.request_id].append(
                        (o.token, o.finished, o.finish_reason))
            assert not engine.has_work()
            return results, engine.preemptions_total

        legacy, legacy_preempt = run(prefill_chunk_size=16)
        budgeted, budget_preempt = run(token_budget=16)
        assert legacy_preempt >= 1 and budget_preempt >= 1
        # the urgent (older) stream is identical under both schedulers
        assert budgeted["old"] == legacy["old"]
        assert budgeted["long"][-1][2] in ("length", "stop")


class TestMetricsExposition:
    def test_scheduler_families_rendered(self):
        from fusioninfer_tpu.engine.metrics import EngineMetrics

        engine = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=2,
                              token_budget=16, decode_burst_steps=4)
        _run_all(engine, [Request("m", list(range(1, 40)),
                                  SamplingParams(max_tokens=8,
                                                 temperature=0.0))])
        text = EngineMetrics("m").render(engine)
        for family in (
            "fusioninfer:sched_token_budget",
            "fusioninfer:sched_budget_utilization",
            "fusioninfer:sched_decode_tokens_total",
            "fusioninfer:sched_prefill_tokens_total",
            "fusioninfer:sched_chunks_total",
            "fusioninfer:sched_admission_deferred_total",
            "fusioninfer:sched_burst_clamped_total",
            "fusioninfer:sched_dispatch_ahead_total",
            "fusioninfer:sched_burst_span_steps_total",
        ):
            assert f"# TYPE {family} " in text, family
            assert f"# HELP {family} " in text, family
        assert "fusioninfer:sched_token_budget{" in text

    def test_stub_engines_skip_scheduler_families(self):
        from fusioninfer_tpu.engine.metrics import EngineMetrics

        class Stub:
            num_running = num_waiting = num_prefilling = 0
            prompt_tokens_total = generation_tokens_total = 0
            spec_proposed_total = spec_accepted_total = 0
            preemptions_total = finished_total = 0
            errors_total = cancelled_total = 0

            def kv_cache_usage(self):
                return 0.0

            def prefix_cache_hit_rate(self):
                return 0.0

        text = EngineMetrics("m").render(Stub())
        assert "sched_token_budget" not in text


class TestCalibration:
    def test_calibrate_installs_measured_budget(self):
        engine = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=2)
        free0 = engine.alloc.free_pages
        budget = engine.calibrate_token_budget()
        assert 32 <= budget <= 4096
        assert engine.token_budget == budget
        assert engine.prefill_chunk == budget
        assert engine.alloc.free_pages == free0  # probe pages released
        # the engine still serves correctly afterwards
        _run_all(engine, [Request("c", [1, 2, 3],
                                  SamplingParams(max_tokens=2,
                                                 temperature=0.0))])


def test_warm_chunk_forwards_dispatches_every_flat_token_bucket_once():
    """Start-up dispatches the chunk forward at every flat-token bucket a
    budgeted chunk can have (16 .. pow2(budget)), on scratch pages it
    gives back: afterwards no bucket is new to the jit cache, so no
    stream stalls on a first dispatch inside the serving window."""
    from fusioninfer_tpu.engine.kv_cache import CacheConfig
    from fusioninfer_tpu.engine.model_runner import fused_step
    from fusioninfer_tpu.models.config import get_preset

    eng = NativeEngine(get_preset("qwen3-tiny"), cache_cfg=CacheConfig(
        n_pages=17, page_size=32, max_pages_per_seq=8), max_batch_size=2,
        token_budget=96)
    before = fused_step._cache_size()
    assert eng.warm_chunk_forwards() == 4  # 16, 32, 64 and 96 -> T = 128
    assert fused_step._cache_size() == before + 4
    assert eng.alloc.used_pages == 0 and not eng.has_work()
    eng.warm_chunk_forwards()
    assert fused_step._cache_size() == before + 4
    no_budget = NativeEngine(get_preset("qwen3-tiny"), cache_cfg=CacheConfig(
        n_pages=17, page_size=32, max_pages_per_seq=8), max_batch_size=2)
    assert no_budget.warm_chunk_forwards() == 0
