"""Fused mixed-batch steps (docs/design/scheduler.md, engine.md).

One weight pass per engine step: when a step has BOTH decode work and
budgeted prefill-chunk work, the engine packs them into a single
``model_runner.fused_step`` forward instead of dispatching a chunk
forward and a decode forward back to back.  The invariants under test:

* output streams are BIT-IDENTICAL with the fused path on vs off —
  greedy and seeded-sampled, including prefix-cache hits,
  preemption/resume, LoRA adapter rows, speculative-decode rows, and
  mid-chunk cancellation;
* the ``weight_passes_per_step`` ledger shows ≈ 1 pass/step under mixed
  load on the fused path vs ≥ 2 on the split path, and decode-only
  stepping is untouched;
* burst engines (``decode_burst_steps > 1``) take the fused path on a
  step with both row kinds when no burst is in flight and the live batch
  samples from candidates; a burst in flight, or a row that needs host
  work per token, keeps the split path; such an engine builds, warms and
  dispatches ONE chunk-carrying program per flat-token bucket;
* there a mixed step enqueues its decode tail before any first-token
  fetch and, when the next step's rows are known, its successor mixed
  step (decode inputs carried on the device) before its own fetch: the
  streams and the chunk sizes are those of an engine that never chains,
  and the chain breaks on an admission, a completing prompt, a row's
  last token and a cancel;
* the new ``/metrics`` families render with HELP/TYPE lines;
* the packing helper (`engine/fused.py`) lays rows out slot-aligned.
"""

import dataclasses

import numpy as np
import pytest

from fusioninfer_tpu.engine.engine import NativeEngine, Request
from fusioninfer_tpu.engine.fused import (
    RaggedBatch,
    pack_ragged_batch,
    pow2_rows,
)
from fusioninfer_tpu.engine.kv_cache import CacheConfig, auto_cache_config
from fusioninfer_tpu.engine.sampler import SamplingParams
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
            tokens[out.request_id].append(out.token)
    assert not engine.has_work(), "engine did not drain"
    return tokens


def _mixed_reqs(seed=5, max_tokens=8, prompt_len=100, top_k=0,
                vocab=CFG.vocab_size):
    """A decode stream + a long chunking prompt + a short prompt — the
    mixed-load shape the fused step exists for.  ``top_k`` makes the
    seeded row a candidate sampler and puts a second one beside the
    greedy stream, so sampled rows are live WHILE the long prompt
    chunks (a burst engine fuses only candidate-sampling batches)."""
    rng = np.random.default_rng(seed)
    reqs = [
        Request("stream", [1, 2, 3],
                SamplingParams(max_tokens=20, temperature=0.0)),
        Request("long", rng.integers(1, vocab, prompt_len).tolist(),
                SamplingParams(max_tokens=max_tokens, temperature=0.8,
                               seed=77, top_k=top_k)),
        Request("short", rng.integers(1, vocab, 9).tolist(),
                SamplingParams(max_tokens=4, temperature=0.0)),
    ]
    if top_k:
        reqs.insert(1, Request(
            "tk", [3, 2, 1], SamplingParams(max_tokens=20, temperature=0.7,
                                            seed=5, top_k=top_k)))
    return reqs


# the two decode loops: classic per-token stepping and burst engines
# (whose mixed step replaces "chunk forward, then a span-1 decode_burst")
BURSTS = pytest.mark.parametrize("burst", [1, 8])
# the two A/B pairs: the fused step off / on, and on a burst engine the
# mixed steps' dispatch-ahead chain off / on (``pipeline_bursts``)
PAIRS = {"fused": ("fused_step", {}),
         "chained": ("pipeline_bursts", {"fused_step": True})}


def _chunk_log(engine) -> list:
    """Every chunk sizing the engine makes, in order (one per batched
    chunk dispatch, fresh or dispatched ahead)."""
    log, sizes = [], engine._chunk_sizes

    def logged(take, budget):
        chunks = sizes(take, budget)
        log.append(list(chunks))
        return chunks
    engine._chunk_sizes = logged
    return log


class TestPacking:
    def test_pow2_rows(self):
        assert [pow2_rows(n) for n in (1, 2, 3, 8, 9)] == [1, 2, 4, 8, 16]

    def test_slot_aligned_flat_layout(self):
        window = np.array([[7], [0], [9], [0]], np.int32)  # B=4, W=1
        counts_w = np.array([1, 0, 1, 0], np.int32)
        positions = np.array([5, 0, 12, 0], np.int32)
        tables = np.arange(8, dtype=np.int32).reshape(4, 2)
        adapters = np.array([0, 0, 1, 0], np.int32)
        entries = [([3, 4, 5], 32, np.array([6, 7], np.int32), 2)]
        p = pack_ragged_batch(window, counts_w, positions, tables, adapters,
                              entries, trash_page=99)
        assert isinstance(p, RaggedBatch)
        # ONE flat token axis — 5 real tokens pad to the 16-token
        # signature floor, never to a [rows, C] rectangle
        assert p.tokens.shape == (16,)
        assert p.q_begins.shape == (8,)  # pow2(4 + 1) rows
        # live decode tokens then chunk tokens, no inter-row rectangle
        assert list(p.tokens[:5]) == [7, 9, 3, 4, 5]
        assert p.packed_tokens == 5  # 2 live decode + 3 chunk tokens

    def test_flat_segments_and_sel(self):
        window = np.array([[7], [0], [9], [0]], np.int32)
        counts_w = np.array([1, 0, 1, 0], np.int32)
        positions = np.array([5, 0, 12, 0], np.int32)
        tables = np.arange(8, dtype=np.int32).reshape(4, 2)
        adapters = np.array([0, 0, 1, 0], np.int32)
        entries = [([3, 4, 5], 32, np.array([6, 7], np.int32), 2)]
        p = pack_ragged_batch(window, counts_w, positions, tables, adapters,
                              entries, trash_page=99)
        # decode rows are the batch SLOTS (logits row i == slot i);
        # dead slots hold zero-length segments
        assert list(p.q_lens[:5]) == [1, 0, 1, 0, 3]
        assert list(p.q_begins[:5]) == [0, 1, 1, 2, 2]
        assert p.tokens[0] == 7 and p.tokens[1] == 9
        assert list(p.tokens[2:5]) == [3, 4, 5]
        assert p.row_starts[0] == 5 and p.row_starts[2] == 12
        # sel covers ONLY the decode slots, pointing at their own
        # FLAT segments
        assert p.sel.shape == (4, 1)
        assert p.sel[0, 0] == 0 and p.sel[2, 0] == 1
        # chunk row rides row B at its own start; its last real token
        # projects through the separate shape-stable chunk_sel group
        assert p.row_starts[4] == 32
        assert p.chunk_sel.shape == (1,) and p.chunk_sel[0] == 4
        assert p.adapter_ids[4] == 2
        # padding rows are inert: zero-length segments, trash tables
        assert p.q_lens[5:].sum() == 0 and (p.page_tables[5:] == 99).all()
        assert p.packed_tokens == 5

    def test_spec_window_sel(self):
        window = np.array([[7, 8, 9], [0, 0, 0]], np.int32)  # W=3
        p = pack_ragged_batch(window, np.array([3, 0], np.int32),
                              np.array([4, 0], np.int32),
                              np.full((2, 2), 0, np.int32),
                              np.zeros(2, np.int32),
                              [([1], 0, np.zeros(2, np.int32), 0)],
                              trash_page=9)
        # decode row 0's spec window is its own flat segment [0, 3)
        assert list(p.sel[0]) == [0, 1, 2]
        assert list(p.tokens[:4]) == [7, 8, 9, 1]
        # 1-token chunk row: its last (only) real flat position
        assert list(p.chunk_sel) == [3]

    def test_chunks_only_packs_without_decode_rows(self):
        """B == 0: the chunk-advance / batched-suffix shape — chunk rows
        are rows 0.. and the flat axis carries only their tokens."""
        p = pack_ragged_batch(
            np.zeros((0, 1), np.int32), np.zeros((0,), np.int32),
            np.zeros((0,), np.int32), np.zeros((0, 2), np.int32),
            np.zeros((0,), np.int32),
            [([5, 6], 0, np.array([1, 2], np.int32), 0),
             ([7], 10, np.array([3, 4], np.int32), 1)],
            trash_page=9)
        assert list(p.q_lens[:2]) == [2, 1]
        assert list(p.tokens[:3]) == [5, 6, 7]
        assert p.sel.shape == (0, 1)
        assert list(p.chunk_sel) == [1, 2]
        assert p.adapter_ids[1] == 1


class TestEquivalence:
    """Bit-identity: the fused step must be invisible in the streams."""

    def _ab(self, reqs_fn, cache_cfg=None, cfg=CFG, pair="fused",
            **engine_kw):
        """Streams of the pair's engine with the switch off (``split``)
        and on (``fused``); on the "chained" pair also the chunk sizes
        of every step."""
        flag, kw = PAIRS[pair]
        kw = dict(cache_cfg=cache_cfg or _cache_cfg(), max_batch_size=4,
                  token_budget=16, **kw, **engine_kw)
        split = NativeEngine(cfg, **{flag: False}, **kw)
        fused = NativeEngine(cfg, **{flag: True}, **kw)
        chunks = _chunk_log(split), _chunk_log(fused)
        a = _run_all(split, reqs_fn())
        b = _run_all(fused, reqs_fn())
        assert fused.sched.fused_steps_total > 0, \
            "fused path never engaged — the A/B proves nothing"
        if pair == "fused":
            assert split.sched.fused_steps_total == 0
        else:
            assert fused.sched.mixed_dispatch_ahead_total > 0, \
                "the chain never engaged — the A/B proves nothing"
            assert split.sched.mixed_dispatch_ahead_total == 0
            assert chunks[0] == chunks[1]
        assert a == b
        return split, fused

    @pytest.mark.parametrize("burst,pair",
                             [(1, "fused"), (8, "fused"), (8, "chained")])
    @pytest.mark.parametrize("top_k", [0, 8])
    def test_mixed_load_greedy_and_seeded_sampled(self, burst, pair, top_k):
        """Greedy rows beside a seeded sampler: plain (``top_k`` 0: on a
        burst engine the batch keeps the split path once that row is
        live, and the chained steps carry greedy rows alone) and top-k
        (candidate draws ride the mixed step)."""
        self._ab(lambda: _mixed_reqs(top_k=top_k), pair=pair,
                 decode_burst_steps=burst)

    @BURSTS
    def test_quantized_kv_int8(self, burst):
        """int8 KV pages (per-token scales folded at read time) must be
        bit-identical fused vs split too — the scales ride the same
        ragged descriptors as the pages, and quantization amplifies any
        low-bit forward divergence into whole int8 buckets (this A/B
        caught both the scale-in-dot rewrite and the solo-suffix
        rectangle path)."""
        self._ab(lambda: _mixed_reqs(prompt_len=72, top_k=8),
                 cache_cfg=CacheConfig(n_pages=65, page_size=16,
                                       max_pages_per_seq=16,
                                       kv_dtype="int8"),
                 decode_burst_steps=burst)

    @pytest.mark.parametrize("pair", sorted(PAIRS))
    @pytest.mark.parametrize("preset",
                             ["qwen3-tiny", "deepseek-v2-tiny",
                              "longcat-flash-tiny", "smallthinker-tiny"])
    def test_burst_engine_across_architectures(self, preset, pair):
        """The burst engine's mixed step, and its chain, over one pool,
        a latent pool and an expert layer (LongCat: two cache layers a
        layer, identity experts) and a cache kept by layer kind
        (SmallThinker: window-kind pages covered and trimmed as chunks
        and chained decode rows advance): streams identical to chunk
        forward + decode_burst, and to the engine that never chains."""
        cfg = dataclasses.replace(get_preset(preset), dtype="float32",
                                  attn_impl="reference")
        cache_cfg = (auto_cache_config(cfg, page_size=16, max_model_len=128,
                                       max_batch_size=4, step_span=16)
                     if cfg.sliding_window else None)
        _, fused = self._ab(
            lambda: _mixed_reqs(top_k=8, vocab=cfg.vocab_size),
            cache_cfg=cache_cfg, cfg=cfg, pair=pair, decode_burst_steps=8)
        assert fused.runtime_info()["kv_layout"] == (
            "latent" if cfg.is_mla else "heads")

    def test_logprobs_and_bias_rows_in_the_mix(self):
        """Tail-path rows (logprobs, logit_bias) share the fused decode
        logits; their streams and the batch's must not move."""
        long = np.random.default_rng(11).integers(
            1, CFG.vocab_size, 90).tolist()

        def reqs():
            return [
                Request("lp", [4, 5, 6],
                        SamplingParams(max_tokens=12, temperature=0.0,
                                       logprobs=2)),
                Request("bias", [6, 5, 4],
                        SamplingParams(max_tokens=12, temperature=0.0,
                                       logit_bias=((7, 3.0),))),
                Request("long", list(long),
                        SamplingParams(max_tokens=3, temperature=0.0)),
            ]

        self._ab(reqs)

    def test_prefix_cache_hit_suffix_chunks(self):
        """A long cache-hit suffix chunks from its reused start position
        — the fused chunk row must start mid-sequence (over pages a
        prior request wrote)."""
        rng = np.random.default_rng(3)
        shared = rng.integers(1, CFG.vocab_size, 64).tolist()
        tail = rng.integers(1, CFG.vocab_size, 60).tolist()

        def run(fused_on):
            engine = NativeEngine(CFG, cache_cfg=_cache_cfg(),
                                  max_batch_size=4, token_budget=16,
                                  fused_step=fused_on)
            # warm the cache to completion first, so the long suffix
            # below is a genuine page-aligned prefix hit
            toks = dict(_run_all(engine, [Request(
                "warm", shared + [11],
                SamplingParams(max_tokens=2, temperature=0.0))]))
            engine.add_request(Request(
                "stream", [9, 8, 7],
                SamplingParams(max_tokens=24, temperature=0.0)))
            engine.add_request(Request(
                "hit", shared + tail,
                SamplingParams(max_tokens=4, temperature=0.0)))
            toks.update({"stream": [], "hit": []})
            for _ in range(200):
                if not engine.has_work():
                    break
                for o in engine.step():
                    assert not (o.finish_reason or "").startswith("error"), o
                    toks[o.request_id].append(o.token)
            assert not engine.has_work()
            return toks, engine

        a, split = run(False)
        b, fused = run(True)
        assert fused.sched.fused_steps_total > 0
        assert a == b
        assert fused.prefix_cache_hit_rate() > 0
        assert split.prefix_cache_hit_rate() > 0

    @BURSTS
    def test_preemption_resume(self, burst):
        """Preempted-and-resumed sequences (the prefix-cache resume
        path: the full prompt+generated prefix re-prefills) stream
        identically fused vs split."""
        cache = CacheConfig(n_pages=9, page_size=16, max_pages_per_seq=8)

        def run(fused_on):
            engine = NativeEngine(CFG, cache_cfg=cache, max_batch_size=2,
                                  enable_prefix_caching=False,
                                  token_budget=16, fused_step=fused_on,
                                  decode_burst_steps=burst)
            # sized so that a row is preempted AND a mixed step runs on
            # both decode loops (a burst engine pre-extends a span's
            # pages, so it meets the pool's edge at another step)
            engine.add_request(Request(
                "old", list(range(1, 16)),
                SamplingParams(max_tokens=40, temperature=0.0)))
            engine.step()
            engine.add_request(Request(
                "long", list(range(1, 91)),
                SamplingParams(max_tokens=12, temperature=0.0)))
            results: dict[str, list] = {"old": [], "long": []}
            for _ in range(300):
                if not engine.has_work():
                    break
                for o in engine.step():
                    results[o.request_id].append(
                        (o.token, o.finished, o.finish_reason))
            assert not engine.has_work()
            return results, engine

        a, ea = run(False)
        b, eb = run(True)
        assert ea.preemptions_total >= 1 and eb.preemptions_total >= 1
        assert eb.sched.fused_steps_total > 0
        assert a == b

    def test_lora_adapter_rows(self):
        import jax

        from fusioninfer_tpu.models.lora import init_adapter

        adapters = {"a1": init_adapter(CFG, 4, jax.random.key(3))}
        long = np.random.default_rng(2).integers(
            1, CFG.vocab_size, 70).tolist()

        def reqs():
            return [
                Request("base", [1, 2, 3],
                        SamplingParams(max_tokens=12, temperature=0.0)),
                Request("lor", list(long),
                        SamplingParams(max_tokens=4, temperature=0.0),
                        lora="a1"),
            ]

        self._ab(reqs, lora_adapters=adapters)

    def test_spec_decode_rows(self):
        """Speculative rows keep their verify windows inside the fused
        forward (decode rows carry count = 1 + drafts); greedy streams
        stay bit-identical."""
        long = np.random.default_rng(5).integers(
            1, CFG.vocab_size, 90).tolist()

        def reqs():
            return [
                Request("rep", [5, 6, 7, 5, 6, 7, 5, 6],
                        SamplingParams(max_tokens=16, temperature=0.0)),
                Request("long", list(long),
                        SamplingParams(max_tokens=4, temperature=0.0)),
            ]

        split, fused = self._ab(reqs, speculative_k=2)
        assert fused.spec_proposed_total > 0

    @BURSTS
    def test_mid_chunk_cancellation(self, burst):
        """Cancelling a mid-chunk prompt between fused steps releases
        its pages and leaves the surviving stream bit-identical."""
        def run(fused_on):
            engine = NativeEngine(CFG, cache_cfg=_cache_cfg(),
                                  max_batch_size=4, token_budget=16,
                                  fused_step=fused_on,
                                  decode_burst_steps=burst)
            engine.add_request(Request(
                "stream", [1, 2, 3],
                SamplingParams(max_tokens=20, temperature=0.0)))
            engine.step()
            engine.add_request(Request(
                "long", list(range(1, 120)),
                SamplingParams(max_tokens=4, temperature=0.0)))
            engine.step()
            engine.step()
            assert engine.num_prefilling == 1  # mid-chunk
            engine.cancel("long")
            toks = []
            for _ in range(100):
                if not engine.has_work():
                    break
                for o in engine.step():
                    assert not (o.finish_reason or "").startswith("error"), o
                    if o.request_id == "stream":
                        toks.append(o.token)
            assert not engine.has_work()
            return toks, engine

        a, ea = run(False)
        b, eb = run(True)
        assert a == b
        assert eb.sched.fused_steps_total > 0
        assert eb.cancelled_total == 1
        # every page returned (one reserved trash page stays allocator-held)
        assert eb.alloc.free_pages == ea.alloc.free_pages


class TestRaggedIsTheOnlyLayout:
    """There is NO path back to the padded ``[rows, C]`` rectangle: the
    packer module exports only the flat layout, the model path's sources
    never name the retired packer, and the decode / suffix / verify
    kernels, their sharded wrappers and the two forwards that called
    them are gone from every module that held them."""

    def test_padded_rectangle_packer_is_gone(self):
        import fusioninfer_tpu.engine.fused as fused

        assert not hasattr(fused, "pack_mixed_batch")
        assert not hasattr(fused, "FusedBatch")

    def test_model_path_sources_never_name_the_rectangle(self):
        import inspect

        import fusioninfer_tpu.engine.engine as eng
        import fusioninfer_tpu.engine.model_runner as mr

        for mod in (eng, mr):
            assert "pack_mixed_batch" not in inspect.getsource(mod)
        # three layer-scan forwards, and the thin jit of the burst's step
        for gone in ("prefill_suffix", "verify_step",
                     "_window_forward_impl", "_refuse_latent"):
            assert not hasattr(mr, gone), gone
        for kept in ("prefill", "decode_step", "decode_burst", "fused_step"):
            assert hasattr(mr, kept), kept

    def test_legacy_kernels_are_gone(self):
        """One paged-attention family: the ragged wrappers are the only
        paged kernels ``ops`` holds, ``ragged_paged_attention_tp`` the
        only sharded one."""
        import fusioninfer_tpu.ops as ops
        import fusioninfer_tpu.ops.paged_attention as pa
        import fusioninfer_tpu.ops.sharded as sharded

        for kind in ("decode", "prefill", "verify"):
            name = f"paged_{kind}_attention"
            assert not hasattr(pa, name), name
            assert not hasattr(ops, name), name
            assert not hasattr(sharded, name + "_tp"), name
        assert not hasattr(pa, "coalesce_fits_vmem")
        assert [n for n in dir(sharded) if n.endswith("_attention_tp")] == [
            "flash_attention_tp", "ragged_paged_attention_tp"]


class TestWeightPassLedger:
    def test_mixed_load_one_pass_per_fused_step(self):
        """During the fused regime every step with both row kinds is ONE
        weight pass; the split engine pays ≥ 2 on those same steps."""
        split = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=4,
                             token_budget=16, fused_step=False)
        fused = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=4,
                             token_budget=16, fused_step=True)
        _run_all(split, _mixed_reqs())
        _run_all(fused, _mixed_reqs())
        assert fused.sched.fused_steps_total > 0
        assert (fused.sched.weight_passes_total
                < split.sched.weight_passes_total)
        # the fused engine's whole run sits near one pass per step; the
        # split engine pays the extra chunk forwards
        assert fused.sched.weight_passes_per_step() < \
            split.sched.weight_passes_per_step()
        assert fused.sched.weight_passes_per_step() < 1.5
        snap = fused.sched.snapshot()
        assert snap["fused_steps"] == fused.sched.fused_steps_total
        assert snap["weight_passes"] == fused.sched.weight_passes_total
        assert snap["weight_passes_per_step"] > 0
        assert snap["fused_packed_tokens_sum"] > 0

    def test_decode_only_is_one_pass_per_step_and_untouched(self):
        """No prefill work → the fused path never engages and decode
        stepping is exactly one weight pass per step."""
        engine = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=2,
                              token_budget=16, fused_step=True)
        _run_all(engine, [Request("d", [1, 2, 3],
                                  SamplingParams(max_tokens=10,
                                                 temperature=0.0))])
        assert engine.sched.fused_steps_total == 0
        # admission step pays the prefill pass; every other step is 1
        assert engine.sched.weight_passes_total <= engine.sched.steps_total + 1

    def _burst_engine(self, **kw):
        return NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=4,
                            token_budget=16, decode_burst_steps=8,
                            fused_step=True, **kw)

    @pytest.mark.parametrize("chained", [False, True])
    def test_burst_engine_fuses_a_mixed_step_into_one_pass(self, chained):
        """A burst engine's step with both row kinds and nothing in
        flight is ONE weight pass (no chunk forward + span-1 burst), and
        a step that never asked for a burst clamps none.  Chained
        (``pipeline_bursts``), a step may also enqueue its successor
        mixed step: one more pass, and a fused one too."""
        engine = self._burst_engine(pipeline_bursts=chained)
        for r in _mixed_reqs(top_k=8):
            engine.add_request(r)
        sched, mixed = engine.sched, 0
        while engine.has_work():
            before = (sched.fused_steps_total, sched.weight_passes_total,
                      sched.burst_clamped_total, sched.decode_tokens_total)
            admitting = engine.num_waiting > 0  # whole-prompt prefills
            engine.step()
            fused = sched.fused_steps_total - before[0]
            if fused and not admitting:
                mixed += 1
                assert sched.weight_passes_total == before[1] + fused
                if chained:
                    assert fused <= 2
                else:
                    assert fused == 1 and not engine.forward_in_flight()
                assert sched.burst_clamped_total == before[2]
                assert sched.decode_tokens_total > before[3]
        assert mixed > 0 and sched.fused_steps_total >= mixed
        assert (sched.mixed_dispatch_ahead_total > 0) == chained
        # decode-only stretches still burst at the full span
        assert sched.burst_span_steps[8] > 0

    def test_burst_in_flight_is_consumed_split(self):
        """A chunk that arrives while a dispatched-ahead burst is in
        flight rides the split path that step (the burst's tokens are
        already being computed; the chunk forward queues behind it); the
        next step, with nothing in flight, fuses."""
        engine = self._burst_engine()
        engine.add_request(Request(
            "stream", [1, 2, 3], SamplingParams(max_tokens=60,
                                                temperature=0.0)))
        for _ in range(3):
            engine.step()
        assert engine.forward_in_flight()
        engine.add_request(Request(
            "long", list(range(1, 100)),
            SamplingParams(max_tokens=2, temperature=0.0)))
        passes = engine.sched.weight_passes_total
        engine.step()
        assert engine.num_prefilling == 1
        assert engine.sched.fused_steps_total == 0
        # the chunk forward alone: the in-flight burst was charged when
        # it was dispatched, and no successor may dispatch beside a chunk
        assert engine.sched.weight_passes_total == passes + 1
        assert not engine.forward_in_flight()
        engine.step()
        # one fused step, and the successor it may enqueue
        assert engine.sched.fused_steps_total == (
            1 + engine.sched.mixed_dispatch_ahead_total)

    @pytest.mark.parametrize("kind", ["logprobs", "logit_bias", "guided",
                                      "min_p"])
    def test_host_work_rows_keep_the_split_path(self, kind):
        """One row that needs the full distribution (or host work) per
        token keeps the whole batch on the split path, so a burst
        engine never needs a [B, W, V] mixed program."""
        from fusioninfer_tpu.engine.guided import build_token_byte_table
        from fusioninfer_tpu.engine.tokenizer import ByteTokenizer

        params = {
            "logprobs": dict(temperature=0.0, logprobs=2),
            "logit_bias": dict(temperature=0.0, logit_bias=((7, 3.0),)),
            "guided": dict(temperature=0.0, guided_json=True),
            "min_p": dict(temperature=0.8, seed=3, top_k=8, min_p=0.05),
        }[kind]
        engine = self._burst_engine(token_byte_table=build_token_byte_table(
            ByteTokenizer(), CFG.vocab_size))
        reqs = [
            Request("special", [4, 5, 6],
                    SamplingParams(max_tokens=40, **params)),
            Request("plain", [6, 5, 4],
                    SamplingParams(max_tokens=40, temperature=0.0)),
            Request("long", list(range(1, 100)),
                    SamplingParams(max_tokens=2, temperature=0.0)),
        ]
        _run_all(engine, reqs)
        assert engine.sched.chunks_total > 1
        assert engine.sched.fused_steps_total == 0

    def test_burst_engine_one_chunk_carrying_program_per_bucket(self):
        """`aot_signatures` of a burst engine that can fuse names ONE
        chunk-carrying program per flat-token bucket (the mixed
        ``decode_hidden`` form: no chunk-only twin, no [B, W, V] mixed
        program), `warm_chunk_forwards` dispatches exactly those and the
        mixed step's greedy sampling tail, and a greedy mixed load
        afterwards meets no program for the first time."""
        from fusioninfer_tpu.engine.engine import _bump_count_rows
        from fusioninfer_tpu.engine.model_runner import fused_step
        from fusioninfer_tpu.engine.sampler import make_row_keys, sample_topk
        from fusioninfer_tpu.ops.lm_head_topk import lm_head_topk

        def engine(**kw):
            return NativeEngine(CFG, cache_cfg=CacheConfig(
                n_pages=19, page_size=32, max_pages_per_seq=8),
                max_batch_size=2, token_budget=96, decode_burst_steps=8,
                **kw)

        eng = engine()
        names = [n for n, _ in eng.aot_signatures()]
        chunk_carrying = [n for n in names if n.startswith(
            ("fused/chunk-", "fused/mixed-"))]
        assert chunk_carrying == [f"fused/mixed-hidden-t{t}"
                                  for t in (16, 32, 64, 128)]
        assert {"lm_head_topk/b2", "sample_topk/greedy",
                "sample_topk/topk"} <= set(names)
        before = fused_step._cache_size()
        assert eng.warm_chunk_forwards() == 4
        assert fused_step._cache_size() == before + 4
        assert eng.alloc.used_pages == 0 and not eng.has_work()
        programs = (fused_step, lm_head_topk, sample_topk, _bump_count_rows,
                    make_row_keys)
        warmed = [f._cache_size() for f in programs]
        rng = np.random.default_rng(9)
        _run_all(eng, [
            Request("a", [1, 2, 3], SamplingParams(max_tokens=24,
                                                   temperature=0.0)),
            Request("b", rng.integers(1, CFG.vocab_size, 230).tolist(),
                    SamplingParams(max_tokens=3, temperature=0.0)),
        ])
        assert eng.sched.fused_steps_total > 0
        # the chain's successors ride the same warmed program
        assert eng.sched.mixed_dispatch_ahead_total > 0
        # decode-only classic steps never run here (every row bursts),
        # so the warmed programs are all the ragged forward meets; an
        # all-greedy tail reads no row key (first tokens take one of [1])
        assert [f._cache_size() for f in programs] == warmed
        # an engine that cannot fuse (fused sampling off, --no-fused-step,
        # speculative) keeps the chunk-only program and no mixed one
        for kw in (dict(fused_sampling=False), dict(fused_step=False),
                   dict(speculative_k=2)):
            names = [n for n, _ in engine(**kw).aot_signatures()]
            assert "fused/chunk-t16" in names, kw
            assert not any(n.startswith("fused/mixed-") for n in names), kw

    def test_flag_off_never_fuses(self):
        engine = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=4,
                              token_budget=16, fused_step=False)
        _run_all(engine, _mixed_reqs())
        assert engine.sched.fused_steps_total == 0

    def test_packed_tokens_histogram_observes(self):
        engine = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=4,
                              token_budget=16, fused_step=True)
        _run_all(engine, _mixed_reqs())
        hist = engine.sched.fused_packed_tokens
        assert sum(hist.values()) == engine.sched.fused_steps_total
        assert engine.sched.fused_packed_tokens_sum >= \
            engine.sched.fused_steps_total


def _chain_log(engine) -> list:
    """Every chain decision: ``(row at its last token, prompt completing,
    successor dispatched)`` per mixed step read back."""
    log, chain = [], engine._chain_mixed

    def logged(fl):
        last = any(st.request.params.max_tokens - st.n_generated <= 1
                   for st in fl.rows.values())
        nxt = chain(fl)
        log.append((last, bool(fl.done), nxt is not None))
        return nxt
    engine._chain_mixed = logged
    return log


class TestMixedChain:
    """Dispatch-ahead for mixed steps on a burst engine: a mixed step
    enqueues its decode tail before any first-token fetch and, when the
    next step's rows are known, its successor (decode inputs carried on
    the device) before its own fetch."""

    def _engine(self, **kw):
        return NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=4,
                            token_budget=16, decode_burst_steps=8, **kw)

    def _stream_and_long(self, engine, stream_tokens=40):
        """A greedy and a top-k row decoding, then a long prompt that
        chunks beside them; the first step's outputs."""
        engine.add_request(Request(
            "stream", [1, 2, 3],
            SamplingParams(max_tokens=stream_tokens, temperature=0.0)))
        engine.add_request(Request(
            "tk", [3, 2, 1], SamplingParams(max_tokens=40, temperature=0.7,
                                            seed=5, top_k=8)))
        outs = engine.step()
        engine.add_request(Request(
            "long", list(range(1, 120)),
            SamplingParams(max_tokens=4, temperature=0.0)))
        return outs

    @staticmethod
    def _drain(engine) -> dict:
        toks: dict[str, list[int]] = {}
        for _ in range(300):
            if not engine.has_work():
                break
            for o in engine.step():
                assert not (o.finish_reason or "").startswith("error"), o
                toks.setdefault(o.request_id, []).append(o.token)
        assert not engine.has_work()
        return toks

    def _chained_step(self, engine, toks=None):
        """Step until a mixed successor is in flight (recording the
        outputs into ``toks``)."""
        for _ in range(20):
            for o in engine.step():
                if toks is not None:
                    toks.setdefault(o.request_id, []).append(o.token)
            fl = engine._inflight
            if fl is not None and fl.kind == "mixed":
                return fl
        raise AssertionError("no mixed step was dispatched ahead")

    def test_engages_in_a_steady_mixed_load(self):
        engine = self._engine()
        log = _chain_log(engine)
        self._stream_and_long(engine)
        self._drain(engine)
        sched = engine.sched
        assert sched.mixed_dispatch_ahead_total == sum(n for *_, n in log) > 0
        # every chained successor is a dispatch ahead and a fused step
        assert sched.dispatch_ahead_total >= sched.mixed_dispatch_ahead_total
        assert sched.fused_steps_total > sched.mixed_dispatch_ahead_total
        assert engine.sched.snapshot()["mixed_dispatch_ahead"] == \
            sched.mixed_dispatch_ahead_total

    @pytest.mark.parametrize("why", ["completing_prompt", "last_token"])
    def test_breaks_where_the_next_rows_are_not_known(self, why):
        """A chunk that completes its prompt (its first token joins the
        next batch) and a row that spends its last token in the step
        each end the chain: that step's read-back enqueues nothing."""
        engine = self._engine()
        log = _chain_log(engine)
        self._stream_and_long(engine, stream_tokens=(
            40 if why == "completing_prompt" else 20))
        self._drain(engine)
        col = 1 if why == "completing_prompt" else 0
        assert any(n for *_, n in log)
        broken = [entry for entry in log if entry[col]]
        assert broken and not any(n for *_, n in broken)

    def test_breaks_on_an_admission(self):
        """A request admitted while a successor is in flight waits one
        mixed step (as behind a burst), joins, and the read-back of the
        in-flight step enqueues nothing."""
        engine = self._engine()
        self._stream_and_long(engine)
        self._chained_step(engine)
        engine.add_request(Request(
            "late", [7, 8, 9], SamplingParams(max_tokens=3, temperature=0.0)))
        outs = engine.step()
        assert {o.request_id for o in outs} >= {"late", "stream", "tk"}
        assert engine._inflight is None
        assert "late" in {st.request.request_id
                          for st in engine.running.values()}

    def test_a_cancel_mid_chain_discards_the_in_flight_token(self):
        """A decode row cancelled while its next token is in flight gets
        no token after the cancel; the other streams are those of a run
        without the cancel, and every page comes back."""
        def run(cancel):
            engine = self._engine()
            toks: dict[str, list[int]] = {}
            for o in self._stream_and_long(engine):
                toks.setdefault(o.request_id, []).append(o.token)
            fl = self._chained_step(engine, toks)
            if cancel:
                assert "tk" in {st.request.request_id
                                for st in fl.rows.values()}
                engine.cancel("tk")
                before = len(toks["tk"])
            for rid, t in self._drain(engine).items():
                toks.setdefault(rid, []).extend(t)
            return toks, engine, (before if cancel else None)

        ref, _, _ = run(False)
        got, eng, n = run(True)
        assert got["tk"] == ref["tk"][:n] and n < len(ref["tk"])
        assert {k: v for k, v in got.items() if k != "tk"} == \
            {k: v for k, v in ref.items() if k != "tk"}
        assert eng.cancelled_total == 1
        assert eng.alloc.free_pages == self._engine().alloc.free_pages

    def test_the_tail_dispatches_before_the_activation_fetch(self):
        """Dispatch-order probe: in a mixed step that completes a prompt,
        the decode rows' tail (lm_head→top-k, draw, count bump) is
        enqueued before the first-token draw and its fetch."""
        engine = self._engine(pipeline_bursts=False)
        order = []
        tail, activate = engine._fused_sample_dispatch, engine._activate_group

        def probe_tail(*a, **k):
            order.append("tail")
            return tail(*a, **k)

        def probe_activate(entries):
            order.append("activate")
            return activate(entries)
        engine._fused_sample_dispatch = probe_tail
        engine._activate_group = probe_activate
        self._stream_and_long(engine)
        mixed_with_activation = 0
        while engine.has_work():
            del order[:]
            before = engine.sched.fused_steps_total
            engine.step()
            if engine.sched.fused_steps_total > before and "activate" in order:
                mixed_with_activation += 1
                assert order.index("tail") < order.index("activate"), order
        assert mixed_with_activation > 0


class TestCLIAndMetrics:
    def test_serve_flag_round_trip(self):
        from fusioninfer_tpu.cli import build_parser

        p = build_parser()
        assert p.parse_args(["engine", "serve"]).fused_step is True
        assert p.parse_args(
            ["engine", "serve", "--no-fused-step"]).fused_step is False
        assert p.parse_args(
            ["engine", "serve", "--fused-step"]).fused_step is True

    def test_metrics_families_rendered(self):
        from fusioninfer_tpu.engine.metrics import EngineMetrics

        engine = NativeEngine(CFG, cache_cfg=_cache_cfg(), max_batch_size=4,
                              token_budget=16, fused_step=True)
        _run_all(engine, _mixed_reqs())
        text = EngineMetrics("m").render(engine)
        for family in ("fusioninfer:sched_fused_steps_total",
                       "fusioninfer:sched_mixed_dispatch_ahead_total",
                       "fusioninfer:sched_weight_passes_total",
                       "fusioninfer:sched_fused_packed_tokens"):
            assert f"# TYPE {family} " in text, family
            assert f"# HELP {family} " in text, family
        # the histogram renders cumulative buckets + sum + count, and
        # the +Inf bucket equals the count (Prometheus contract)
        inf = [ln for ln in text.splitlines()
               if ln.startswith("fusioninfer:sched_fused_packed_tokens_bucket")
               and 'le="+Inf"' in ln]
        cnt = [ln for ln in text.splitlines()
               if ln.startswith("fusioninfer:sched_fused_packed_tokens_count")]
        assert len(inf) == 1 and len(cnt) == 1
        assert inf[0].rsplit(" ", 1)[1] == cnt[0].rsplit(" ", 1)[1]
        assert int(cnt[0].rsplit(" ", 1)[1]) == engine.sched.fused_steps_total
