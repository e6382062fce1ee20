"""Automatic prefix caching: allocator sharing/eviction semantics and
engine-level correctness — cached-prefix generation must be token-
identical to cold generation, while actually skipping prefill compute."""

import dataclasses


from fusioninfer_tpu.engine.engine import NativeEngine, Request
from fusioninfer_tpu.engine.kv_cache import CacheConfig
from fusioninfer_tpu.engine.prefix_cache import PrefixCachingAllocator, block_hashes
from fusioninfer_tpu.engine.sampler import SamplingParams
from fusioninfer_tpu.models.config import get_preset

CFG = dataclasses.replace(get_preset("qwen3-tiny"), dtype="float32")
CACHE = CacheConfig(n_pages=33, page_size=8, max_pages_per_seq=8)


class TestBlockHashes:
    def test_chain_depends_on_prefix(self):
        a = block_hashes([1, 2, 3, 4, 5, 6, 7, 8], 4)
        b = block_hashes([9, 2, 3, 4, 5, 6, 7, 8], 4)
        assert len(a) == len(b) == 2
        assert a[0] != b[0]
        assert a[1] != b[1]  # second block differs because its parent does

    def test_partial_block_not_hashed(self):
        assert len(block_hashes([1, 2, 3], 4)) == 0
        assert len(block_hashes([1, 2, 3, 4, 5], 4)) == 1


class TestAllocatorSharing:
    def test_match_caps_at_prompt_minus_one(self):
        alloc = PrefixCachingAllocator(CACHE)
        prompt = list(range(16))  # exactly two full pages of 8
        alloc.allocate("a", len(prompt) + 1)
        alloc.register_blocks("a", prompt)
        alloc.release("a")
        # identical prompt: only the first page may be reused (cap len-1)
        assert alloc.match_prefix("b", prompt) == 8

    def test_shared_pages_survive_owner_release(self):
        alloc = PrefixCachingAllocator(CACHE)
        prompt = list(range(24))
        alloc.allocate("a", len(prompt) + 1)
        alloc.register_blocks("a", prompt)
        pages_a = alloc.pages_of("a")

        got = alloc.match_prefix("b", prompt + [99, 98])
        assert got == 24  # all three full pages reusable (longer prompt)
        assert alloc.pages_of("b") == pages_a[:3]
        alloc.release("a")
        # b still holds the shared pages; they are not free
        alloc.allocate("b", 26 + 1)
        assert set(alloc.pages_of("b")[:3]) == set(pages_a[:3])
        alloc.release("b")

    def test_eviction_reclaims_lru_cached_pages(self):
        small = CacheConfig(n_pages=5, page_size=8, max_pages_per_seq=4)
        alloc = PrefixCachingAllocator(small)  # 4 usable pages
        p1 = list(range(8))
        alloc.allocate("a", 9)  # 2 pages
        alloc.register_blocks("a", p1)
        alloc.release("a")  # page 0 cached+evictable, page 1 free
        assert alloc.match_prefix("probe", p1 + [1]) == 8
        alloc.release("probe")
        # exhaust the pool: cached page must be reclaimed
        alloc.allocate("big", 32)  # needs all 4 usable pages
        assert alloc.free_pages == 0
        # the cached content is gone now
        assert alloc.match_prefix("after", p1 + [1]) == 0
        alloc.release("big")

    def test_touch_block_shields_chain_from_adoption_reclaim(self):
        # the restore planner MRU-bumps a chain's HBM-resident blocks
        # before adopting pages for the host-held ones: without the
        # bump, the adoptions would LRU-reclaim the very chain being
        # restored (its blocks are typically the oldest evictable)
        small = CacheConfig(n_pages=5, page_size=8, max_pages_per_seq=4)
        alloc = PrefixCachingAllocator(small)
        pa, pb = list(range(8)), list(range(100, 108))
        alloc.allocate("a", 8)
        alloc.register_blocks("a", pa)
        alloc.release("a")  # oldest evictable
        alloc.allocate("b", 8)
        alloc.register_blocks("b", pb)
        alloc.release("b")  # newer evictable
        alloc.allocate("c", 16)  # exhaust the free list
        ha = block_hashes(pa, 8)[0]
        hb = block_hashes(pb, 8)[0]
        assert alloc.touch_block(ha) is True  # evictable -> bumped
        alloc.adopt_block(b"\x99" * 16)  # reclaims LRU: now b, not a
        assert alloc.has_block(ha)
        assert not alloc.has_block(hb)
        assert alloc.touch_block(b"\x77" * 16) is False  # unknown hash
        alloc.release("c")

    def test_hit_rate_accounting(self):
        alloc = PrefixCachingAllocator(CACHE)
        prompt = list(range(16)) + [77]
        alloc.allocate("a", len(prompt) + 1)
        alloc.register_blocks("a", prompt)
        alloc.release("a")
        assert alloc.match_prefix("b", prompt) == 16
        assert 0.0 < alloc.prefix_hit_rate() < 1.0


def _generate(engine, rid, prompt, n=8):
    engine.add_request(Request(rid, prompt, SamplingParams(temperature=0.0, max_tokens=n)))
    out = []
    while engine.has_work():
        for o in engine.step():
            if o.request_id == rid:
                out.append(o.token)
    return out


class TestEnginePrefixCaching:
    def test_warm_generation_identical_and_hits(self):
        prompt = list(range(1, 21))  # 20 tokens → two full pages cacheable
        cold_engine = NativeEngine(
            CFG, cache_cfg=CACHE, max_batch_size=2, seed=0,
            enable_prefix_caching=False,
        )
        cold = _generate(cold_engine, "c", list(prompt))

        engine = NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2, seed=0)
        first = _generate(engine, "r1", list(prompt))
        assert first == cold  # caching off vs on, cold: same tokens
        hits_before = engine.alloc.hit_tokens_total
        second = _generate(engine, "r2", list(prompt))
        assert second == cold  # warm (cached prefix) must not change output
        assert engine.alloc.hit_tokens_total > hits_before
        assert engine.prefix_cache_hit_rate() > 0.0

    def test_extended_prompt_reuses_shared_prefix(self):
        base = list(range(1, 17))  # two full pages
        long = base + [42, 43, 44]
        cold_engine = NativeEngine(
            CFG, cache_cfg=CACHE, max_batch_size=2, seed=0,
            enable_prefix_caching=False,
        )
        cold = _generate(cold_engine, "c", list(long))

        engine = NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2, seed=0)
        _generate(engine, "r1", list(base))
        warm = _generate(engine, "r2", list(long))
        assert warm == cold
        assert engine.alloc.hit_tokens_total >= 16

    def test_caching_engine_metrics_exposed(self):
        from fusioninfer_tpu.engine.metrics import EngineMetrics

        engine = NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2, seed=0)
        _generate(engine, "r1", list(range(1, 21)))
        _generate(engine, "r2", list(range(1, 21)))
        text = EngineMetrics("m").render(engine)
        assert "vllm:gpu_prefix_cache_hit_rate" in text


class TestReuseAwareAdmission:
    def test_cached_prompt_admits_under_pressure(self):
        # 8 usable pages; a 40-token prompt needs 6 pages (40+1 tokens / 8)
        small = CacheConfig(n_pages=9, page_size=8, max_pages_per_seq=8)
        alloc = PrefixCachingAllocator(small)
        prompt = list(range(40))
        alloc.allocate("a", len(prompt) + 1)
        alloc.register_blocks("a", prompt)
        # another seq pins 2 of the remaining pages
        alloc.allocate("pin", 16)
        alloc.release("a")  # 5 full-prompt pages cached+evictable, 1 freed

        # naive math: needs 6 pages but only 6 free (1 + 5 evictable) — the
        # cached 4 reusable blocks mean only 2 fresh pages are truly needed
        assert alloc.can_admit(prompt, 1)
        got = alloc.match_prefix("b", prompt)
        assert got == 32  # 4 blocks (cap at len-1 tokens)
        alloc.allocate("b", len(prompt) + 1)  # must not raise
        alloc.release("b")
        alloc.release("pin")

    def test_uncached_prompt_still_blocked(self):
        small = CacheConfig(n_pages=9, page_size=8, max_pages_per_seq=8)
        alloc = PrefixCachingAllocator(small)
        alloc.allocate("pin", 48)  # 6 of 8 usable pages
        assert not alloc.can_admit(list(range(40)), 1)  # needs 6, 2 free
        alloc.release("pin")


class TestBatchedSuffixPrefill:
    """A burst of short-suffix cache hits runs as rows of ONE fused_step
    forward (engine._prefill_suffix_batch) — tokens must be identical to
    serial per-request admission."""

    def _mk(self, rid, prompt, seed=None, temperature=0.0):
        return Request(
            request_id=rid, prompt_tokens=list(prompt),
            params=SamplingParams(max_tokens=5, temperature=temperature,
                                  seed=seed))

    def _drain(self, engine, reqs):
        toks: dict[str, list[int]] = {r.request_id: [] for r in reqs}
        for _ in range(80):
            if not engine.has_work():
                break
            for o in engine.step():
                assert not (o.finish_reason or "").startswith("error"), o
                toks[o.request_id].append(o.token)
        assert not engine.has_work()
        return toks

    BIG = CacheConfig(n_pages=65, page_size=8, max_pages_per_seq=24)

    def test_burst_matches_serial(self):
        import numpy as np

        common = list(range(1, 25))  # 3 full pages of 8
        rng = np.random.default_rng(0)
        tails = [rng.integers(1, CFG.vocab_size, n).tolist()
                 for n in (3, 47, 100)]  # all within the batch window (128)
        prompts = [common + t for t in tails]

        def warm_engine():
            eng = NativeEngine(CFG, cache_cfg=self.BIG, max_batch_size=4, seed=0)
            seed_req = self._mk("seed", common + [99])
            eng.add_request(seed_req)
            self._drain(eng, [seed_req])  # registers the common pages
            return eng

        # serial: one request at a time (hits take _prefill_suffix_one)
        serial = warm_engine()
        out_serial = {}
        for i, p in enumerate(prompts):
            r = self._mk(f"r{i}", p, seed=50 + i, temperature=0.8)
            serial.add_request(r)
            out_serial.update(self._drain(serial, [r]))

        # burst: all three land in one admission round -> one forward
        burst = warm_engine()
        reqs = [self._mk(f"r{i}", p, seed=50 + i, temperature=0.8)
                for i, p in enumerate(prompts)]
        for r in reqs:
            burst.add_request(r)
        out_burst = self._drain(burst, reqs)
        assert out_burst == out_serial
        assert burst.prefix_cache_hit_rate() > 0

    def test_long_suffix_falls_back_to_serial_path(self):
        import numpy as np

        common = list(range(1, 25))
        tail = np.random.default_rng(1).integers(
            1, CFG.vocab_size, 150).tolist()  # > _SUFFIX_BATCH_WINDOW
        eng = NativeEngine(CFG, cache_cfg=self.BIG, max_batch_size=4, seed=0)
        seed_req = self._mk("seed", common + [99])
        eng.add_request(seed_req)
        self._drain(eng, [seed_req])
        r = self._mk("long", common + tail)
        eng.add_request(r)
        toks = self._drain(eng, [r])
        assert len(toks["long"]) == 5


class TestPrecomputedChain:
    """PR 9 satellite: admission computes a prompt's block-hash chain
    ONCE and threads it through the restore consult, can_admit and
    match_prefix — the precomputed chain must be semantically identical
    to the internally rebuilt one."""

    def _chain(self, prompt, namespace=b""):
        ps = CACHE.page_size
        usable = max(0, (len(prompt) - 1) // ps)
        return block_hashes(prompt, ps, namespace)[:usable]

    def test_match_prefix_equivalent_with_and_without_chain(self):
        prompt = list(range(24))
        a = PrefixCachingAllocator(CACHE)
        a.allocate("seed", len(prompt) + 1)
        a.register_blocks("seed", prompt)
        a.release("seed")
        without = a.match_prefix("x", prompt)
        a.release("x")
        with_chain = a.match_prefix("y", prompt,
                                    chain=self._chain(prompt))
        assert with_chain == without == 16
        a.release("y")

    def test_can_admit_equivalent_with_and_without_chain(self):
        prompt = list(range(24))
        alloc = PrefixCachingAllocator(CACHE)
        alloc.allocate("seed", len(prompt) + 1)
        alloc.register_blocks("seed", prompt)
        alloc.release("seed")
        assert (alloc.can_admit(prompt, 1)
                == alloc.can_admit(prompt, 1, chain=self._chain(prompt)))

    def test_engine_admission_hashes_once_per_request(self, monkeypatch):
        """The whole point of the satellite: one admission = one
        block_hashes build (it used to be up to three — restore consult,
        can_admit's peek, match_prefix)."""
        import fusioninfer_tpu.engine.engine as engine_mod
        import fusioninfer_tpu.engine.prefix_cache as pc_mod
        from fusioninfer_tpu.engine.engine import block_hashes as real_bh

        calls = []

        def counting_bh(tokens, ps, namespace=b""):
            calls.append(len(tokens))
            return real_bh(tokens, ps, namespace)

        # BOTH from-import bindings: if the chain= threading were
        # dropped, the allocator would silently rebuild through its own
        # module-level import and an engine-only count would miss it
        monkeypatch.setattr(engine_mod, "block_hashes", counting_bh)
        monkeypatch.setattr(pc_mod, "block_hashes", counting_bh)
        eng = NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2)
        eng.add_request(Request(
            "r1", list(range(20)),
            SamplingParams(temperature=0.0, max_tokens=2)))
        while eng.has_work():
            eng.step()
        admission_builds = [n for n in calls if n == 20]
        assert len(admission_builds) == 1, calls
