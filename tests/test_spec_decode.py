"""Speculative decoding: n-gram proposer, window rows, engine identity.

The invariants: greedy output with speculation on is BIT-identical to
speculation off (argmax acceptance); sampled (temperature>0) rows
speculate via delta-draft rejection sampling, which preserves the
filtered target distribution EXACTLY and is deterministic for a given
(seed, speculation config) — but is not stream-identical to the
unspeculated run (randomness is consumed differently).  Penalized
requests in the same batch run unspeculated, losslessly.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fusioninfer_tpu.engine.engine import NativeEngine, Request
from fusioninfer_tpu.engine.fused import pack_ragged_batch
from fusioninfer_tpu.engine.kv_cache import CacheConfig, PageAllocator, init_kv_cache
from fusioninfer_tpu.engine.model_runner import decode_step, fused_step, prefill
from fusioninfer_tpu.engine.sampler import SamplingParams
from fusioninfer_tpu.engine.spec import NgramProposer
from fusioninfer_tpu.models.config import get_preset
from fusioninfer_tpu.models.transformer import init_params

CFG = get_preset("qwen3-tiny")


class TestNgramProposer:
    def test_finds_latest_match(self):
        p = NgramProposer(max_ngram=2)
        #          0  1  2  3  4  5  6  7
        tokens = [5, 6, 9, 9, 5, 6, 7, 5]  # suffix [6?]... last is [5]
        # suffix n=2 is (7, 5): no earlier occurrence; n=1 suffix (5,)
        # latest earlier 5 at index 4 -> followers 6, 7, 5
        assert p.propose(tokens, 3) == [6, 7, 5]

    def test_longest_ngram_wins(self):
        p = NgramProposer(max_ngram=3)
        tokens = [1, 2, 3, 8, 4, 2, 3, 9, 1, 2, 3]
        # n=3 suffix (1,2,3) matches at 0 -> follower 8
        assert p.propose(tokens, 2) == [8, 4]

    def test_periodic_run_extends(self):
        p = NgramProposer()
        assert p.propose([4, 4, 4, 4, 4, 4], 3) == [4, 4, 4]
        assert p.propose([4, 4], 3) == [4]  # only one follower exists

    def test_no_match(self):
        assert NgramProposer().propose([1, 2, 3, 4], 4) == []

    def test_short_sequences(self):
        p = NgramProposer()
        assert p.propose([], 4) == []
        assert p.propose([7], 4) == []
        assert p.propose([7, 7], 4) == [7]

    def test_k_caps_draft(self):
        p = NgramProposer()
        assert p.propose([1, 2, 3, 4, 5, 1], 2) == [2, 3]
        assert p.propose([1, 2, 3], 0) == []


def _seeded_cache(cfg, cache_cfg, prompt_len, B):
    """Prefill B identical prompts so decode/verify start from real KV."""
    params = init_params(cfg, jax.random.key(0))
    cache = init_kv_cache(cfg, cache_cfg)
    alloc = PageAllocator(cache_cfg)
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, cfg.vocab_size, prompt_len, dtype=np.int32)
    mp = cache_cfg.max_pages_per_seq
    rows = np.zeros((B, mp), np.int32)
    for b in range(B):
        alloc.allocate(str(b), prompt_len + 16)
        rows[b] = alloc.page_table_row(str(b))
    padded = np.tile(prompt, (B, 1))
    cache, _ = prefill(cfg, cache_cfg, params, cache,
                       jnp.asarray(padded),
                       jnp.full((B,), prompt_len, jnp.int32),
                       jnp.asarray(rows))
    return params, cache, jnp.asarray(rows), prompt_len


def _window_forward(cfg, cache_cfg, params, cache, window, starts, counts,
                    rows):
    """Speculative-window rows through the program the engine runs them
    on: row ``b`` is ``counts[b]`` tokens of ``window[b]`` from position
    ``starts[b]`` (``q_len = 1 + drafts``; 0 an inert slot), packed as
    the engine packs a decode-only step, ``sel`` over every window
    column → ``(cache, logits [B, W, V])``."""
    B = window.shape[0]
    p = pack_ragged_batch(
        np.asarray(window, np.int32), np.asarray(counts, np.int32),
        np.asarray(starts, np.int32), np.asarray(rows, np.int32),
        np.zeros((B,), np.int32), [], cache_cfg.trash_page, chunk_rows=0)
    cache, logits, _ = fused_step(
        cfg, cache_cfg, params, cache, jnp.asarray(p.tokens),
        jnp.asarray(p.row_starts), jnp.asarray(p.q_begins),
        jnp.asarray(p.q_lens), jnp.asarray(p.page_tables),
        jnp.asarray(p.sel), jnp.asarray(p.chunk_sel))
    return cache, logits


@pytest.mark.parametrize("attn_impl", ["reference", "flash"])
class TestSpecWindowRows:
    """A speculative window is rows of ``fused_step`` with ``q_len = 1 +
    drafts``: what one forward says of a window is what sequential
    ``decode_step``s say, kernel path and portable path alike."""

    def test_matches_sequential_decode(self, attn_impl):
        """logits[b, j] of one window forward == the j-th sequential
        decode_step's logits, and the final caches agree."""
        cfg = dataclasses.replace(CFG, attn_impl=attn_impl)
        cache_cfg = CacheConfig(n_pages=17, page_size=16, max_pages_per_seq=4)
        B, C, plen = 2, 4, 18  # window straddles a page boundary
        params, cache0, rows, pos0 = _seeded_cache(cfg, cache_cfg, plen, B)
        rng = np.random.default_rng(3)
        window = rng.integers(1, cfg.vocab_size, (B, C), dtype=np.int32)

        cache_v, logits_v = _window_forward(
            cfg, cache_cfg, params, jax.tree.map(jnp.copy, cache0), window,
            np.full((B,), pos0), np.full((B,), C), rows)

        cache_s = jax.tree.map(jnp.copy, cache0)
        for j in range(C):
            cache_s, logits_j = decode_step(
                cfg, cache_cfg, params, cache_s,
                jnp.asarray(window[:, j]),
                jnp.full((B,), pos0 + j, jnp.int32),
                rows, jnp.ones((B,), bool),
            )
            np.testing.assert_allclose(
                np.asarray(logits_v[:, j]), np.asarray(logits_j),
                atol=2e-2, rtol=2e-2,
            )
        # the flat axis' signature pad writes the trash page; every
        # page a sequence owns agrees
        real = np.arange(cache_cfg.n_pages) != cache_cfg.trash_page
        for k in ("k", "v"):
            np.testing.assert_allclose(
                np.asarray(cache_v[k], np.float32)[:, :, real],
                np.asarray(cache_s[k], np.float32)[:, :, real],
                atol=1e-2, rtol=1e-2,
            )

    def test_partial_counts_mask_writes(self, attn_impl):
        """Window columns past counts[b] must not touch the sequence's
        pages, and count-0 slots are fully inert."""
        cfg = dataclasses.replace(CFG, attn_impl=attn_impl)
        cache_cfg = CacheConfig(n_pages=17, page_size=16, max_pages_per_seq=4)
        B, C, plen = 2, 4, 20
        params, cache0, rows, pos0 = _seeded_cache(cfg, cache_cfg, plen, B)
        window = np.full((B, C), 7, np.int32)
        counts = np.asarray([2, 0], np.int32)
        cache_v, _ = _window_forward(
            cfg, cache_cfg, params, jax.tree.map(jnp.copy, cache0), window,
            np.full((B,), pos0), counts, rows)
        ps = cache_cfg.page_size
        k0, kv = np.asarray(cache0["k"], np.float32), np.asarray(cache_v["k"], np.float32)
        # seq 0: positions pos0, pos0+1 written; pos0+2.. untouched
        page = int(np.asarray(rows)[0, pos0 // ps])
        assert not np.array_equal(kv[:, :, page, pos0 % ps + 1],
                                  k0[:, :, page, pos0 % ps + 1])
        page = int(np.asarray(rows)[0, (pos0 + 2) // ps])
        slot = (pos0 + 2) % ps
        np.testing.assert_array_equal(kv[:, :, page, slot], k0[:, :, page, slot])
        # seq 1 (count 0): all its real pages untouched (its table rows
        # are padded with the trash page, which masked writes DO hit)
        for p in np.asarray(rows)[1]:
            if p == cache_cfg.trash_page:
                continue
            np.testing.assert_array_equal(kv[:, :, p], k0[:, :, p])

    def test_int8_pages_close_to_bf16(self, attn_impl):
        """The same window over int8 pages stays within the accumulated
        quantization error of the model-dtype pages."""
        cfg = dataclasses.replace(CFG, attn_impl=attn_impl)
        rng = np.random.default_rng(3)
        window = rng.integers(1, cfg.vocab_size, (2, 4), dtype=np.int32)
        counts = np.asarray([4, 2], np.int32)
        logits = {}
        for kv_dtype in ("int8", "model"):
            cache_cfg = CacheConfig(n_pages=33, page_size=16,
                                    max_pages_per_seq=8, kv_dtype=kv_dtype)
            params, cache, rows, pos0 = _seeded_cache(cfg, cache_cfg, 21, 2)
            _, lg = _window_forward(cfg, cache_cfg, params, cache, window,
                                    np.full((2,), pos0), counts, rows)
            logits[kv_dtype] = np.asarray(lg, np.float32)
        a, b = logits["int8"], logits["model"]
        denom = np.maximum(np.abs(b).max(), 1.0)
        assert np.max(np.abs(a[:, :2] - b[:, :2])) / denom < 0.08


def _drain(engine, requests, max_steps=500):
    import copy

    for r in copy.deepcopy(requests):
        engine.add_request(r)
    tokens: dict[str, list[int]] = {r.request_id: [] for r in requests}
    steps = 0
    while engine.has_work():
        steps += 1
        assert steps <= max_steps, "engine did not drain"
        for o in engine.step():
            assert not (o.finish_reason or "").startswith("error"), o
            tokens[o.request_id].append(o.token)
    return tokens, steps


class TestEngineIdentity:
    CACHE = CacheConfig(n_pages=65, page_size=16, max_pages_per_seq=16)

    def _requests(self):
        # highly repetitive prompt -> n-gram lookup actually accepts
        loop = [11, 12, 13, 14, 15, 16, 17, 18] * 8
        rng = np.random.default_rng(5)
        return [
            Request(request_id="greedy-rep", prompt_tokens=loop,
                    params=SamplingParams(max_tokens=24, temperature=0.0)),
            Request(request_id="greedy-rand",
                    prompt_tokens=rng.integers(1, CFG.vocab_size, 21).tolist(),
                    params=SamplingParams(max_tokens=10, temperature=0.0)),
            Request(request_id="sampled",
                    prompt_tokens=rng.integers(1, CFG.vocab_size, 15).tolist(),
                    params=SamplingParams(max_tokens=10, temperature=0.9,
                                          seed=42)),
            Request(request_id="penalized", prompt_tokens=loop[:32],
                    params=SamplingParams(max_tokens=8, temperature=0.0,
                                          repetition_penalty=1.3)),
        ]

    def test_identity_and_step_savings(self):
        base = NativeEngine(CFG, cache_cfg=self.CACHE, max_batch_size=4)
        spec = NativeEngine(CFG, cache_cfg=self.CACHE, max_batch_size=4,
                            speculative_k=7)
        a, steps_a = _drain(base, self._requests())
        b, steps_b = _drain(spec, self._requests())
        # greedy and penalized rows: BIT-identical with speculation on.
        # The sampled row is distribution-exact, not stream-identical
        # (rejection sampling consumes randomness differently) — its
        # determinism contract is covered by TestSampledSpeculation.
        for rid in ("greedy-rep", "greedy-rand", "penalized"):
            assert a[rid] == b[rid], f"speculation changed tokens for {rid}"
        assert len(b["sampled"]) == len(a["sampled"])
        assert spec.spec_proposed_total > 0
        assert spec.spec_accepted_total > 0, (
            "repetitive greedy prompt should accept drafts"
        )
        assert steps_b < steps_a, "accepted drafts should save steps"

    def test_solo_greedy_repetitive(self):
        base = NativeEngine(CFG, cache_cfg=self.CACHE, max_batch_size=2)
        spec = NativeEngine(CFG, cache_cfg=self.CACHE, max_batch_size=2,
                            speculative_k=4)
        req = [Request(request_id="r",
                       prompt_tokens=[3, 4, 5] * 12,
                       params=SamplingParams(max_tokens=16, temperature=0.0))]
        a, _ = _drain(base, req)
        b, _ = _drain(spec, req)
        assert a == b

    def test_max_tokens_exact(self):
        """A burst must stop exactly at max_tokens with finish 'length'."""
        spec = NativeEngine(CFG, cache_cfg=self.CACHE, max_batch_size=2,
                            speculative_k=7)
        spec.add_request(Request(
            request_id="r", prompt_tokens=[9, 8] * 16,
            params=SamplingParams(max_tokens=5, temperature=0.0)))
        outs = []
        while spec.has_work():
            outs.extend(o for o in spec.step() if o.request_id == "r")
        assert len(outs) == 5
        assert outs[-1].finished and outs[-1].finish_reason in ("length", "stop")
        assert all(not o.finished for o in outs[:-1])

    def test_spec_metrics_rendered(self):
        from fusioninfer_tpu.engine.metrics import EngineMetrics

        spec = NativeEngine(CFG, cache_cfg=self.CACHE, max_batch_size=2,
                            speculative_k=4)
        text = EngineMetrics("m").render(spec)
        assert "vllm:spec_decode_num_draft_tokens_total" in text
        assert "vllm:spec_decode_num_accepted_tokens_total" in text


class TestSampledSpeculation:
    """Rejection-sampling speculation for temperature>0 rows: the
    acceptance rule preserves the target distribution EXACTLY for delta
    drafts, output is deterministic for a (seed, spec config), and a
    top_k=1 filtered distribution (a delta) must reproduce greedy."""

    CACHE = CacheConfig(n_pages=65, page_size=16, max_pages_per_seq=16)

    def test_marginal_distribution_preserved(self):
        """Sampler-level exactness: emit = draft if u < p(draft) else
        replacement ⇒ the emitted marginal equals the filtered target
        distribution, whatever token is proposed."""
        import jax
        import jax.numpy as jnp

        from fusioninfer_tpu.engine.sampler import (
            filter_logits,
            make_row_keys,
            spec_window_draws,
        )

        V, N = 12, 4000
        base = jax.random.normal(jax.random.key(0), (1, V)) * 2.0
        temps = jnp.full((N,), 0.8, jnp.float32)
        tks = jnp.zeros((N,), jnp.int32)
        tps = jnp.full((N,), 0.9, jnp.float32)
        mps = jnp.zeros((N,), jnp.float32)
        target = np.asarray(jax.nn.softmax(filter_logits(
            base, temps[:1], tks[:1], tps[:1], mps[:1]), axis=-1))[0]
        draft = int(np.argsort(target)[-2])  # a plausible draft token

        # one batched call: N independent keys over the SAME position
        logits_w = jnp.tile(base.astype(jnp.float32), (N, 1))[:, None, :]
        dn = jnp.full((N, 1), draft, jnp.int32)
        keys = make_row_keys(jnp.full((N,), 7, jnp.uint32),
                             jnp.arange(N, dtype=jnp.int32)).reshape(N, 1)
        full, p_d, u, repl = spec_window_draws(
            logits_w, dn, keys, temps, tks, tps, mps)
        full = np.asarray(full[:, 0])
        accept = np.asarray(u[:, 0]) < np.asarray(p_d[:, 0])
        emitted = np.where(accept, draft, np.asarray(repl[:, 0]))
        emp = np.bincount(emitted, minlength=V) / N
        np.testing.assert_allclose(emp, target, atol=0.04)
        # the independent full draw (the bonus-token path) matches the
        # target marginal too
        emp_full = np.bincount(full, minlength=V) / N
        np.testing.assert_allclose(emp_full, target, atol=0.04)

    def test_seeded_sampled_deterministic_under_spec(self):
        def run():
            eng = NativeEngine(CFG, cache_cfg=self.CACHE, max_batch_size=2,
                               speculative_k=4)
            reqs = [Request(request_id="s", prompt_tokens=[3, 4, 5] * 10,
                            params=SamplingParams(max_tokens=16,
                                                  temperature=0.8, seed=11))]
            out, _ = _drain(eng, reqs)
            return out["s"], eng.spec_proposed_total, eng.spec_accepted_total

        a, prop_a, acc_a = run()
        b, prop_b, acc_b = run()
        assert a == b and (prop_a, acc_a) == (prop_b, acc_b)
        assert len(a) == 16

    def test_top_k_one_reproduces_greedy(self):
        """top_k=1 collapses the filtered distribution to a delta at the
        argmax: a 'sampled' request must then emit exactly the greedy
        stream, speculation on or off — a sharp correctness check on
        the acceptance math (any off-by-one in p/u/replacement shows)."""
        prompts = [3, 4, 5] * 10

        def run(spec_k, temperature, top_k=0):
            eng = NativeEngine(CFG, cache_cfg=self.CACHE, max_batch_size=2,
                               speculative_k=spec_k)
            reqs = [Request(request_id="r", prompt_tokens=list(prompts),
                            params=SamplingParams(max_tokens=14,
                                                  temperature=temperature,
                                                  top_k=top_k, seed=5))]
            out, _ = _drain(eng, reqs)
            return out["r"]

        greedy = run(None, 0.0)
        assert run(4, 0.9, top_k=1) == greedy
        assert run(None, 0.9, top_k=1) == greedy

    def test_sampled_spec_proposes_and_saves_steps(self):
        """Near-greedy temperature on a repetitive prompt: the sampled
        row follows the pattern, n-gram drafts flow, acceptance fires,
        and accepted bursts save decode steps — through the REJECTION
        path, not the argmax path (temperature > 0)."""
        reqs = lambda: [Request(  # noqa: E731
            request_id="s", prompt_tokens=[7, 8, 9] * 12,
            params=SamplingParams(max_tokens=24, temperature=0.05, seed=2))]
        base = NativeEngine(CFG, cache_cfg=self.CACHE, max_batch_size=2)
        spec = NativeEngine(CFG, cache_cfg=self.CACHE, max_batch_size=2,
                            speculative_k=6)
        _, steps_a = _drain(base, reqs())
        out, steps_b = _drain(spec, reqs())
        assert len(out["s"]) == 24
        assert spec.spec_proposed_total > 0  # sampled rows DO speculate
        assert spec.spec_accepted_total > 0
        assert steps_b < steps_a
