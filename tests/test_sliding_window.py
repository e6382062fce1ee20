"""Sliding-window attention (Mistral family).

Every execution path — full forward, fresh prefill, and the decode,
chunk, suffix and speculative-window rows of the ragged forwards — must
band attention to the trailing ``sliding_window`` positions, in both the
Pallas kernels (which skip out-of-window pages) and the portable gather
paths.  Correctness bars: windowed kernels match windowed oracles (the
flash kernel here; the ragged family row kind by row kind in
``tests/test_ragged_row_kinds.py``); window ≥ context reproduces full
causal attention
exactly; the engine serves a Mistral-shaped model end-to-end with
token identity between the portable and kernel paths.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from fusioninfer_tpu.engine.engine import NativeEngine, Request
from fusioninfer_tpu.engine.kv_cache import CacheConfig, PageAllocator, init_kv_cache
from fusioninfer_tpu.engine.model_runner import decode_step, prefill
from fusioninfer_tpu.engine.sampler import SamplingParams
from fusioninfer_tpu.models.config import get_preset
from fusioninfer_tpu.models.transformer import init_params

MISTRAL = get_preset("mistral-tiny")  # sliding_window=24


class TestFlashWindow:
    def test_windowed_flash_matches_oracle(self):
        from fusioninfer_tpu.ops.flash_attention import (
            flash_attention,
            reference_attention,
        )

        B, S, H, KV, Hd = 2, 128, 4, 2, 64
        ks = jax.random.split(jax.random.key(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, Hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KV, Hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KV, Hd), jnp.float32)
        for w in (16, 32, 100):
            out = flash_attention(q, k, v, causal=True, window=w,
                                  block_q=32, block_k=32, interpret=True)
            ref = reference_attention(q, k, v, causal=True, window=w)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-4, rtol=2e-4)

    def test_window_ge_seq_is_full_causal(self):
        from fusioninfer_tpu.ops.flash_attention import (
            flash_attention,
            reference_attention,
        )

        B, S, H, KV, Hd = 1, 64, 4, 2, 64
        ks = jax.random.split(jax.random.key(1), 3)
        q = jax.random.normal(ks[0], (B, S, H, Hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KV, Hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KV, Hd), jnp.float32)
        out = flash_attention(q, k, v, causal=True, window=S,
                              block_q=32, block_k=32, interpret=True)
        full = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                                   atol=2e-4, rtol=2e-4)


class TestModelLevel:
    def test_decode_matches_windowed_oracle_prefill_then_decode(self):
        """Prefill + a few decode steps under the Mistral config, portable
        vs flash(interpret) paths token-for-logit close — both honor the
        window (context 40 > window 24, so the band is active)."""
        cache_cfg = CacheConfig(n_pages=17, page_size=16, max_pages_per_seq=4)
        rng = np.random.default_rng(0)
        prompt = rng.integers(1, MISTRAL.vocab_size, 40, dtype=np.int32)
        outs = {}
        for impl in ("reference", "flash"):
            cfg = dataclasses.replace(MISTRAL, attn_impl=impl, dtype="float32")
            params = init_params(cfg, jax.random.key(0))
            cache = init_kv_cache(cfg, cache_cfg)
            alloc = PageAllocator(cache_cfg)
            alloc.allocate("s", 50)
            row = jnp.asarray(alloc.page_table_row("s"))[None]
            cache, logits = prefill(
                cfg, cache_cfg, params, cache,
                jnp.asarray(prompt)[None],
                jnp.asarray([40], jnp.int32), row)
            steps = [np.asarray(logits)]
            pos = 40
            for t in (11, 12, 13):
                cache, lg = decode_step(
                    cfg, cache_cfg, params, cache,
                    jnp.asarray([t], jnp.int32),
                    jnp.asarray([pos], jnp.int32), row,
                    jnp.ones((1,), bool))
                steps.append(np.asarray(lg))
                pos += 1
            outs[impl] = steps
        for a, b in zip(outs["reference"], outs["flash"]):
            np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-3)

    def test_window_actually_changes_logits(self):
        """The same weights WITHOUT the window must differ once context
        exceeds the window — proves the band is live, not decorative."""
        cache_cfg = CacheConfig(n_pages=17, page_size=16, max_pages_per_seq=4)
        rng = np.random.default_rng(1)
        prompt = rng.integers(1, MISTRAL.vocab_size, 48, dtype=np.int32)

        def last_logits(cfg):
            params = init_params(cfg, jax.random.key(0))
            cache = init_kv_cache(cfg, cache_cfg)
            alloc = PageAllocator(cache_cfg)
            alloc.allocate("s", 49)
            row = jnp.asarray(alloc.page_table_row("s"))[None]
            _, logits = prefill(
                cfg, cache_cfg, params, cache, jnp.asarray(prompt)[None],
                jnp.asarray([48], jnp.int32), row)
            return np.asarray(logits)

        windowed = last_logits(dataclasses.replace(MISTRAL, dtype="float32"))
        full = last_logits(dataclasses.replace(
            MISTRAL, dtype="float32", sliding_window=None))
        assert not np.allclose(windowed, full, atol=1e-3)


class TestEngineMistral:
    def test_serves_end_to_end_with_long_context(self):
        """mistral-tiny generates past the window boundary; portable and
        kernel paths agree token-for-token (greedy)."""
        cache_cfg = CacheConfig(n_pages=33, page_size=16, max_pages_per_seq=8)
        rng = np.random.default_rng(3)
        prompt = rng.integers(1, MISTRAL.vocab_size, 50).tolist()

        def run(impl):
            cfg = dataclasses.replace(MISTRAL, attn_impl=impl, dtype="float32")
            eng = NativeEngine(cfg, cache_cfg=cache_cfg, max_batch_size=2,
                               seed=0)
            eng.add_request(Request(
                request_id="r", prompt_tokens=list(prompt),
                params=SamplingParams(max_tokens=12, temperature=0.0)))
            toks = []
            for _ in range(40):
                if not eng.has_work():
                    break
                toks += [o.token for o in eng.step() if o.request_id == "r"]
            assert not eng.has_work()
            return toks

        a, b = run("reference"), run("flash")
        assert len(a) == 12
        assert a == b

    def test_spec_decode_composes_with_window(self):
        cache_cfg = CacheConfig(n_pages=33, page_size=16, max_pages_per_seq=8)
        cfg = dataclasses.replace(MISTRAL, dtype="float32")
        base = NativeEngine(cfg, cache_cfg=cache_cfg, max_batch_size=2, seed=0)
        spec = NativeEngine(cfg, cache_cfg=cache_cfg, max_batch_size=2, seed=0,
                            speculative_k=4)

        def run(eng):
            eng.add_request(Request(
                request_id="r", prompt_tokens=[5, 6, 7] * 12,
                params=SamplingParams(max_tokens=10, temperature=0.0)))
            toks = []
            for _ in range(40):
                if not eng.has_work():
                    break
                toks += [o.token for o in eng.step()]
            return toks

        assert run(base) == run(spec)
