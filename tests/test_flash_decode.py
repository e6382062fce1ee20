"""Flash-decode KV-split grid + fused lm_head→top-k sampling (r15).

Two bit-identity contracts pinned here:

* **KV-split**: ``ragged_paged_attention_kvsplit`` emits partials at a
  FIXED virtual-chunk granularity and combines them in a fixed order,
  so split counts 1/2/4/8 produce the same bits — greedy and
  seeded-sampled engine streams included, int8 KV included, mixed
  ragged batches (decode + spec-verify + chunk rows) included.
  Oversized VMEM configs demote to the single-walk grid.
* **Fused sampling**: eligible decode batches sample from blocked
  lm_head candidates (``ops/lm_head_topk.py``) without materializing
  ``[rows, V]`` logits; the unfused path computes the same candidates
  from full logits and both feed ONE candidate sampler, so streams are
  bit-identical — pinned across greedy / seeded top-k / penalties /
  min-tokens / int8-KV, with the jaxpr shape-discipline probe proving
  no [rows, V] intermediate exists on the fused path, and explicit
  fallbacks (logprobs / logit_bias / min_p) taking the unfused path.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fusioninfer_tpu.engine.engine import NativeEngine, Request
from fusioninfer_tpu.engine.kv_cache import CacheConfig
from fusioninfer_tpu.engine.sampler import (
    SamplingParams,
    apply_penalties,
    make_row_keys,
    sample,
    sample_topk,
)
from fusioninfer_tpu.models.config import get_preset
from fusioninfer_tpu.ops.lm_head_topk import (
    LM_HEAD_TOPK,
    lm_head_topk,
)
from fusioninfer_tpu.ops.paged_attention import (
    KV_SPLIT_CHUNKS,
    KV_SPLIT_MIN_CTX_TOKENS,
    pick_kv_splits,
    ragged_paged_attention,
    ragged_paged_attention_kvsplit,
    reference_ragged_paged_attention,
)

from test_paged_attention import (
    _MIXED,
    _STREAM_CASES,
    _ragged_setup,
    _stream_run,
    _stream_variant,
)

CFG = get_preset("qwen3-tiny")
CACHE = CacheConfig(n_pages=33, page_size=16, max_pages_per_seq=4)


# -- kernel tier -------------------------------------------------------


class TestKVSplitKernel:
    @pytest.mark.parametrize("kv_splits", [1, 2, 4, 8])
    def test_mixed_rows_match_oracle(self, kv_splits):
        q, kp, vp, tables, starts, qb, ql = _ragged_setup(**_MIXED)
        out = ragged_paged_attention_kvsplit(
            q, kp, vp, tables, starts, qb, ql, kv_splits=kv_splits,
            interpret=True)
        ref = reference_ragged_paged_attention(q, kp, vp, tables, starts,
                                               qb, ql)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_split_count_bit_identity_grid(self):
        """splits {1, 2, 4, 8} are bit-identical on the mixed shape,
        bf16 GQA, sliding-window and int8-scaled-page variants — the
        fixed-virtual-chunk construction, not float luck."""
        from fusioninfer_tpu.models.quantization import kv_quantize

        cases = []
        base = _ragged_setup(**_MIXED)
        cases.append(("f32", base, {}))
        cases.append(("bf16", _ragged_setup(
            q_lens=[1, 6], starts=[30, 9], KV=2, G=4,
            dtype=jnp.bfloat16, seed=7), {}))
        cases.append(("window", _ragged_setup(
            q_lens=[1, 6, 2], starts=[60, 24, 40], mp=6, seed=5,
            n_pages=17), {"window": 24}))
        for name, ops, kw in cases:
            q, kp, vp, tables, starts, qb, ql = ops
            outs = {s: np.asarray(ragged_paged_attention_kvsplit(
                q, kp, vp, tables, starts, qb, ql, kv_splits=s,
                interpret=True, **kw)) for s in (1, 2, 4, 8)}
            for s in (2, 4, 8):
                np.testing.assert_array_equal(outs[s], outs[1], err_msg=name)
        q, kp, vp, tables, starts, qb, ql = _ragged_setup(**_MIXED, seed=11)
        k8, k_s = kv_quantize(kp)
        v8, v_s = kv_quantize(vp)
        outs = {s: np.asarray(ragged_paged_attention_kvsplit(
            q, k8, v8, tables, starts, qb, ql,
            k_s[:, :, None, :], v_s[:, :, None, :], kv_splits=s,
            interpret=True)) for s in (1, 2, 4)}
        np.testing.assert_array_equal(outs[2], outs[1])
        np.testing.assert_array_equal(outs[4], outs[1])

    def test_split_agrees_with_single_walk(self):
        """Numeric (tolerance) agreement with the single-walk grid —
        the two paths are different float schedules of one math."""
        q, kp, vp, tables, starts, qb, ql = _ragged_setup(**_MIXED)
        split = np.asarray(ragged_paged_attention_kvsplit(
            q, kp, vp, tables, starts, qb, ql, kv_splits=8,
            interpret=True))
        walk = np.asarray(ragged_paged_attention(
            q, kp, vp, tables, starts, qb, ql, interpret=True))
        np.testing.assert_allclose(split, walk, atol=2e-5, rtol=2e-5)

    def test_stacked_layer_operand(self):
        L = 3
        ops = [_ragged_setup(**_MIXED, seed=20 + layer) for layer in range(L)]
        k_stack = jnp.stack([o[1] for o in ops])
        v_stack = jnp.stack([o[2] for o in ops])
        for layer in range(L):
            q, kp, vp, tables, starts, qb, ql = ops[layer]
            out = ragged_paged_attention_kvsplit(
                q, k_stack, v_stack, tables, starts, qb, ql,
                kv_splits=4, interpret=True, layer=jnp.int32(layer))
            ref = ragged_paged_attention_kvsplit(
                q, kp, vp, tables, starts, qb, ql,
                kv_splits=4, interpret=True)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_vmem_guard_demotes_to_single_walk(self, monkeypatch):
        """An oversized split config must never enter the KV-split
        kernel: the guard demotes to the single-walk grid (whose own
        guard handles per-head demotion), still matching the oracle."""
        from fusioninfer_tpu.ops import paged_attention as pa

        def bomb(*a, **k):
            raise AssertionError("kvsplit kernel entered despite "
                                 "over-budget scratch")

        monkeypatch.setattr(pa, "_ragged_kernel_kvsplit", bomb)
        monkeypatch.setattr(pa, "_COALESCE_VMEM_SCRATCH_BUDGET", 1024)
        q, kp, vp, tables, starts, qb, ql = _ragged_setup(**_MIXED)
        out = pa.ragged_paged_attention_kvsplit.__wrapped__(
            q, kp, vp, tables, starts, qb, ql, kv_splits=8,
            interpret=True)
        ref = reference_ragged_paged_attention(q, kp, vp, tables, starts,
                                               qb, ql)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("case", list(_STREAM_CASES))
    def test_page_stream_split_count_bit_identity(self, case):
        """Each split's page stream runs through other walks of other
        rows, tile after tile; the partial a (tile, row, chunk) emits
        is the same bits whichever split's stream brought its pages:
        splits {1, 2, 4, 8} agree bit for bit on rows that meet every
        boundary (inert rows, chunks past a row's live pages, a row over
        three tiles, a tile of eight decode rows, one-page rows), and
        with the oracle."""
        from fusioninfer_tpu.ops import paged_attention as pa

        args = _ragged_setup(**_STREAM_CASES[case], seed=2)
        outs = {s: _stream_run(pa, f"split{s}", args) for s in (1, 2, 4, 8)}
        for s in (2, 4, 8):
            np.testing.assert_array_equal(outs[s], outs[1])
        ref = reference_ragged_paged_attention(*args)
        np.testing.assert_allclose(outs[8], np.asarray(ref), atol=2e-5,
                                   rtol=2e-5)

    @pytest.mark.parametrize("kv_splits", [1, 2, 8])
    @pytest.mark.parametrize("variant", ["window", "int8", "bf16"])
    def test_page_stream_ring_depth_bit_identity(self, variant, kv_splits,
                                                 monkeypatch):
        """Ring depth 2 against the shipped one at every split count,
        for sliding-window, int8-page and bfloat16 (native dot) rows."""
        from fusioninfer_tpu.ops import paged_attention as pa

        args, scales, kw = _stream_variant(variant)
        grid = f"split{kv_splits}"
        shipped = _stream_run(pa, grid, args, scales, **kw)
        monkeypatch.setattr(pa, "RAGGED_RING_SLOTS", 2)
        np.testing.assert_array_equal(
            _stream_run(pa, grid, args, scales, **kw), shipped)

    def test_pick_kv_splits_heuristic(self):
        """Static config decides: below the context floor the single
        walk (existing families untouched), at/above it the full split
        fan-out — never a per-batch choice."""
        ps = 128
        short = KV_SPLIT_MIN_CTX_TOKENS // ps - 1
        assert pick_kv_splits(short, ps) == 0
        assert pick_kv_splits(short + 1, ps) == KV_SPLIT_CHUNKS
        assert pick_kv_splits(4, 16) == 0  # the test-tier cache config


# -- engine tier: KV-split streams ------------------------------------


def _drain(engine, reqs, max_steps=400):
    for r in reqs:
        engine.add_request(r)
    outs: dict = {}
    steps = 0
    while engine.has_work() and steps < max_steps:
        for o in engine.step():
            outs.setdefault(o.request_id, []).append(o.token)
        steps += 1
    return outs


def _mixed_reqs(int8=False):
    """Greedy + seeded-sampled rows with prompts long enough that
    chunked prefill packs chunk rows beside decode rows."""
    return [
        Request("g", list(range(1, 20)),
                SamplingParams(temperature=0.0, max_tokens=6)),
        Request("s", [2, 7, 1, 8, 3, 9, 4, 6, 5, 1, 2, 7],
                SamplingParams(temperature=0.9, top_k=12, top_p=0.9,
                               seed=7, max_tokens=6)),
        Request("s2", [9, 2, 6, 1],
                SamplingParams(temperature=0.7, top_k=40, seed=11,
                               max_tokens=6)),
    ]


def _flash_engine(**over):
    kw = dict(
        cfg=dataclasses.replace(CFG, attn_impl="flash"),
        cache_cfg=CACHE, max_batch_size=4, seed=0, prefill_chunk_size=8)
    kw.update(over)
    return NativeEngine(**kw)


class TestKVSplitEngineStreams:
    """Stream bit-identity ACROSS SPLIT COUNTS — the contract is
    splits {1, 2, 4} of the KV-split path agree bit for bit (the
    fixed-chunk construction); split=1 vs the retired-for-long-context
    single walk (kv_splits=0) agree only to float tolerance, like any
    two schedules of one math, and the kernel tier pins that."""

    @pytest.mark.parametrize("kv_splits", [2, 4])
    def test_streams_bit_identical_across_splits(self, kv_splits):
        """Mixed ragged batches (decode + chunk rows) through the
        kernel path: greedy AND seeded-sampled streams are split-count
        invariant."""
        base = _drain(_flash_engine(kv_splits=1), _mixed_reqs())
        split = _drain(_flash_engine(kv_splits=kv_splits), _mixed_reqs())
        assert split == base

    def test_streams_bit_identical_int8_kv(self):
        cache8 = dataclasses.replace(CACHE, kv_dtype="int8")
        base = _drain(_flash_engine(cache_cfg=cache8, kv_splits=1),
                      _mixed_reqs())
        split = _drain(_flash_engine(cache_cfg=cache8, kv_splits=4),
                       _mixed_reqs())
        assert split == base

    def test_streams_bit_identical_with_spec_rows(self):
        """Spec-verify windows ride the same ragged dispatch: a
        speculative engine's greedy streams are split-count invariant."""
        def reqs():
            return [Request("a", [3, 1, 4, 1, 5, 9, 2, 6] * 2,
                            SamplingParams(temperature=0.0, max_tokens=10)),
                    Request("b", [2, 7, 1, 8, 2, 8] * 2,
                            SamplingParams(temperature=0.0, max_tokens=10))]
        base = _drain(_flash_engine(kv_splits=1, speculative_k=3), reqs())
        split = _drain(_flash_engine(kv_splits=4, speculative_k=3), reqs())
        assert split == base

    def test_auto_resolution_is_static_config(self):
        assert _flash_engine()._kv_splits == 0  # 64-token max context
        long_cache = CacheConfig(n_pages=2049, page_size=128,
                                 max_pages_per_seq=32)
        assert _flash_engine(cache_cfg=long_cache)._kv_splits == \
            KV_SPLIT_CHUNKS


# -- fused lm_head→top-k sampling --------------------------------------


def _sampling_reqs():
    return [
        Request("g", [3, 1, 4, 1, 5],
                SamplingParams(temperature=0.0, max_tokens=6)),
        Request("pen", [2, 7, 1, 8],
                SamplingParams(temperature=0.9, top_k=12, seed=7,
                               presence_penalty=0.4, frequency_penalty=0.2,
                               repetition_penalty=1.2, max_tokens=6)),
        Request("mint", [9, 2, 6],
                SamplingParams(temperature=0.8, top_k=LM_HEAD_TOPK,
                               seed=11, min_tokens=4, max_tokens=6,
                               stop_token_ids=(5,))),
        Request("tp", [4, 4, 2],
                SamplingParams(temperature=0.7, top_k=8, top_p=0.85,
                               seed=13, max_tokens=6)),
    ]


class TestFusedSampling:
    def test_streams_bit_identical_vs_unfused(self):
        a = _drain(_flash_engine(fused_sampling=True), _sampling_reqs())
        b = _drain(_flash_engine(fused_sampling=False), _sampling_reqs())
        assert a == b

    def test_streams_bit_identical_int8_kv(self):
        cache8 = dataclasses.replace(CACHE, kv_dtype="int8")
        a = _drain(_flash_engine(cache_cfg=cache8, fused_sampling=True),
                   _sampling_reqs())
        b = _drain(_flash_engine(cache_cfg=cache8, fused_sampling=False),
                   _sampling_reqs())
        assert a == b

    def test_fused_path_actually_ran(self):
        eng = _flash_engine(fused_sampling=True)
        _drain(eng, _sampling_reqs())
        assert eng.fused_sampling_steps_total > 0

    @pytest.mark.parametrize("params,field", [
        (dict(temperature=0.0, logprobs=2), "logprobs"),
        (dict(temperature=0.8, top_k=4, seed=3,
              logit_bias=((7, 5.0),)), "logit_bias"),
        (dict(temperature=0.8, top_k=4, seed=3, min_p=0.05), "min_p"),
        (dict(temperature=0.8, seed=3), "unbounded top_k"),
        (dict(temperature=0.8, top_k=LM_HEAD_TOPK + 1, seed=3),
         "oversized top_k"),
    ])
    def test_fallback_rows_take_unfused_path(self, params, field):
        """Carve-outs are explicit: these rows must sample through the
        unfused path (fused_sampling_steps stays 0) and still stream —
        the full-logprobs fallback works end to end."""
        eng = _flash_engine(fused_sampling=True)
        outs = _drain(eng, [Request(
            "r", [3, 1, 4], SamplingParams(max_tokens=4, **params))])
        assert len(outs["r"]) == 4, field
        assert eng.fused_sampling_steps_total == 0, field

    def test_logprobs_fallback_returns_logprobs(self):
        eng = _flash_engine(fused_sampling=True)
        eng.add_request(Request(
            "lp", [3, 1, 4],
            SamplingParams(temperature=0.0, max_tokens=4, logprobs=2)))
        got = []
        while eng.has_work():
            for o in eng.step():
                got.append((o.logprob, o.top_logprobs))
        assert got and all(lp is not None and tops for lp, tops in got)

    def test_fused_sampling_off_for_spec_engines(self):
        eng = _flash_engine(fused_sampling=True, speculative_k=3)
        _drain(eng, [Request("a", [3, 1, 4, 1, 5, 9, 2, 6],
                             SamplingParams(temperature=0.0,
                                            max_tokens=8))])
        assert eng.fused_sampling_steps_total == 0


class TestLmHeadTopk:
    def _chain(self, N=5, D=32, V=777, seed=0):
        key = jax.random.key(seed)
        h = jax.random.normal(key, (N, D), jnp.float32)
        w = jax.random.normal(jax.random.key(seed + 1), (D, V),
                              jnp.float32)
        rng = np.random.default_rng(seed + 2)
        tc = jnp.asarray(rng.integers(0, 3, (N, V)), jnp.int32)
        oc = jnp.asarray(np.minimum(np.asarray(tc),
                                    rng.integers(0, 2, (N, V))), jnp.int32)
        pres = jnp.asarray(rng.random(N) * 0.5, jnp.float32)
        freq = jnp.asarray(rng.random(N) * 0.3, jnp.float32)
        rep = jnp.asarray(1.0 + rng.random(N) * 0.3, jnp.float32)
        early = jnp.asarray(rng.random(N) < 0.5)
        sup = jnp.asarray(rng.random((N, V)) < 0.01)
        chain = (h, w, tc, oc, pres, freq, rep, early, sup)
        return (*chain, self._penalized(*chain))

    @staticmethod
    def _product(h, mat, block_v=None):
        """``h @ mat``; ``block_v``: taken in column blocks of that
        width.  Each element is the same contraction either way, but a
        backend may sum it in another order at another width (jax 0.9's
        CPU backend: [5, 32] @ [32, 777] differs from its own 128- and
        256-column blocks by one float32 step in 33 of 3 885 elements,
        from 16-column blocks by up to three, from 250- and 4096-column
        blocks in none)."""
        if block_v is None:
            return h @ mat
        return jnp.concatenate(
            [h @ mat[:, lo:lo + block_v]
             for lo in range(0, mat.shape[1], block_v)], axis=1)

    @classmethod
    def _penalized(cls, h, mat, tc, oc, pres, freq, rep, early, sup,
                   block_v=None):
        """The unfused chain's penalized logits over ``_product``."""
        logits = apply_penalties(
            cls._product(h, mat, block_v).astype(jnp.float32), tc, oc, pres,
            freq, rep)
        return jnp.where(early[:, None] & sup, -jnp.inf, logits)

    def _assert_candidates(self, got, chain, mat, block_v, k=LM_HEAD_TOPK):
        """``got`` = the blocked kernel's ``(vals, idx)``.  The running
        top-k is exact: bit for bit ``lax.top_k`` over the penalized
        logits of the same column blocks, values AND indices, ties
        included.  Against the ONE full product it picks the same
        indices in the same order, and the two products (all that
        differs between the paths) lie at every candidate within one
        float32 ulp of what the dot sums, ``|h| @ |mat|`` (a sum that
        cancels has a small value and large terms: an ulp of the value
        is not the scale of a reordering)."""
        h, _, *rest = chain
        bv, bi = (np.asarray(x) for x in got)
        fv, fi = jax.lax.top_k(
            self._penalized(h, mat, *rest, block_v=block_v), k)
        np.testing.assert_array_equal(bv, np.asarray(fv))
        np.testing.assert_array_equal(bi, np.asarray(fi))
        _, fi = jax.lax.top_k(self._penalized(h, mat, *rest), k)
        np.testing.assert_array_equal(bi, np.asarray(fi))
        one, blocks, terms = (
            np.take_along_axis(np.asarray(x), bi, 1)
            for x in (self._product(h, mat), self._product(h, mat, block_v),
                      jnp.abs(h) @ jnp.abs(mat)))
        assert (np.abs(blocks - one) <= np.spacing(terms)).all()

    @pytest.mark.parametrize("block_v", [128, 250, 4096])
    def test_blocked_candidates_match_full_topk_bits(self, block_v):
        """The tentpole's exactness claim: the vocab-blocked running
        top-k equals lax.top_k over the full penalized logits — values
        AND indices, ties included — at any block width."""
        *chain, _ = self._chain()
        got = lm_head_topk(*chain, tied=False, block_v=block_v)
        self._assert_candidates(got, chain, chain[1], block_v)

    def test_quantized_and_tied_heads(self):
        from fusioninfer_tpu.models.quantization import (
            dequantize,
            quantize_int8,
            quantize_rows,
        )

        *chain, _ = self._chain()
        h, w, *rest = chain
        for head, tied, mat in [
            (w.T, True, w),
            (quantize_int8(w), False,
             dequantize(quantize_int8(w), jnp.float32)),
            (quantize_rows(w.T), True,
             dequantize(quantize_rows(w.T), jnp.float32).T),
        ]:
            got = lm_head_topk(h, head, *rest, tied=tied, block_v=256)
            self._assert_candidates(got, chain, mat, 256)

    def test_sample_topk_parity_with_sample(self):
        """sample(mode="topk") over full logits == sample_topk over the
        blocked candidates, row for row, greedy rows included."""
        h, w, tc, oc, pres, freq, rep, early, sup, logits = self._chain()
        N = logits.shape[0]
        keys = make_row_keys(jnp.arange(N, dtype=jnp.uint32) + 3,
                             jnp.zeros((N,), jnp.int32))
        temps = jnp.asarray([0.0, 0.8, 1.2, 0.9, 0.7], jnp.float32)
        topk = jnp.asarray([0, 12, 40, 5, LM_HEAD_TOPK], jnp.int32)
        topp = jnp.asarray([1.0, 0.9, 1.0, 0.8, 0.95], jnp.float32)
        full = sample(logits, keys, temps, topk, topp,
                      jnp.zeros((N,)), mode="topk")
        bv, bi = lm_head_topk(h, w, tc, oc, pres, freq, rep, early, sup,
                              tied=False, block_v=256)
        cand = sample_topk(bv, bi, keys, temps, topk, topp, mode="topk")
        np.testing.assert_array_equal(np.asarray(full), np.asarray(cand))
        greedy = np.asarray(jnp.argmax(logits, axis=-1))
        assert int(np.asarray(cand)[0]) == int(greedy[0])

    def test_candidate_rows_immune_to_batch_mode(self):
        """A seeded candidate-eligible row draws the SAME token whether
        its batch compiled as "topk" or as "filtered" (a min_p neighbor
        forces the general mode) — mid-stream admissions must never
        flip a seeded stream's bits (the round-1 batch-composition
        contract, re-pinned for the candidate path)."""
        h, w, tc, oc, pres, freq, rep, early, sup, logits = self._chain(
            N=2)
        keys = make_row_keys(jnp.asarray([5, 6], jnp.uint32),
                             jnp.zeros((2,), jnp.int32))
        temps = jnp.asarray([0.9, 0.8], jnp.float32)
        topk = jnp.asarray([12, 0], jnp.int32)
        topp = jnp.asarray([0.9, 0.9], jnp.float32)
        # row 1 carries min_p → the batch mode is "filtered"
        minp = jnp.asarray([0.0, 0.05], jnp.float32)
        mixed = sample(logits, keys, temps, topk, topp, minp,
                       mode="filtered")
        solo = sample(logits[:1], keys[:1], temps[:1], topk[:1],
                      topp[:1], jnp.zeros((1,)), mode="topk")
        assert int(np.asarray(mixed)[0]) == int(np.asarray(solo)[0])

    def test_top_k_one_is_greedy(self):
        h, w, tc, oc, pres, freq, rep, early, sup, logits = self._chain()
        N = logits.shape[0]
        keys = make_row_keys(jnp.arange(N, dtype=jnp.uint32),
                             jnp.zeros((N,), jnp.int32))
        out = sample(logits, keys, jnp.full((N,), 0.9),
                     jnp.ones((N,), jnp.int32), jnp.ones((N,)),
                     jnp.zeros((N,)), mode="topk")
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(jnp.argmax(logits, -1)))

    def test_vocab_smaller_than_cap(self):
        """V < LM_HEAD_TOPK clamps the candidate set to V exactly like
        full top_k would (16-column blocks: three float32 steps from the
        one full product on this backend, so only the blocks' own logits
        are compared, bit for bit)."""
        *chain, _ = self._chain(V=40)
        fv, fi = jax.lax.top_k(self._penalized(*chain, block_v=16), 40)
        bv, bi = lm_head_topk(*chain, tied=False, block_v=16)
        np.testing.assert_array_equal(np.asarray(bv), np.asarray(fv))
        np.testing.assert_array_equal(np.asarray(bi), np.asarray(fi))

    def test_tp_candidates_match_single_device(self):
        """The collective top-k merge: per-vocab-shard candidates
        rebased + all_gathered in shard order reduce to the
        single-device candidate bits (the sharded.py wrapper)."""
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices for a tp mesh")
        from jax.sharding import Mesh

        from fusioninfer_tpu.ops.sharded import lm_head_topk_tp

        mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
        h, w, tc, oc, pres, freq, rep, early, sup, _ = self._chain(
            V=768)
        sv, si = lm_head_topk(h, w, tc, oc, pres, freq, rep, early, sup,
                              tied=False, block_v=128)
        tv, ti = lm_head_topk_tp(mesh, h, w, tc, oc, pres, freq, rep,
                                 early, sup, tied=False, block_v=128)
        np.testing.assert_array_equal(np.asarray(tv), np.asarray(sv))
        np.testing.assert_array_equal(np.asarray(ti), np.asarray(si))


class TestShapeDiscipline:
    """The acceptance pin: no [rows, V] logits tensor exists anywhere
    on the fused-sampling path — asserted on the jaxprs, not inferred
    from counters."""

    def _assert_no_aval(self, jaxpr, shape):
        """No FLOAT tensor of ``shape`` anywhere in the jaxpr — int32
        penalty-count and bool suppression operands are legitimately
        [rows, V]; the contract bans the float LOGITS rectangle."""
        def walk(jx):
            for eqn in jx.eqns:
                for var in list(eqn.outvars) + list(eqn.invars):
                    aval = getattr(var, "aval", None)
                    if (aval is not None
                            and tuple(getattr(aval, "shape", ())) == shape
                            and jnp.issubdtype(
                                getattr(aval, "dtype", jnp.int32),
                                jnp.floating)):
                        raise AssertionError(
                            f"float {shape} tensor found in jaxpr: {eqn}")
                for sub in eqn.params.values():
                    if hasattr(sub, "jaxpr"):
                        walk(sub.jaxpr)
                    elif isinstance(sub, (list, tuple)):
                        for s in sub:
                            if hasattr(s, "jaxpr"):
                                walk(s.jaxpr)
        walk(jaxpr.jaxpr)

    def test_lm_head_topk_never_holds_rows_by_vocab(self):
        N, D, V = 6, 32, 1000
        h = jnp.zeros((N, D), jnp.float32)
        w = jnp.zeros((D, V), jnp.float32)
        counts = jnp.zeros((N, V), jnp.int32)
        row = jnp.zeros((N,), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda *a: lm_head_topk(*a, tied=False, block_v=128))(
            h, w, counts, counts, row, row, row,
            jnp.zeros((N,), bool), jnp.zeros((N, V), bool))
        self._assert_no_aval(jaxpr, (N, V))

    def test_fused_step_decode_hidden_never_projects_decode_rows(self):
        """fused_step with decode_hidden=True must not contain a
        [B·W, V] tensor — the decode group's lm_head is gone; only the
        chunk group's [NC, V] logits remain (NC != B·W here so the
        shapes are distinguishable)."""
        from fusioninfer_tpu.engine.model_runner import fused_step

        cfg = CFG.validate()
        cc = CACHE.validate()
        B, W, NC, R, T, mp = 4, 1, 8, 16, 16, cc.max_pages_per_seq
        V = cfg.vocab_size
        from fusioninfer_tpu.models.transformer import init_params

        params = init_params(cfg, jax.random.key(0))
        from fusioninfer_tpu.engine.kv_cache import init_kv_cache

        cache = init_kv_cache(cfg, cc)
        i32 = jnp.int32
        args = (jnp.zeros((T,), i32), jnp.zeros((R,), i32),
                jnp.zeros((R,), i32), jnp.zeros((R,), i32),
                jnp.full((R, mp), cc.trash_page, i32),
                jnp.zeros((B, W), i32), jnp.zeros((NC,), i32))
        jaxpr = jax.make_jaxpr(
            lambda p, c, *a: fused_step.__wrapped__(
                cfg, cc, p, c, *a, coalesce=False,
                decode_hidden=True))(params, cache, *args)
        self._assert_no_aval(jaxpr, (B * W, V))
        self._assert_no_aval(jaxpr, (B, W, V))
        # the unfused variant DOES hold the decode logits — the probe
        # can tell the difference (self-test of the assertion)
        jaxpr_unfused = jax.make_jaxpr(
            lambda p, c, *a: fused_step.__wrapped__(
                cfg, cc, p, c, *a, coalesce=False,
                decode_hidden=False))(params, cache, *args)
        with pytest.raises(AssertionError):
            self._assert_no_aval(jaxpr_unfused, (B * W, V))


class TestSampleModeSelection:
    def _mode(self, *params):
        return NativeEngine._sample_mode(iter(params))

    def test_modes(self):
        P = SamplingParams
        assert self._mode(P(temperature=0.0)) == "greedy"
        assert self._mode(P(temperature=0.8)) == "plain"
        assert self._mode(P(temperature=0.8, top_k=12)) == "topk"
        assert self._mode(P(temperature=0.8, top_k=12),
                          P(temperature=0.0)) == "topk"
        # a plain row + a topk row need the general path
        assert self._mode(P(temperature=0.8, top_k=12),
                          P(temperature=0.8)) == "filtered"
        assert self._mode(
            P(temperature=0.8, top_k=LM_HEAD_TOPK + 1)) == "filtered"
        assert self._mode(
            P(temperature=0.8, top_k=12, min_p=0.05)) == "filtered"
        # bounded top-k + nucleus stays candidate-eligible
        assert self._mode(
            P(temperature=0.8, top_k=12, top_p=0.9)) == "topk"
