"""Hardware kernel tests: Pallas kernels with ``interpret=False`` on a TPU.

Run through the chip tool (``make test-tpu`` sets
``FUSIONINFER_TEST_TPU=1`` so the root conftest leaves the TPU backend
in place); skipped in the CPU tiers.  With the variable set and no TPU
the module FAILS at import — a hardware tier that skips where there is
no hardware reports a pass it never ran.  These exist because a
paged-attention layout Mosaic rejects once shipped while every in-repo
kernel test passed, all of them ``interpret=True``.  The shapes are
``engine serve qwen3-1.7b``'s own (bf16, KV=8, G=2, Hd=128,
page_size=128, 32-page tables at ``--max-model-len 4096``), its int8
page layout and its per-shard shapes under ``--tensor-parallel-size 4``
(KV=2, G=2), plus non-multiple-of-8 lengths, so a kernel that cannot
compile on hardware fails HERE, not in front of a request.
"""

import os

import pytest

_ON_TPU_TIER = os.environ.get("FUSIONINFER_TEST_TPU", "") == "1"

pytestmark = pytest.mark.skipif(
    not _ON_TPU_TIER,
    reason="hardware tier: run via make test-tpu on a TPU host",
)

if _ON_TPU_TIER:
    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        raise RuntimeError(
            "FUSIONINFER_TEST_TPU=1 but the default backend is "
            f"{jax.default_backend()!r}: the hardware tier runs on a TPU "
            "or fails")


def _int8_pages(kp, vp):
    """bf16 pages → (int8 pages, [KV, n_pages, 1, ps] scale rows, the
    dequantized bf16 pages an oracle reads)."""
    import jax.numpy as jnp

    from fusioninfer_tpu.models.quantization import kv_quantize

    k8, ksc = kv_quantize(kp)
    v8, vsc = kv_quantize(vp)
    kd = (k8.astype(jnp.float32) * ksc[..., None]).astype(jnp.bfloat16)
    vd = (v8.astype(jnp.float32) * vsc[..., None]).astype(jnp.bfloat16)
    return (k8, v8), (ksc[:, :, None, :], vsc[:, :, None, :]), (kd, vd)


class TestFlashAttentionHW:
    def test_bench_shapes_bf16_causal(self):
        from fusioninfer_tpu.ops.flash_attention import (
            flash_attention,
            reference_attention,
        )

        B, S, H, KV, Hd = 1, 1024, 16, 8, 128
        ks = jax.random.split(jax.random.key(1), 3)
        q = jax.random.normal(ks[0], (B, S, H, Hd), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, S, KV, Hd), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, S, KV, Hd), jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True, interpret=False)
        out.block_until_ready()
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=5e-2, rtol=5e-2,
        )

    def test_small_pow2_bucket(self):
        """Smallest prefill bucket (32) — block sizes clamp below 128."""
        from fusioninfer_tpu.ops.flash_attention import (
            flash_attention,
            reference_attention,
        )

        B, S, H, KV, Hd = 2, 32, 4, 2, 128
        ks = jax.random.split(jax.random.key(2), 3)
        q = jax.random.normal(ks[0], (B, S, H, Hd), jnp.bfloat16)
        k = jax.random.normal(ks[1], (B, S, KV, Hd), jnp.bfloat16)
        v = jax.random.normal(ks[2], (B, S, KV, Hd), jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True, interpret=False)
        out.block_until_ready()
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=5e-2, rtol=5e-2,
        )


class TestDecodeStepHW:
    def test_decode_step_kernel_path_compiles(self):
        """End-to-end decode_step with attn_impl=flash at small-model
        shapes but REAL page/head dims — the integration the bench runs."""
        import dataclasses

        from fusioninfer_tpu.engine.kv_cache import (
            CacheConfig,
            PageAllocator,
            init_kv_cache,
        )
        from fusioninfer_tpu.engine.model_runner import decode_step
        from fusioninfer_tpu.models.config import get_preset
        from fusioninfer_tpu.models.transformer import init_params

        cfg = dataclasses.replace(
            get_preset("qwen3-tiny"),
            n_heads=16, n_kv_heads=8, head_dim=128, attn_impl="flash",
        )
        cache_cfg = CacheConfig(n_pages=17, page_size=128, max_pages_per_seq=4)
        params = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(0))
        cache = init_kv_cache(cfg, cache_cfg)
        B = 4
        alloc = PageAllocator(cache_cfg)
        tables = np.zeros((B, cache_cfg.max_pages_per_seq), np.int32)
        for i in range(B):
            alloc.allocate(str(i), 200)
            tables[i] = alloc.page_table_row(str(i))
        cache, logits = decode_step(
            cfg, cache_cfg, params, cache,
            jnp.arange(B, dtype=jnp.int32),
            jnp.full((B,), 150, jnp.int32),
            jnp.asarray(tables),
            jnp.ones((B,), bool),
        )
        logits.block_until_ready()
        assert logits.shape == (B, cfg.vocab_size)
        assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


class TestRaggedPagedAttentionHW:
    """The one true ragged kernel with interpret=False at serving
    shapes: a Mosaic rejection of the flat-tile layout must fail here,
    not in front of a request."""

    def _ragged(self, q_lens, starts, seed, KV=8, G=2, Hd=128, ps=128,
                n_pages=257, mp=8):
        q_lens = np.asarray(q_lens, np.int32)
        starts = np.asarray(starts, np.int32)
        q_begins = np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(
            np.int32)
        T = int(q_lens.sum())
        H = KV * G
        ks = jax.random.split(jax.random.key(seed), 3)
        q = jax.random.normal(ks[0], (T, H, Hd), jnp.bfloat16)
        kp = jax.random.normal(ks[1], (KV, n_pages, ps, Hd), jnp.bfloat16)
        vp = jax.random.normal(ks[2], (KV, n_pages, ps, Hd), jnp.bfloat16)
        rng = np.random.default_rng(seed)
        tables = np.full((len(q_lens), mp), n_pages - 1, np.int32)
        perm = iter(rng.permutation(n_pages - 1))
        for r in range(len(q_lens)):
            need = -(-int(starts[r] + q_lens[r]) // ps) if q_lens[r] else 0
            for i in range(min(need, mp)):
                tables[r, i] = next(perm)
        return (q, kp, vp, jnp.asarray(tables), jnp.asarray(starts),
                jnp.asarray(q_begins), jnp.asarray(q_lens))

    @pytest.mark.parametrize("coalesce", [True, False])
    def test_tp4_shard_shapes_bf16(self, coalesce):
        """What one shard of ``--tensor-parallel-size 4`` runs: KV=2,
        G=2 (tiles whose two minor dims are (2, 128)), 32-page tables,
        rows reaching the end of a 4096-token context."""
        from fusioninfer_tpu.ops.paged_attention import (
            ragged_paged_attention,
            reference_ragged_paged_attention,
        )

        q, kp, vp, tables, starts, qb, ql = self._ragged(
            q_lens=[1, 1, 0, 40, 1], starts=[4000, 129, 0, 2500, 7],
            seed=35, KV=2, n_pages=129, mp=32)
        out = ragged_paged_attention(q, kp, vp, tables, starts, qb, ql,
                                     interpret=False, coalesce=coalesce)
        out.block_until_ready()
        ref = reference_ragged_paged_attention(q, kp, vp, tables, starts,
                                               qb, ql)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=5e-2, rtol=5e-2)

    @pytest.mark.parametrize("coalesce", [True, False])
    def test_int8_pages(self, coalesce):
        """``--kv-cache-dtype int8``: int8 pages + [KV, n_pages, 1, ps]
        scale rows through the ragged grids (the quantized DMA and the
        scale folded in after the dots) against the dequantized-page
        oracle."""
        from fusioninfer_tpu.ops.paged_attention import (
            ragged_paged_attention,
            reference_ragged_paged_attention,
        )

        q, kp, vp, tables, starts, qb, ql = self._ragged(
            q_lens=[1, 1, 0, 3, 200, 1], starts=[129, 7, 0, 500, 0, 1015],
            seed=37)
        pages8, scales, deq = _int8_pages(kp, vp)
        out = ragged_paged_attention(
            q, *pages8, tables, starts, qb, ql, *scales,
            interpret=False, coalesce=coalesce)
        out.block_until_ready()
        ref = reference_ragged_paged_attention(q, *deq, tables, starts,
                                               qb, ql)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=6e-2, rtol=6e-2)

    @pytest.mark.parametrize("coalesce", [True, False])
    def test_mixed_bench_shapes_bf16(self, coalesce):
        """Decode rows at ragged depths + a dead slot + a spec window +
        a 512-token chunk — the fused-step mix — must COMPILE on the
        chip and match the flat-gather oracle."""
        from fusioninfer_tpu.ops.paged_attention import (
            ragged_paged_attention,
            reference_ragged_paged_attention,
        )

        q, kp, vp, tables, starts, qb, ql = self._ragged(
            q_lens=[1, 1, 0, 3, 512, 1], starts=[129, 7, 0, 500, 0, 1015],
            seed=31)
        out = ragged_paged_attention(q, kp, vp, tables, starts, qb, ql,
                                     interpret=False, coalesce=coalesce)
        out.block_until_ready()
        ref = reference_ragged_paged_attention(q, kp, vp, tables, starts,
                                               qb, ql)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=5e-2, rtol=5e-2)

    @pytest.mark.parametrize("grid", ["coalesced", "per-head", "split8"])
    def test_sliding_window(self, grid):
        """Mistral-style banded attention at serving shapes: decode rows
        at ragged depths (a dead slot among them) and a chunk row from
        the middle of a prompt skip their out-of-window pages on every
        grid AND compile under Mosaic."""
        from fusioninfer_tpu.ops.paged_attention import (
            ragged_paged_attention,
            ragged_paged_attention_kvsplit,
            reference_ragged_paged_attention,
        )

        args = self._ragged(
            q_lens=[1, 1, 1, 1, 0, 1, 1, 1, 189],
            starts=[128, 999, 6, 0, 0, 127, 254, 512, 901], seed=7, mp=16)
        if grid == "split8":
            out = ragged_paged_attention_kvsplit(
                *args, kv_splits=8, window=300, interpret=False)
        else:
            out = ragged_paged_attention(
                *args, window=300, interpret=False,
                coalesce=grid == "coalesced")
        out.block_until_ready()
        ref = reference_ragged_paged_attention(*args, window=300)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=5e-2, rtol=5e-2)

    def test_five_token_windows_and_a_suffix_row(self):
        """``--speculative-ngram`` k=4 packs 1 + 4-token window rows:
        segments that are no multiple of the 8-token q tile, rows that
        share a tile, a dead slot between them — and a cache-hit suffix
        of 189 tokens from position 901 behind them."""
        from fusioninfer_tpu.ops.paged_attention import (
            ragged_paged_attention,
            reference_ragged_paged_attention,
        )

        args = self._ragged(
            q_lens=[5, 3, 1, 0, 5, 2, 4, 5, 189],
            starts=[0, 17, 127, 129, 500, 900, 1, 1018, 901], seed=9, mp=16)
        out = ragged_paged_attention(*args, interpret=False)
        out.block_until_ready()
        ref = reference_ragged_paged_attention(*args)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=5e-2, rtol=5e-2)

    def test_stacked_pools_layer_indexing(self):
        """The production in-place cache path: full [L, KV, ...] stacked
        pools + a layer scalar-prefetch operand must COMPILE under
        Mosaic (interpret=False) and read the right layer.  L=1
        auto-wrap shares the DMA slicing pattern, but multi-layer
        indexing on hardware is pinned only here."""
        from fusioninfer_tpu.ops.paged_attention import (
            ragged_paged_attention,
            reference_ragged_paged_attention,
        )

        L = 3
        ops = [self._ragged(q_lens=[1, 1, 0, 3, 40, 1],
                            starts=[129, 7, 0, 255, 100, 500],
                            seed=20 + layer, n_pages=33) for layer in range(L)]
        k_stack = jnp.stack([o[1] for o in ops])
        v_stack = jnp.stack([o[2] for o in ops])
        for layer, (q, kp, vp, *rows) in enumerate(ops):
            out = ragged_paged_attention(
                q, k_stack, v_stack, *rows, interpret=False,
                layer=jnp.int32(layer))
            out.block_until_ready()
            ref = reference_ragged_paged_attention(q, kp, vp, *rows)
            np.testing.assert_allclose(
                np.asarray(out, np.float32), np.asarray(ref, np.float32),
                atol=5e-2, rtol=5e-2)

    def test_inert_slots_zero(self):
        """A decode step's dead slots (``q_len`` 0 at their own flat
        offset, as ``decode_burst`` packs them) give exactly zero."""
        from fusioninfer_tpu.ops.paged_attention import ragged_paged_attention

        q, kp, vp, tables, starts, _, _ = self._ragged(
            q_lens=[1, 1, 1, 1], starts=[0, 199, 0, 63], seed=11, n_pages=33)
        out = np.asarray(ragged_paged_attention(
            q, kp, vp, tables, starts, jnp.arange(4, dtype=jnp.int32),
            jnp.asarray([0, 1, 0, 1], jnp.int32), interpret=False),
            np.float32)
        assert not out[0].any() and not out[2].any()
        assert out[1].any() and out[3].any()

    def test_decode_only_offset_invariance_bits(self):
        """The scorer-switch retirement contract ON HARDWARE: the same
        row packed solo vs among neighbors is bit-identical."""
        from fusioninfer_tpu.ops.paged_attention import ragged_paged_attention

        q, kp, vp, tables, starts, qb, ql = self._ragged(
            q_lens=[1, 1, 1, 1], starts=[129, 7, 500, 1015], seed=33)
        mixed = np.asarray(ragged_paged_attention(
            q, kp, vp, tables, starts, qb, ql, interpret=False))
        solo = np.asarray(ragged_paged_attention(
            q[2:3], kp, vp, tables[2:3], starts[2:3],
            jnp.zeros((1,), jnp.int32), ql[2:3], interpret=False))
        np.testing.assert_array_equal(solo[0], mixed[2])


class TestKVSplitHW:
    """The flash-decode KV-split grid (r15 tentpole) with
    interpret=False: the split grid's multi-output partial blocks must
    COMPILE under Mosaic, agree with the single walk numerically, and
    keep the split-count bit-identity + offset invariance the CPU tier
    pins in interpret mode."""

    def test_split_grid_bench_shapes_bf16(self):
        from fusioninfer_tpu.ops.paged_attention import (
            ragged_paged_attention,
            ragged_paged_attention_kvsplit,
        )

        helper = TestRaggedPagedAttentionHW()
        q, kp, vp, tables, starts, qb, ql = helper._ragged(
            q_lens=[1, 1, 0, 1, 1], starts=[1015, 129, 0, 500, 7],
            seed=41)
        outs = {}
        for s in (1, 2, 4, 8):
            o = ragged_paged_attention_kvsplit(
                q, kp, vp, tables, starts, qb, ql, kv_splits=s,
                interpret=False)
            o.block_until_ready()
            outs[s] = np.asarray(o, np.float32)
        # split-count bit-identity holds on hardware, not just in
        # interpret mode (the fixed-chunk construction is dtype- and
        # backend-agnostic, but Mosaic lowering must prove it)
        np.testing.assert_array_equal(outs[2], outs[1])
        np.testing.assert_array_equal(outs[4], outs[1])
        np.testing.assert_array_equal(outs[8], outs[1])
        base = np.asarray(ragged_paged_attention(
            q, kp, vp, tables, starts, qb, ql, interpret=False),
            np.float32)
        np.testing.assert_allclose(outs[1], base, atol=5e-2, rtol=5e-2)

    @pytest.mark.parametrize("kv_heads,int8", [(8, False), (2, False),
                                               (8, True)])
    def test_serve_default_split_grid(self, kv_heads, int8):
        """The grid ``engine serve`` takes by default: 8 splits over
        32-page tables (``--max-model-len 4096``) with rows deep enough
        that every chunk window holds pages — at full KV, at the tp=4
        per-shard KV=2 (m/l blocks whose two minor dims are (2, 2)) and
        over int8 pages — against the flat-gather oracle."""
        from fusioninfer_tpu.ops.paged_attention import (
            ragged_paged_attention_kvsplit,
            reference_ragged_paged_attention,
        )

        helper = TestRaggedPagedAttentionHW()
        q, kp, vp, tables, starts, qb, ql = helper._ragged(
            q_lens=[1, 1, 0, 40, 1], starts=[4000, 129, 0, 2500, 7],
            seed=45, KV=kv_heads, n_pages=129, mp=32)
        pages, scales, ref_pages = (_int8_pages(kp, vp) if int8
                                    else ((kp, vp), (), (kp, vp)))
        out = ragged_paged_attention_kvsplit(
            q, *pages, tables, starts, qb, ql, *scales, kv_splits=8,
            interpret=False)
        out.block_until_ready()
        ref = reference_ragged_paged_attention(q, *ref_pages, tables,
                                               starts, qb, ql)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=6e-2, rtol=6e-2)

    @pytest.mark.parametrize("slots", [2, 5])
    def test_page_stream_ring_depth_bits(self, slots, monkeypatch):
        """The page stream runs copies ahead through walk, row and tile
        boundaries — a race or a wrong slot shows only with real DMAs:
        decode rows, an inert row and a chunk row over five tiles give
        the same bits at another ring depth, on every grid."""
        from fusioninfer_tpu.ops import paged_attention as pa

        helper = TestRaggedPagedAttentionHW()
        args = helper._ragged(
            q_lens=[1, 1, 0, 40, 1], starts=[4000, 129, 0, 2500, 7],
            seed=47, n_pages=129, mp=32)

        def run():
            return [np.asarray(f(*args, interpret=False, **kw), np.float32)
                    for f, kw in (
                        (pa.ragged_paged_attention_kvsplit.__wrapped__,
                         {"kv_splits": 8}),
                        (pa.ragged_paged_attention.__wrapped__,
                         {"coalesce": True}),
                        (pa.ragged_paged_attention.__wrapped__,
                         {"coalesce": False}))]

        shipped = run()
        monkeypatch.setattr(pa, "RAGGED_RING_SLOTS", slots)
        for got, want in zip(run(), shipped):
            np.testing.assert_array_equal(got, want)

    def test_offset_invariance_bits_kvsplit(self):
        """The interpret=False twin of the split-axis extension of
        test_offset_and_neighbor_invariance_bit_identity."""
        from fusioninfer_tpu.ops.paged_attention import (
            ragged_paged_attention_kvsplit,
        )

        helper = TestRaggedPagedAttentionHW()
        q, kp, vp, tables, starts, qb, ql = helper._ragged(
            q_lens=[1, 1, 1, 1], starts=[129, 7, 500, 1015], seed=43)
        mixed = np.asarray(ragged_paged_attention_kvsplit(
            q, kp, vp, tables, starts, qb, ql, kv_splits=4,
            interpret=False))
        solo = np.asarray(ragged_paged_attention_kvsplit(
            q[2:3], kp, vp, tables[2:3], starts[2:3],
            jnp.zeros((1,), jnp.int32), ql[2:3], kv_splits=4,
            interpret=False))
        np.testing.assert_array_equal(solo[0], mixed[2])
