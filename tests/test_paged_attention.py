"""The ragged paged-attention family vs gather oracles (interpret mode).

Every paged forward of the engine scores through
``ragged_paged_attention`` / ``ragged_paged_attention_kvsplit``: the
kernels against the flat oracle on every grid, the page stream, the walk
lists, the VMEM guards — and the load-bearing bit-identity property: a
row's output bits are independent of its flat offset and tile
neighbors, so split and fused engine dispatches score identically.
Row kind by row kind against second oracles:
``tests/test_ragged_row_kinds.py``.
"""

import jax
import pytest
import jax.numpy as jnp
import numpy as np

from fusioninfer_tpu.ops.paged_attention import (
    ragged_paged_attention,
    ragged_token_rows,
    reference_ragged_paged_attention,
)


def _ragged_setup(q_lens, starts, KV=2, G=2, Hd=64, ps=16, n_pages=17,
                  mp=4, seed=0, dtype=jnp.float32):
    """Flat ragged operand set: rows with the given token counts and
    global start positions, each over its own permuted pages."""
    q_lens = np.asarray(q_lens, np.int32)
    starts = np.asarray(starts, np.int32)
    q_begins = np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(np.int32)
    T = int(q_lens.sum())
    H = KV * G
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (T, H, Hd), dtype)
    kp = jax.random.normal(ks[1], (KV, n_pages, ps, Hd), dtype)
    vp = jax.random.normal(ks[2], (KV, n_pages, ps, Hd), dtype)
    rng = np.random.default_rng(seed)
    tables = np.full((len(q_lens), mp), n_pages - 1, np.int32)
    perm = iter(rng.permutation(n_pages - 1))
    for r in range(len(q_lens)):
        need = -(-int(starts[r] + q_lens[r]) // ps) if q_lens[r] else 0
        for i in range(min(need, mp)):
            tables[r, i] = next(perm)
    return (q, kp, vp, jnp.asarray(tables), jnp.asarray(starts),
            jnp.asarray(q_begins), jnp.asarray(q_lens))


# the mixed fused-step shape: decode rows, a dead slot, a spec window,
# a budgeted chunk — T=15 also exercises the tile-multiple pad
_MIXED = dict(q_lens=[1, 0, 3, 10, 1], starts=[37, 0, 20, 5, 63])


class TestRaggedKernel:
    @pytest.mark.parametrize("coalesce", [False, True])
    def test_mixed_rows_match_oracle(self, coalesce):
        q, kp, vp, tables, starts, qb, ql = _ragged_setup(**_MIXED)
        out = ragged_paged_attention(q, kp, vp, tables, starts, qb, ql,
                                     interpret=True, coalesce=coalesce)
        ref = reference_ragged_paged_attention(q, kp, vp, tables, starts,
                                               qb, ql)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("coalesce", [False, True])
    def test_decode_only_rows(self, coalesce):
        """Pure decode (every q_len 1, one dead row) — the split decode
        dispatch's degenerate descriptor shape."""
        q, kp, vp, tables, starts, qb, ql = _ragged_setup(
            q_lens=[1, 1, 0, 1], starts=[12, 40, 0, 60], seed=3)
        out = ragged_paged_attention(q, kp, vp, tables, starts, qb, ql,
                                     interpret=True, coalesce=coalesce)
        ref = reference_ragged_paged_attention(q, kp, vp, tables, starts,
                                               qb, ql)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_gqa_bf16(self):
        q, kp, vp, tables, starts, qb, ql = _ragged_setup(
            q_lens=[1, 6], starts=[30, 9], KV=2, G=4, dtype=jnp.bfloat16,
            seed=7)
        out = ragged_paged_attention(q, kp, vp, tables, starts, qb, ql,
                                     interpret=True)
        ref = reference_ragged_paged_attention(q, kp, vp, tables, starts,
                                               qb, ql)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=4e-2, rtol=4e-2)

    @pytest.mark.parametrize("coalesce", [False, True])
    def test_sliding_window(self, coalesce):
        q, kp, vp, tables, starts, qb, ql = _ragged_setup(
            q_lens=[1, 6, 2], starts=[60, 24, 40], mp=6, seed=5,
            n_pages=17)
        out = ragged_paged_attention(q, kp, vp, tables, starts, qb, ql,
                                     interpret=True, window=24,
                                     coalesce=coalesce)
        ref = reference_ragged_paged_attention(q, kp, vp, tables, starts,
                                               qb, ql, window=24)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("coalesce", [False, True])
    @pytest.mark.parametrize("window", [None, 24])
    def test_int8_scaled_pages(self, window, coalesce):
        """Scale folding is exact against the oracle over the dequantized
        pages, and composes with the window: the walk starts at the
        window's first live page AND streams the int8 scale rows from
        the same offset."""
        (q, k8, v8, *rows), (k_s, v_s), kw = _stream_variant(
            "int8" if window is None else "int8+window")
        out = ragged_paged_attention(q, k8, v8, *rows, k_s, v_s,
                                     interpret=True, coalesce=coalesce, **kw)
        # oracle over the dequantized pages
        kd = k8.astype(jnp.float32) * k_s[:, :, 0, :, None]
        vd = v8.astype(jnp.float32) * v_s[:, :, 0, :, None]
        ref = reference_ragged_paged_attention(q, kd, vd, *rows, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-4, rtol=3e-4)

    @pytest.mark.parametrize("coalesce", [False, True])
    def test_stacked_layer_operand(self, coalesce):
        """The production path passes the FULL [L, KV, ...] stacked
        pools plus a layer scalar (the in-place cache design)."""
        L = 3
        ops = [_ragged_setup(**_MIXED, seed=20 + layer) for layer in range(L)]
        k_stack = jnp.stack([o[1] for o in ops])
        v_stack = jnp.stack([o[2] for o in ops])
        for layer in range(L):
            q, kp, vp, tables, starts, qb, ql = ops[layer]
            out = ragged_paged_attention(
                q, k_stack, v_stack, tables, starts, qb, ql,
                interpret=True, coalesce=coalesce, layer=jnp.int32(layer))
            ref = ragged_paged_attention(
                q, kp, vp, tables, starts, qb, ql,
                interpret=True, coalesce=coalesce)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.parametrize("kv_splits", [0, 1, 2, 4])
    def test_offset_and_neighbor_invariance_bit_identity(self, kv_splits):
        """THE property that retires the scorer switch: a row scored
        alone, and the same row packed among neighbors at a different
        flat offset, produce bit-identical outputs — so decode-only and
        fused mixed dispatches can never disagree.  The split-count
        axis extends the pin to the flash-decode KV-split grid
        (``kv_splits > 0``): its per-(tile, row, chunk) fresh
        accumulators and fixed-order combine preserve the same
        invariance at every split count (the interpret=False HW twin
        lives in tests/test_kernels_tpu.py)."""
        from fusioninfer_tpu.ops.paged_attention import (
            ragged_paged_attention_kvsplit,
        )

        def run(*a, **k):
            if kv_splits:
                return ragged_paged_attention_kvsplit(
                    *a, kv_splits=kv_splits, **k)
            return ragged_paged_attention(*a, **k)

        q, kp, vp, tables, starts, qb, ql = _ragged_setup(**_MIXED)
        mixed = np.asarray(run(q, kp, vp, tables, starts, qb, ql,
                               interpret=True))
        qb_h = np.asarray(qb)
        ql_h = np.asarray(ql)
        for r in [0, 2, 3]:
            seg = slice(int(qb_h[r]), int(qb_h[r] + ql_h[r]))
            solo = np.asarray(run(
                q[seg], kp, vp, tables[r: r + 1], starts[r: r + 1],
                jnp.zeros((1,), jnp.int32), ql[r: r + 1], interpret=True))
            np.testing.assert_array_equal(solo, mixed[seg])

    def test_stacked_requires_layer(self):
        """Stacked pools without ``layer`` and ``layer`` with one
        layer's pools both raise, on either wrapper."""
        from fusioninfer_tpu.ops.paged_attention import (
            ragged_paged_attention_kvsplit,
        )

        q, kp, vp, *rows = _ragged_setup(**_MIXED)
        for run in (ragged_paged_attention, ragged_paged_attention_kvsplit):
            with pytest.raises(ValueError, match="require layer"):
                run(q, jnp.stack([kp]), jnp.stack([vp]), *rows,
                    interpret=True)
            with pytest.raises(ValueError, match="only applies"):
                run(q, kp, vp, *rows, interpret=True, layer=0)

    def test_token_rows_zero_length_neighbors(self):
        """Token→row resolution must skip zero-length rows that share a
        begin with a live neighbor (dead decode slots)."""
        qb = jnp.asarray([0, 1, 1, 1, 4], jnp.int32)
        ql = jnp.asarray([1, 0, 0, 3, 0], jnp.int32)
        row_of, off, live = ragged_token_rows(qb, ql, 6)
        assert list(np.asarray(row_of)[:4]) == [0, 3, 3, 3]
        assert list(np.asarray(live)) == [True] * 4 + [False, False]
        assert list(np.asarray(off)[:4]) == [0, 0, 1, 2]


# rows whose walks meet every boundary the page stream runs through
# (tile length 8, pages of 16 tokens)
_STREAM_CASES = {
    "mixed": _MIXED,
    "inert_rows": dict(q_lens=[0, 1, 0, 0, 5, 0], starts=[0, 20, 0, 0, 3, 0]),
    # 8-page tables, 1-2 live pages: most KV-split chunks lie past a row
    "chunk_past_live": dict(q_lens=[1, 3], starts=[10, 17], mp=8),
    "row_spans_three_tiles": dict(q_lens=[2, 20, 1], starts=[5, 9, 33]),
    "tile_of_eight_decode_rows": dict(
        q_lens=[1] * 8, starts=[3, 17, 40, 63, 0, 31, 16, 50], n_pages=25),
    "one_page_rows": dict(q_lens=[1, 1, 4], starts=[0, 3, 2]),
}


def _stream_variant(variant):
    """``(operands, scale operands, kwargs)`` of a sliding-window, an
    int8-page, an int8-page sliding-window or a bfloat16 (native score
    dot) dispatch."""
    from fusioninfer_tpu.models.quantization import kv_quantize

    if variant == "window":
        return (_ragged_setup(q_lens=[1, 6, 2], starts=[60, 24, 40], mp=6,
                              seed=5), (), {"window": 24})
    if variant in ("int8", "int8+window"):
        if variant == "int8":
            (q, kp, vp, *rest), kw = _ragged_setup(**_MIXED, seed=11), {}
        else:  # decode rows 5, 40 and 100 tokens deep and an inert slot
            (q, kp, vp, *rest), kw = _ragged_setup(
                q_lens=[1, 1, 1, 0], starts=[4, 39, 99, 0], mp=8,
                n_pages=33, seed=13), {"window": 24}
        (k8, k_s), (v8, v_s) = kv_quantize(kp), kv_quantize(vp)
        return ((q, k8, v8, *rest),
                (k_s[:, :, None, :], v_s[:, :, None, :]), kw)
    return (_ragged_setup(q_lens=[1, 12, 1], starts=[30, 9, 47], KV=2, G=4,
                          dtype=jnp.bfloat16, seed=7), (), {})


def _stream_run(pa, grid, args, scales=(), **kw):
    """One ragged dispatch through the un-jitted wrapper (so a patched
    ring depth is what is traced): ``grid`` is ``per-head``,
    ``coalesced`` or ``split<S>``."""
    if grid.startswith("split"):
        return np.asarray(pa.ragged_paged_attention_kvsplit.__wrapped__(
            *args, *scales, kv_splits=int(grid[5:]), interpret=True, **kw))
    return np.asarray(pa.ragged_paged_attention.__wrapped__(
        *args, *scales, coalesce=grid == "coalesced", interpret=True, **kw))


class TestRaggedPageStream:
    """The ragged grids' page stream (one per program column, through
    walk, row and tile boundaries): against the oracle, bit for bit with
    itself across ring depths, and its walk lists against a plain
    enumeration."""

    @pytest.mark.parametrize("grid", ["per-head", "coalesced", "split8"])
    @pytest.mark.parametrize("case", list(_STREAM_CASES))
    def test_matches_oracle(self, case, grid):
        from fusioninfer_tpu.ops import paged_attention as pa

        args = _ragged_setup(**_STREAM_CASES[case], seed=3)
        out = _stream_run(pa, grid, args)
        ref = reference_ragged_paged_attention(*args)
        np.testing.assert_allclose(out, np.asarray(ref), atol=2e-5,
                                   rtol=2e-5)

    @pytest.mark.parametrize("grid", ["per-head", "coalesced", "split8"])
    @pytest.mark.parametrize("case", list(_STREAM_CASES))
    def test_ring_depth_decides_no_bit(self, case, grid, monkeypatch):
        from fusioninfer_tpu.ops import paged_attention as pa

        args = _ragged_setup(**_STREAM_CASES[case], seed=4)
        shipped = _stream_run(pa, grid, args)
        monkeypatch.setattr(pa, "RAGGED_RING_SLOTS", 2)
        np.testing.assert_array_equal(_stream_run(pa, grid, args), shipped)

    @pytest.mark.parametrize("grid", ["per-head", "coalesced", "split4"])
    @pytest.mark.parametrize("variant", ["window", "int8", "int8+window",
                                         "bf16"])
    def test_ring_depth_decides_no_bit_variants(self, variant, grid,
                                                monkeypatch):
        from fusioninfer_tpu.ops import paged_attention as pa

        args, scales, kw = _stream_variant(variant)
        shipped = _stream_run(pa, grid, args, scales, **kw)
        for slots in (2, 5):
            monkeypatch.setattr(pa, "RAGGED_RING_SLOTS", slots)
            np.testing.assert_array_equal(
                _stream_run(pa, grid, args, scales, **kw), shipped)

    @pytest.mark.parametrize("grid", ["per-head", "coalesced", "split8"])
    def test_native_dot_matches_upcast_dot(self, grid, monkeypatch):
        """16-bit queries and pages feed the score dot as stored
        (float32 accumulation; only ``q * sm_scale`` is no longer
        rounded): against the same kernel with the operands upcast first
        it stays within the latent kernel's tolerance
        (tests/test_deepseek_v2.py, 2e-5) beyond one rounding step of
        the bfloat16 output."""
        from fusioninfer_tpu.ops import paged_attention as pa

        args, _, _ = _stream_variant("bf16")
        assert pa._native_scores(args[0].dtype, args[1].dtype)
        assert not pa._native_scores(jnp.float32, jnp.float32)
        assert not pa._native_scores(jnp.bfloat16, jnp.int8)
        native = _stream_run(pa, grid, args).astype(np.float32)
        monkeypatch.setattr(pa, "_native_scores", lambda *_: False)
        upcast = _stream_run(pa, grid, args).astype(np.float32)
        np.testing.assert_allclose(native, upcast, atol=2e-5,
                                   rtol=2.0 ** -8)

    @pytest.mark.parametrize("window", [None, 24])
    @pytest.mark.parametrize("splits", [0, 1, 4, 8])
    @pytest.mark.parametrize("case", ["mixed", "inert_rows",
                                      "chunk_past_live",
                                      "row_spans_three_tiles"])
    def test_walk_lists_match_plain_enumeration(self, case, splits, window):
        """Each column's list is its non-empty (tile, row, chunk) spans
        in program order, and only those: the scorer and the fetch
        cursor both run down it."""
        from fusioninfer_tpu.ops import paged_attention as pa

        q, kp, vp, tables, starts, qb, ql = _ragged_setup(
            **_STREAM_CASES[case])
        bq, ps, mp = pa.RAGGED_BLOCK_Q, kp.shape[2], tables.shape[1]
        nb = -(-q.shape[0] // bq)
        n_cols, cpp = max(splits, 1), (8 // splits if splits else 1)
        chunk_pages = -(-mp // 8) if splits else mp
        got = [np.asarray(a) for a in pa.ragged_walk_lists(
            q, kp, vp, tables, starts, qb, ql, window=window,
            kv_splits=splits)]
        tile_walks = got[0].reshape(n_cols, nb + 1)
        lists = [a.reshape(n_cols, -1) for a in got[1:]]
        st, b, n = (np.asarray(a) for a in (starts, qb, ql))
        for col in range(n_cols):
            want, offsets = [], [0]
            for t in range(nb):
                for r in range(len(n)):
                    lo, hi = max(b[r], t * bq), min(b[r] + n[r], (t + 1) * bq)
                    if hi <= lo:
                        continue
                    end = -(-(st[r] + hi - b[r]) // ps)
                    first = (max(st[r] + lo - b[r] - (window - 1), 0) // ps
                             if window else 0)
                    for c in range(cpp):
                        ch = col * cpp + c
                        f = max(first, ch * chunk_pages)
                        e = min(end, (ch + 1) * chunk_pages)
                        if e > f:
                            want.append((r * cpp + c, f, e))
                offsets.append(len(want))
            assert list(tile_walks[col]) == offsets
            assert [tuple(a[col, k] for a in lists)
                    for k in range(len(want))] == want

    def test_walks_of_another_grid_are_refused(self):
        from fusioninfer_tpu.ops import paged_attention as pa

        args = _ragged_setup(**_MIXED)
        walks = pa.ragged_walk_lists(*args, kv_splits=4)
        with pytest.raises(ValueError, match="another grid"):
            ragged_paged_attention(*args, interpret=True, walks=walks)
        out = pa.ragged_paged_attention_kvsplit(
            *args, kv_splits=4, interpret=True, walks=walks)
        ref = reference_ragged_paged_attention(*args)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


def traced_equations(jaxpr) -> int:
    """Equations of a jaxpr, those of every nested jaxpr included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += traced_equations(sub)
    return n


def kernel_of(jaxpr):
    """The kernel jaxpr of the one top-level ``pallas_call``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn.params["jaxpr"]
    raise AssertionError("no pallas_call at the top level")


class TestStartupBudget:
    """A program that carries the kernel pays for its traced size at
    every warm start: its first dispatch traces and lowers it again
    though the executable comes from the compile cache (PERF.md, PR 31:
    on the chip's host a first dispatch of the kernel alone is 0.37 s of
    trace + lower and 0.04 s of retrieve + load).  PR 30's stream
    enumerated walks inside the kernel (580 equations at two ring slots,
    more with every slot) and cost every start 4.9 s."""

    def test_kvsplit_kernel_lowers_within_budget(self):
        """Traced and lowered for ("tpu",) at ``qwen3-1.7b``'s cell
        shapes ([28, 8, 744, 128, 128] bfloat16 pages, 32 rows x 32 table
        pages, T 16 and 512, 8 splits), measured on this sandbox's CPU:
        the parent (PR 29's two-slot walk) is a kernel of 187 equations
        in a module of 47.6 k characters, lowered in 0.20 s; this tree
        183 equations in 59.2 k (the walk lists are plain XLA operations
        outside the kernel) in 0.20 s.  Held: the kernel within 1.2 x
        the parent's equations and the module within 1.3 x its
        characters, whatever the ring's depth."""
        from fusioninfer_tpu.ops import paged_attention as pa

        KV, G, Hd, ps, n_pages, L, mp, R = 8, 2, 128, 128, 744, 28, 32, 32
        pool = jax.ShapeDtypeStruct((L, KV, n_pages, ps, Hd), jnp.bfloat16)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731

        def run(q, kp, vp, tables, st, qb, ql, layer):
            return pa.ragged_paged_attention_kvsplit.__wrapped__(
                q, kp, vp, tables, st, qb, ql, kv_splits=8, layer=layer)

        sizes = {}
        for slots in (pa.RAGGED_RING_SLOTS, 6):
            for T in (16, 512):
                q = jax.ShapeDtypeStruct((T, KV * G, Hd), jnp.bfloat16)
                with pytest.MonkeyPatch.context() as patch:
                    patch.setattr(pa, "RAGGED_RING_SLOTS", slots)
                    traced = jax.jit(run).trace(
                        q, pool, pool, i32(R, mp), i32(R), i32(R), i32(R),
                        i32())
                    module = traced.lower(
                        lowering_platforms=("tpu",)).as_text()
                sizes[slots, T] = (traced_equations(kernel_of(traced.jaxpr.jaxpr)),
                                   len(module))
        for key, (kernel, module) in sizes.items():
            assert kernel <= 1.2 * 187, (key, kernel)
            assert module <= 1.3 * 47_600, (key, module)
        # the ring's depth is a scratch shape, not traced code
        assert sizes[6, 512][0] == sizes[pa.RAGGED_RING_SLOTS, 512][0]


class TestRaggedVmemGuard:
    """The coalesced grid's page ring [slots, KV, ps, Hd] and its tiles
    must fit a conservative VMEM budget; oversized configurations fall
    back to the per-head grid instead of failing Mosaic allocation."""

    def test_scratch_bytes_math(self):
        from fusioninfer_tpu.ops.paged_attention import coalesced_scratch_bytes

        # 2 slots x KV=2 heads x 16 x 64 x (4 + 4) bytes f32 K+V
        assert coalesced_scratch_bytes(16, 64, 2, jnp.float32, jnp.float32,
                                       quantized=False,
                                       slots=2) == 2 * 2 * 16 * 64 * 8
        # int8 adds two f32 [1, ps] scale rows per head per slot
        q8 = coalesced_scratch_bytes(16, 64, 2, jnp.int8, jnp.int8,
                                     quantized=True, slots=3)
        assert q8 == 3 * (2 * 16 * 64 * 2 + 2 * 2 * 16 * 4)

    def test_fits_vmem_adds_tile_term(self):
        from fusioninfer_tpu.ops.paged_attention import (
            RAGGED_RING_SLOTS,
            coalesced_scratch_bytes,
            ragged_fits_vmem,
        )

        assert ragged_fits_vmem(8, 128, 128, 8, 4, jnp.bfloat16,
                                jnp.bfloat16, jnp.bfloat16,
                                quantized=False)  # the serving shape
        # a pathological KV x ps x Hd product must NOT coalesce
        assert not ragged_fits_vmem(8, 2048, 256, 32, 1, jnp.float32,
                                    jnp.float32, jnp.float32,
                                    quantized=False)
        # explicit budget override for unit determinism
        assert not ragged_fits_vmem(8, 16, 64, 2, 2, jnp.float32,
                                    jnp.float32, jnp.float32,
                                    quantized=False, budget=1024)
        # the tile term matters: a budget that fits the page scratch
        # alone must reject once q/out tiles are counted
        pages = coalesced_scratch_bytes(16, 64, 2, jnp.float32,
                                        jnp.float32, quantized=False,
                                        slots=RAGGED_RING_SLOTS)
        assert not ragged_fits_vmem(8, 16, 64, 2, 2, jnp.float32,
                                    jnp.float32, jnp.float32,
                                    quantized=False, budget=pages + 1)

    def test_oversized_falls_back_to_per_head_grid(self, monkeypatch):
        from fusioninfer_tpu.ops import paged_attention as pa

        def bomb(*a, **k):
            raise AssertionError("coalesced ragged kernel entered despite "
                                 "over-budget scratch")

        monkeypatch.setattr(pa, "_ragged_kernel_coalesced", bomb)
        monkeypatch.setattr(pa, "_COALESCE_VMEM_SCRATCH_BUDGET", 1024)
        q, kp, vp, tables, starts, qb, ql = _ragged_setup(**_MIXED)
        out = pa.ragged_paged_attention.__wrapped__(
            q, kp, vp, tables, starts, qb, ql, interpret=True,
            coalesce=True)
        ref = reference_ragged_paged_attention(q, kp, vp, tables, starts,
                                               qb, ql)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestEagerCoalesceResolution:
    """Flipping FUSIONINFER_DECODE_COALESCE mid-process must take effect:
    the engine resolves the env var OUTSIDE the jitted step and passes
    the concrete bool as a static argument, so the flip retraces instead
    of silently reusing the latched variant."""

    def test_decode_step_takes_coalesce_static(self, monkeypatch):
        from fusioninfer_tpu.engine.engine import NativeEngine, Request
        from fusioninfer_tpu.engine.sampler import SamplingParams
        from fusioninfer_tpu.engine.kv_cache import CacheConfig
        from fusioninfer_tpu.models.config import get_preset

        engine = NativeEngine(
            get_preset("qwen3-tiny"),
            cache_cfg=CacheConfig(n_pages=33, page_size=16,
                                  max_pages_per_seq=4),
            max_batch_size=2)
        engine.add_request(Request("a", [2, 4], SamplingParams(
            max_tokens=6, temperature=0.0)))
        outs = []
        monkeypatch.setenv("FUSIONINFER_DECODE_COALESCE", "1")
        for _ in range(3):
            outs += engine.step()
        # flip mid-stream: the next step resolves the new value eagerly
        monkeypatch.setenv("FUSIONINFER_DECODE_COALESCE", "0")
        while engine.has_work():
            outs += engine.step()
        toks = [o.token for o in outs if o.request_id == "a"]
        # both grids compute identical math: the stream is unbroken
        assert len(toks) == 6

    def test_bad_env_value_raises(self, monkeypatch):
        from fusioninfer_tpu.ops import dispatch

        monkeypatch.setenv("FUSIONINFER_DECODE_COALESCE", "yes")
        with pytest.raises(ValueError):
            dispatch.decode_coalesce()
