"""The ragged paged-attention family, one row kind at a time.

What the engine packs onto the flat token axis — decode rows, one chunk
row (a cache-hit suffix, a chunk from the middle of a prompt),
speculative window rows — each alone, on every grid (``per-head``,
``coalesced``, ``split8``), against a SECOND oracle written for that
kind without the flat axis (``reference_decode_rows_attention``,
``reference_chunk_row_attention``, ``reference_window_rows_attention``),
so a fault shared by the kernels and the flat oracle's token-to-row
resolution cannot hide.  Interpret mode; the flat oracle, the page
stream and the bit-identity properties are
``tests/test_paged_attention.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fusioninfer_tpu.ops.paged_attention import (
    RAGGED_BLOCK_Q,
    ragged_paged_attention,
    ragged_paged_attention_kvsplit,
    reference_chunk_row_attention,
    reference_decode_rows_attention,
    reference_window_rows_attention,
)

GRIDS = ["per-head", "coalesced", "split8"]


def _run(grid, args, scales=(), **kw):
    """One ragged dispatch on ``grid`` → float32 [T, H*Hd]."""
    if grid.startswith("split"):
        out = ragged_paged_attention_kvsplit(
            *args, *scales, kv_splits=int(grid[5:]), interpret=True, **kw)
    else:
        out = ragged_paged_attention(
            *args, *scales, coalesce=grid == "coalesced", interpret=True,
            **kw)
    return np.asarray(out, np.float32)


def _pages(KV, n_pages, ps, Hd, seed, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 2)
    return (jax.random.normal(ks[0], (KV, n_pages, ps, Hd), dtype),
            jax.random.normal(ks[1], (KV, n_pages, ps, Hd), dtype))


def _tables(rows, mp, n_pages, seed):
    """[rows, mp] page tables over distinct pages (the last is trash)."""
    perm = np.random.default_rng(seed).permutation(n_pages - 1)
    return jnp.asarray(perm[: rows * mp].reshape(rows, mp).astype(np.int32))


# One row kind alone, as the ragged kernels take it, beside what a second
# oracle says of it — one written for that kind, with no flat token
# axis: ``(ragged operands, expected [T, H*Hd] float32, live tokens [T])``.

def _decode_rows(lengths, *, H=4, KV=2, Hd=64, ps=16, mp=8, n_pages=33,
                 seed=0, dtype=jnp.float32, window=None):
    """B decode rows — one token at position ``lengths[b] - 1``, length
    0 an inert slot — against ``reference_decode_rows_attention``."""
    lengths = jnp.asarray(lengths, jnp.int32)
    B = lengths.shape[0]
    kp, vp = _pages(KV, n_pages, ps, Hd, seed, dtype)
    q = jax.random.normal(jax.random.key(seed + 100), (B, H, Hd), dtype)
    tables = _tables(B, mp, n_pages, seed)
    want = reference_decode_rows_attention(q, kp, vp, tables, lengths,
                                           window=window)
    args = (q, kp, vp, tables, jnp.maximum(lengths - 1, 0),
            jnp.arange(B, dtype=jnp.int32), (lengths > 0).astype(jnp.int32))
    return args, np.asarray(want, np.float32), np.asarray(lengths) > 0


def _chunk_row(C, start, true_len, *, H=4, KV=2, Hd=64, ps=16, mp=8,
               n_pages=17, seed=0, dtype=jnp.float32, window=None):
    """One chunk row — ``true_len`` tokens from position ``start`` (a
    cache-hit suffix, a chunk from the middle of a prompt), the flat axis
    padded to ``C`` tokens no row owns — against
    ``reference_chunk_row_attention``."""
    kp, vp = _pages(KV, n_pages, ps, Hd, seed, dtype)
    q = jax.random.normal(jax.random.key(seed + 100), (C, H, Hd), dtype)
    row = _tables(1, mp, n_pages, seed)
    want = reference_chunk_row_attention(
        q, kp, vp, row[0], jnp.int32(start), jnp.int32(true_len),
        window=window)
    args = (q, kp, vp, row, jnp.asarray([start], jnp.int32),
            jnp.zeros((1,), jnp.int32), jnp.asarray([true_len], jnp.int32))
    return args, np.asarray(want, np.float32), np.arange(C) < true_len


def _window_rows(C, starts, counts, *, H=4, KV=2, Hd=64, ps=16, mp=8,
                 n_pages=33, seed=0, dtype=jnp.float32, window=None):
    """B speculative-window rows — ``counts[b]`` tokens (the input token
    and its drafts) from ``starts[b]``, row ``b``'s segment at flat
    offset ``b * C`` — against ``reference_window_rows_attention``."""
    starts = jnp.asarray(starts, jnp.int32)
    counts = jnp.asarray(counts, jnp.int32)
    B = starts.shape[0]
    kp, vp = _pages(KV, n_pages, ps, Hd, seed, dtype)
    q = jax.random.normal(jax.random.key(seed + 100), (B, C, H, Hd), dtype)
    tables = _tables(B, mp, n_pages, seed)
    want = reference_window_rows_attention(q, kp, vp, tables, starts,
                                           counts, window=window)
    args = (q.reshape(B * C, H, Hd), kp, vp, tables, starts,
            jnp.arange(B, dtype=jnp.int32) * C, counts)
    live = np.arange(C)[None, :] < np.asarray(counts)[:, None]
    return (args, np.asarray(want, np.float32).reshape(B * C, -1),
            live.reshape(-1))


ROW_KINDS = {
    "decode": lambda **kw: _decode_rows([5, 40, 100, 0], **kw),
    # the whole chunk lies past the window: its first pages are skipped
    "chunk": lambda **kw: _chunk_row(32, 67, 21, **kw),
    "window": lambda **kw: _window_rows(4, [0, 37, 90], [4, 3, 0], **kw),
}


def _assert_live_rows(got, want, live, tol):
    got = np.asarray(got, np.float32)
    np.testing.assert_allclose(got[live], want[live], atol=tol, rtol=tol)


@pytest.mark.parametrize("grid", GRIDS)
class TestRaggedRowKinds:
    """What the engine packs, one row kind at a time, on every grid,
    against a second oracle written for that kind (the helpers above):
    decode rows, one chunk row (a cache-hit suffix, a chunk from the
    middle of a prompt), speculative window rows."""

    @pytest.mark.parametrize("case", ["base", "inert_slot", "gqa", "bf16"])
    def test_decode_rows_match_gather_oracle(self, case, grid):
        # two pages and a bit, one token, the whole table
        lengths, kw, tol = {
            "base": ([35, 1, 64], {}, 2e-5),
            "inert_slot": ([0, 5], {}, 2e-5),
            "gqa": ([35, 1, 64], dict(H=8, KV=2, seed=4), 2e-5),
            "bf16": ([35, 1, 64], dict(dtype=jnp.bfloat16, seed=7), 4e-2),
        }[case]
        args, want, live = _decode_rows(lengths, mp=4, n_pages=13, **kw)
        out = _run(grid, args)
        _assert_live_rows(out, want, live, tol)
        # a slot with no token gives exactly zero, not merely nothing read
        assert not out[~live].any()

    @pytest.mark.parametrize("case", ["midstream", "from_zero",
                                      "many_tiles", "gqa_bf16"])
    def test_chunk_row_matches_suffix_oracle(self, case, grid):
        from fusioninfer_tpu.ops.flash_attention import reference_attention

        kw, tol = {
            # start and length are no multiples of the page or the tile
            "midstream": (dict(C=32, start=19, true_len=21), 2e-5),
            "from_zero": (dict(C=32, start=0, true_len=32, seed=3), 2e-5),
            "many_tiles": (dict(C=64, start=50, true_len=40, mp=12, seed=5),
                           2e-5),
            "gqa_bf16": (dict(C=32, start=7, true_len=30, H=8, KV=2,
                              dtype=jnp.bfloat16, seed=9), 4e-2),
        }[case]
        args, want, live = _chunk_row(**kw)
        assert kw["true_len"] > 2 * RAGGED_BLOCK_Q  # several q tiles
        out = _run(grid, args)
        _assert_live_rows(out, want, live, tol)
        if case == "from_zero":
            # a row from position 0 is whole-prompt causal attention
            # over the row's own pages laid end to end
            q, kp, vp, row = args[:4]
            C, KV, Hd = q.shape[0], kp.shape[0], kp.shape[-1]
            k, v = (jnp.swapaxes(x[:, row[0]].reshape(KV, -1, Hd)[:, :C],
                                 0, 1)[None] for x in (kp, vp))
            whole = reference_attention(q[None], k, v, causal=True)[0]
            np.testing.assert_allclose(out, np.asarray(whole), atol=tol,
                                       rtol=tol)

    @pytest.mark.parametrize("case", ["under_a_tile", "a_tile", "over_a_tile"])
    def test_window_rows_match_rectangle_oracle(self, case, grid):
        kw = {
            # the input token and three drafts; a row of one; an inert row
            "under_a_tile": dict(C=4, starts=[0, 17, 30, 100],
                                 counts=[4, 3, 1, 0], seed=1),
            "a_tile": dict(C=RAGGED_BLOCK_Q, starts=[0, 17, 30, 100],
                           counts=[8, 5, 1, 0], H=8, KV=4),
            # a window longer than a q tile (a batch of suffix rows)
            "over_a_tile": dict(C=64, starts=[0, 21, 50],
                                counts=[64, 37, 0], seed=9),
        }[case]
        args, want, live = _window_rows(**kw)
        out = _run(grid, args)
        _assert_live_rows(out, want, live, 3e-4)

    @pytest.mark.parametrize("kind", list(ROW_KINDS))
    def test_sliding_window_bands_each_kind(self, kind, grid):
        """Decode rows, a chunk row from ``start > window`` and 1 + 3-token
        window rows, at windows under a page, over a page and over most
        contexts."""
        for w in (8, 24, 64):
            args, want, live = ROW_KINDS[kind](window=w, seed=2)
            _assert_live_rows(_run(grid, args, window=w), want, live, 2e-4)

    def test_int8_pages_under_a_window(self, grid):
        """Banding and scale folding compose: the walk starts at the
        window's first live page AND streams the int8 scale rows from
        the same offset — decode rows against the gather oracle over the
        dequantized pages."""
        from fusioninfer_tpu.models.quantization import kv_quantize

        lengths = jnp.asarray([5, 40, 100, 0], jnp.int32)
        (q, kp, vp, tables, *rows), _, live = _decode_rows(lengths, seed=13)
        (k8, k_s), (v8, v_s) = kv_quantize(kp), kv_quantize(vp)
        out = _run(grid, (q, k8, v8, tables, *rows),
                   (k_s[:, :, None, :], v_s[:, :, None, :]), window=24)
        want = reference_decode_rows_attention(
            q, k8.astype(jnp.float32) * k_s[..., None],
            v8.astype(jnp.float32) * v_s[..., None], tables, lengths,
            window=24)
        _assert_live_rows(out, np.asarray(want, np.float32), live, 3e-4)
