"""The latent (MLA) paged-attention kernel's page stream
(``ops/mla_attention.py``): the cases a stream can get wrong, against
the kernel's jnp oracle in interpret mode on the CPU; a token's bits
against its flat offset and its neighbours; what the kernel costs a warm
start to trace and lower.

Tiles are ``MLA_BLOCK_Q`` = 8 flat tokens, pages 16 positions, a ring
slot ``MLA_PAGES_PER_UPDATE`` = 2 pages, float32 (kernel and oracle then
differ by the order of float32 sums only: the online softmax against one
softmax over the gathered context).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fusioninfer_tpu.ops import mla_attention as mla
from fusioninfer_tpu.ops.mla_attention import (
    mla_ragged_paged_attention,
    mla_walk_lists,
    reference_mla_ragged_paged_attention,
)
from tests.test_paged_attention import kernel_of, traced_equations

PS, RANK, ROPE, H, W = 16, 64, 16, 4, 128


def _setup(q_lens, starts, *, T=None, mp=8, n_pages=48, layers=2, seed=0,
           dtype=jnp.float32):
    """Kernel operands for rows of ``q_lens`` tokens from positions
    ``starts``, packed in flat order, every live row on pages of its own
    (shuffled), table entries past a row's pages on a page no row owns."""
    rng = np.random.default_rng(seed)
    q_lens = np.asarray(q_lens, np.int32)
    starts = np.asarray(starts, np.int32)
    q_begins = np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(np.int32)
    T = T or max(8, -(-int(q_lens.sum()) // 8) * 8)
    perm = rng.permutation(n_pages - 1)
    tables = np.full((len(q_lens), mp), n_pages - 1, np.int32)
    used = 0
    for r, (n, st) in enumerate(zip(q_lens, starts)):
        need = -(-int(st + n) // PS) if n else 0
        tables[r, :need] = perm[used:used + need]
        used += need
    return (jnp.asarray(rng.normal(size=(T, H, RANK)) * 0.3, dtype),
            jnp.asarray(rng.normal(size=(T, H, ROPE)) * 0.3, dtype),
            jnp.asarray(rng.normal(size=(layers, 1, n_pages, PS, W)), dtype),
            jnp.asarray(tables), jnp.asarray(starts), jnp.asarray(q_begins),
            jnp.asarray(q_lens))


# rows whose walks meet every boundary the stream runs through
_STREAM_CASES = {
    # row 0's last token is the last of tile 0
    "row_ends_at_a_tile_edge": dict(q_lens=[8, 1, 3], starts=[5, 20, 40]),
    # tile 0 holds all of rows 0 and 1 and the first token of row 2
    "tile_straddles_three_rows": dict(q_lens=[5, 2, 6], starts=[11, 30, 2]),
    "inert_rows_between_live_rows_then_empty_tiles": dict(
        q_lens=[1, 0, 0, 3, 0, 1, 0, 0], starts=[20, 0, 0, 7, 0, 33, 0, 0],
        T=32),
    # one page (half a ring slot); positions 0..16: the second page holds
    # one position
    "one_page_row_and_a_last_page_of_one_position": dict(
        q_lens=[1, 1, 4], starts=[3, 16, 13]),
    "chunk_row_from_a_non_zero_context": dict(q_lens=[12], starts=[37]),
    "decode_and_chunk_rows_in_one_tile": dict(
        q_lens=[1, 5, 1, 1], starts=[50, 9, 0, 31]),
    # eight walks in the one program, 23 pages in 14 slots through the
    # ring: odd walks' last slots still hold an earlier walk's page
    "more_walks_than_ring_slots": dict(
        q_lens=[1] * 8, starts=[3, 17, 40, 63, 0, 31, 16, 50]),
    # one walk of one page: the primed cursor runs off the list's end
    "fewer_pages_than_ring_slots": dict(q_lens=[1], starts=[2]),
    "row_over_three_tiles_between_decode_rows": dict(
        q_lens=[1, 20, 1], starts=[5, 9, 33]),
}


@pytest.mark.parametrize("slots", [2, mla.MLA_RING_SLOTS, 5])
@pytest.mark.parametrize("case", sorted(_STREAM_CASES))
def test_latent_stream_matches_the_oracle(case, slots, monkeypatch):
    """Every case at the served ring depth, at the shallowest ring (each
    slot waited for with one behind it) and at one deeper than most of
    these lists are long."""
    monkeypatch.setattr(mla, "MLA_RING_SLOTS", slots)
    args = _setup(**_STREAM_CASES[case])
    got = mla_ragged_paged_attention.__wrapped__(
        *args, layer=1, rank=RANK, interpret=True)
    want = reference_mla_ragged_paged_attention(*args, layer=1, rank=RANK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=0)


@pytest.mark.parametrize("case", ["tile_straddles_three_rows",
                                  "inert_rows_between_live_rows_then_empty_tiles"])
def test_walk_lists_built_by_the_caller_give_the_same_bits(case):
    """``walks=`` (built once a forward, before the layer scan) against
    the lists the wrapper builds itself; lists of another tile count are
    refused."""
    args = _setup(**_STREAM_CASES[case])
    q_lat, _, pages, _, starts, q_begins, q_lens = args
    walks = mla_walk_lists(q_lat.shape[0], pages, starts, q_begins, q_lens)
    kw = dict(layer=0, rank=RANK, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(mla_ragged_paged_attention(*args, **kw, walks=walks)),
        np.asarray(mla_ragged_paged_attention(*args, **kw)))
    with pytest.raises(ValueError, match="another grid"):
        mla_ragged_paged_attention(
            *args, **kw, walks=mla_walk_lists(
                q_lat.shape[0] + 8, pages, starts, q_begins, q_lens))


def test_walk_lists_name_no_walk_for_inert_rows_and_empty_tiles():
    args = _setup(**_STREAM_CASES[
        "inert_rows_between_live_rows_then_empty_tiles"])
    q_lat, _, pages, _, starts, q_begins, q_lens = args
    tile_walks, w_row, w_first, w_end = (np.asarray(a) for a in mla_walk_lists(
        q_lat.shape[0], pages, starts, q_begins, q_lens))
    # tokens 0 | 1 2 3 | 4 lie in tile 0; tiles 1-3 are empty
    assert list(tile_walks) == [0, 3, 3, 3, 3]
    assert list(w_row[:3]) == [0, 3, 5]
    assert list(w_first[:3]) == [0, 0, 0]
    assert list(w_end[:3]) == [2, 1, 3]  # positions 20, 7..9, 33
    assert not w_end[3:].any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_latent_offset_and_neighbor_invariance_bit_identity(dtype):
    """The latent twin of ``test_offset_and_neighbor_invariance_bit_
    identity``: a row scored alone from flat offset 0, and the same row
    among neighbours at another offset, give the same BITS, so a split
    dispatch and the fused step that absorbs it can never disagree.
    Each walk starts fresh accumulators, every dot and reduction is
    row-wise, and which ring slot held a page decides nothing."""
    q_lens, starts = [1, 16, 1, 24, 1], [50, 9, 0, 40, 31]
    args = _setup(q_lens, starts, seed=3, dtype=dtype)
    q_lat, q_rope, pages, tables, st, qb, ql = args
    kw = dict(layer=1, rank=RANK, interpret=True)
    mixed = np.asarray(mla_ragged_paged_attention(*args, **kw))
    for r in range(len(q_lens)):
        seg = slice(int(qb[r]), int(qb[r] + ql[r]))
        solo = np.asarray(mla_ragged_paged_attention(
            q_lat[seg], q_rope[seg], pages, tables[r:r + 1], st[r:r + 1],
            jnp.zeros((1,), jnp.int32), ql[r:r + 1], **kw))
        np.testing.assert_array_equal(solo, mixed[seg])


def test_latent_kernel_traces_and_lowers_within_the_start_up_budget():
    """A program that carries the kernel traces and lowers it again at
    every warm start (PERF.md, PR 31), five layers a forward in one scan
    body.  At ``deepseek-v2-ep4``'s cell shapes ([5, 1, 2048, 128, 640]
    bfloat16 pages, 128 heads, rank 512 + rope 64, 64 rows x 64 table
    pages, T 64 and 1 024), lowered for ("tpu",) on this sandbox's CPU:
    the parent (PR 32's two-buffer walk) is a kernel of 233 equations in
    a module of 27.4 k characters (27.7 k at T 1 024); this tree 290
    equations in 25.7 k as the layer scan holds it (``walks=`` passed:
    the lists are plain XLA operations built once a forward; 41.8 k with
    the lists built inside the call).  Held: the kernel within 1.3 x the
    parent's equations, whatever the ring's depth, and the module as the
    layer scan holds it within 1.3 x the parent's characters."""
    Hh, rank, rope, R, mp = 128, 512, 64, 64, 64
    pool = jax.ShapeDtypeStruct((5, 1, 2048, 128, 640), jnp.bfloat16)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731

    def run(q_lat, q_rope, pages, tables, st, qb, ql, layer, *walks):
        return mla_ragged_paged_attention.__wrapped__(
            q_lat, q_rope, pages, tables, st, qb, ql, layer=layer, rank=rank,
            walks=walks)

    sizes = {}
    for slots in (mla.MLA_RING_SLOTS, 6):
        for T in (64, 1024):
            nb = T // mla.MLA_BLOCK_Q
            with pytest.MonkeyPatch.context() as patch:
                patch.setattr(mla, "MLA_RING_SLOTS", slots)
                traced = jax.jit(run).trace(
                    jax.ShapeDtypeStruct((T, Hh, rank), jnp.bfloat16),
                    jax.ShapeDtypeStruct((T, Hh, rope), jnp.bfloat16), pool,
                    i32(R, mp), i32(R), i32(R), i32(R), i32(),
                    i32(nb + 1), i32(nb + R), i32(nb + R), i32(nb + R))
                module = traced.lower(lowering_platforms=("tpu",)).as_text()
            sizes[slots, T] = (
                traced_equations(kernel_of(traced.jaxpr.jaxpr)), len(module))
    for key, (kernel, module) in sizes.items():
        assert kernel <= 1.3 * 233, (key, kernel)
        assert module <= 1.3 * 27_400, (key, module)
    # the ring's depth is a scratch shape, not traced code
    assert sizes[6, 1024][0] == sizes[mla.MLA_RING_SLOTS, 1024][0]
