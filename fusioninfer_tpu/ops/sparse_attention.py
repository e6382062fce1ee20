"""Learned sparse attention (DeepSeek Sparse Attention's indexer) over the
paged cache: the indexer's scores over paged indexer keys, the exact
top-k selection, and GQA attention over the chosen positions alone, each
a Pallas TPU kernel or an exact jnp form, with the jnp oracles.

A query ``t`` scores every position ``s <= t`` of its row with the
indexer, ``I[t, s] = sum_j w_j[t] relu(q_j[t] . k[s] / sqrt(Di))`` (``j``
over the indexer heads, one indexer key a position), keeps the ``topk``
positions of highest ``I`` (ties to the lower position; every ``s <= t``
while ``t < topk``) and attends over those alone.

The selection is kept as a THRESHOLD a query: ``(thr_s, thr_c)`` such
that ``s`` is chosen iff ``I[t, s] > thr_s`` or ``I[t, s] == thr_s and s
<= thr_c`` (:func:`selection_mask`).  That is the exact top-k with the
tie rule: ``thr_s`` is the k-th largest score and ``thr_c`` the position
of the last equal one taken.  The serving programs find it with a
kernel (:func:`sparse_select`: bisection over the scores' ordered bits in
VMEM, an item's live context alone); the portable form
(:func:`sparse_threshold`) is ``lax.top_k``.

Work is laid out in ITEMS (:func:`sparse_items`): ``block_q``
consecutive query tokens of ONE row, so that an item reads one row's
pages.  A row of ``n`` tokens is ``ceil(n / block_q)`` items; a decode
row is one item.  The indexer and attention kernels run a grid
``(items, context blocks)``: a context block is ``pages_per_block`` pages
of the item's row, fetched by DMA from the pool in place; blocks past
the item's last query position are neither fetched nor scored.

The attention kernel reads the pages of the item's row and masks out
what the selection did not choose, so its HBM traffic is the row's
context (``docs/design/engine.md``, "Sparse attention", says why the
chosen token rows are not gathered one by one).
Its arithmetic runs in float32 with bfloat16 dot operands; a query of no
item, and a query that chooses nothing, give zeros.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import numpy as np

from fusioninfer_tpu.ops.paged_attention import ragged_token_rows

# query tokens of one item: chunk-carrying programs, decode-only programs
SPARSE_BLOCK_Q = 32
SPARSE_BLOCK_Q_DECODE = 8
# pages a context block fetches at most (a divisor of the page table's)
SPARSE_PAGES_PER_BLOCK = 16
# context positions a step of the selection kernel fetches at most
SELECT_BLOCK = 2048
SPARSE_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
_MASKED = -1e30  # the softmax state's floor: finite, so no inf - inf


class SparseItems(NamedTuple):
    """The item layout of one forward's flat tokens (:func:`sparse_items`)."""

    row: jax.Array      # [N] the item's row
    pos0: jax.Array     # [N] position of the item's first query
    n: jax.Array        # [N] live queries of the item (0 = inert)
    tok: jax.Array      # [N, bq] flat index of each query (clipped)
    live: jax.Array     # [N, bq] the query exists
    item_of: jax.Array  # [T] the item of each flat token
    slot_of: jax.Array  # [T] its slot in the item
    tok_live: jax.Array  # [T] the flat token belongs to a row


def sparse_items(q_begins, q_lens, row_starts, n_tokens: int,
                 block_q: int) -> SparseItems:
    """Split each row of a flat ragged layout (rows packed in flat order)
    into items of ``block_q`` consecutive tokens.  The count of items is
    static: ``ceil(n_tokens / block_q)`` whole items plus one partial a
    row at most."""
    R = q_lens.shape[0]
    N = -(-n_tokens // block_q) + R
    nb = (q_lens + block_q - 1) // block_q
    ends = jnp.cumsum(nb)
    starts = ends - nb
    i = jnp.arange(N)
    row = jnp.clip(jnp.searchsorted(ends, i, side="right"), 0, R - 1)
    b = i - starts[row]
    n = jnp.where(i < ends[-1],
                  jnp.clip(q_lens[row] - b * block_q, 0, block_q), 0)
    slots = jnp.arange(block_q)
    tok = jnp.clip((q_begins[row] + b * block_q)[:, None] + slots, 0,
                   n_tokens - 1)
    row_of, off, tok_live = ragged_token_rows(q_begins, q_lens, n_tokens)
    return SparseItems(
        row.astype(jnp.int32), (row_starts[row] + b * block_q).astype(jnp.int32),
        n.astype(jnp.int32), tok, slots[None, :] < n[:, None],
        starts[row_of] + off // block_q, off % block_q, tok_live)


def pages_per_block(max_pages: int) -> int:
    """Pages a context block holds: the largest divisor of the page
    table's width up to :data:`SPARSE_PAGES_PER_BLOCK`."""
    return max(p for p in range(1, SPARSE_PAGES_PER_BLOCK + 1)
               if max_pages % p == 0)


def _positive_zero(s):
    """-0.0 and +0.0 compare equal but order apart by their bits: one
    zero, so the selection by value and by bits agree."""
    return jnp.where(s == 0, 0.0, s)


# -- the indexer's scores ----------------------------------------------------


def index_scores(q, w, k, valid):
    """Exact form of the indexer: q [M, HI, Di], w [M, HI] float32, k
    [M, C, Di] (each query's context) → scores [M, C] float32, -inf where
    not ``valid`` [M, C]."""
    s = jnp.einsum("mhd,mcd->mhc", q, k, preferred_element_type=jnp.float32)
    s = jnp.maximum(s * (q.shape[-1] ** -0.5), 0.0)
    s = jnp.einsum("mhc,mh->mc", s, w.astype(jnp.float32))
    return jnp.where(valid, _positive_zero(s), -jnp.inf)


def _item_valid(items: SparseItems, n_ctx: int):
    """[N, bq, C]: context position ``c`` is seen by slot ``j`` of an item."""
    pos = items.pos0[:, None] + jnp.arange(items.tok.shape[1])
    c = jnp.arange(n_ctx)
    return items.live[:, :, None] & (c[None, None, :] <= pos[:, :, None])


def reference_indexer_paged_scores(q, w, k_idx, page_tables,
                                   items: SparseItems, *, layer):
    """Gathered-context oracle of :func:`indexer_paged_scores`."""
    N, HI, bq, Di = q.shape
    mp = page_tables.shape[1]
    ps = k_idx.shape[2]
    pool = lax.dynamic_index_in_dim(k_idx, layer, 0, keepdims=False)
    ctx = pool[page_tables[items.row]].reshape(N, mp * ps, -1)[..., :Di]
    s = index_scores(
        jnp.moveaxis(q, 1, 2).reshape(N * bq, HI, Di),
        w.reshape(N * bq, HI),
        jnp.repeat(ctx, bq, axis=0),
        _item_valid(items, mp * ps).reshape(N * bq, mp * ps))
    return s.reshape(N, bq, mp * ps)


def _indexer_kernel(layer_ref, row_ref, pos0_ref, n_ref, tables_ref,
                    q_ref, w_ref, k_hbm, out_ref, kbuf, sem, *, n_heads,
                    page_size, pages):
    i, j = pl.program_id(0), pl.program_id(1)
    bq, tc = out_ref.shape[1], out_ref.shape[2]
    n, pos0 = n_ref[i], pos0_ref[i]
    last = pos0 + n - 1
    first = j * tc

    @pl.when(first > last)
    def _skip():
        out_ref[...] = jnp.full(out_ref.shape, -jnp.inf, out_ref.dtype)

    @pl.when(first <= last)
    def _score():
        r = row_ref[i]

        def copy(p):  # a page's keys, [ps, W]
            return pltpu.make_async_copy(
                k_hbm.at[layer_ref[0], tables_ref[r, j * pages + p]],
                kbuf.at[p], sem.at[p])

        for p in range(pages):
            copy(p).start()
        for p in range(pages):
            copy(p).wait()
        Di = q_ref.shape[-1]
        k = kbuf[...].reshape(tc, kbuf.shape[-1])[:, :Di]
        w = w_ref[0]  # [bq, HI]
        scale = Di ** -0.5
        acc = jnp.zeros((bq, tc), jnp.float32)
        for h in range(n_heads):
            s = lax.dot_general(q_ref[0, h], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            acc = acc + w[:, h:h + 1] * jnp.maximum(s * scale, 0.0)
        slot = lax.broadcasted_iota(jnp.int32, (bq, tc), 0)
        c = first + lax.broadcasted_iota(jnp.int32, (bq, tc), 1)
        valid = (slot < n) & (c <= pos0 + slot)
        out_ref[0] = jnp.where(valid, _positive_zero(acc), -jnp.inf)


def indexer_paged_scores(q, w, k_idx, page_tables, items: SparseItems, *,
                         layer, interpret: bool = False):
    """The indexer's scores of each item's queries over its row's paged
    indexer keys → [N, bq, C] float32 (``C`` = the page table's reach),
    -inf past each query's position.  q [N, HI, bq, Di] (an item's
    queries a head), w [N, bq, HI] float32 (the head weights, their
    ``HI^-1/2`` folded in), k_idx the pool [L, n_pages, ps, W] (``W``:
    ``Di`` in whole 128-lane tiles, zeros past it).  The sum
    over heads is taken inside the tile: no [heads, q, ctx] array is
    made."""
    N, HI, bq, Di = q.shape
    mp = page_tables.shape[1]
    ps = k_idx.shape[2]
    P = pages_per_block(mp)
    tc = P * ps
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(N, mp // P),
        in_specs=[
            pl.BlockSpec((1, HI, bq, Di), lambda i, j, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, bq, HI), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, bq, tc), lambda i, j, *_: (i, 0, j)),
        scratch_shapes=[pltpu.VMEM((P, ps, k_idx.shape[3]), k_idx.dtype),
                        pltpu.SemaphoreType.DMA((P,))],
    )
    return pl.pallas_call(
        functools.partial(_indexer_kernel, n_heads=HI, page_size=ps,
                          pages=P),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, bq, mp * ps), jnp.float32),
        interpret=interpret,
        name="indexer_paged_scores",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=SPARSE_VMEM_LIMIT_BYTES),
    )(jnp.asarray(layer, jnp.int32).reshape(1), items.row, items.pos0,
      items.n, page_tables.astype(jnp.int32), q, w.astype(jnp.float32),
      k_idx)


# -- the selection -----------------------------------------------------------


def selection_mask(scores, thr_s, thr_c, first: int = 0):
    """[M, C] chosen: above the threshold, or on it at or before its
    position (``first``: the position of column 0)."""
    c = first + lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    return (scores > thr_s[:, None]) | (
        (scores == thr_s[:, None]) & (c <= thr_c[:, None]))


def sparse_threshold(scores, k: int):
    """The exact top-``k`` of each row of ``scores`` [M, C] (float32,
    -inf where a position may not be chosen) as ``(thr_s [M], thr_c [M])``
    (:func:`selection_mask`), the portable form of :func:`sparse_select`:
    ``lax.top_k`` (equal scores: the lower position first), its k-th
    value and that value's position.  Where a row has ``k`` or fewer
    choosable positions all of them are chosen (with some -inf ones,
    which the caller's causal mask removes)."""
    M, C = scores.shape
    if C <= k:
        return (jnp.full((M,), -jnp.inf, jnp.float32),
                jnp.full((M,), C - 1, jnp.int32))
    vals, idx = lax.top_k(scores, k)
    return vals[:, k - 1], idx[:, k - 1].astype(jnp.int32)


_INT_MIN = -(1 << 31)
# the ordered key (:func:`_select_kernel`) of a -inf score
_NEG_INF_KEY = int(np.array([-np.inf], np.float32).view(np.int32)[0]
                   ^ 0x7FFFFFFF)


def _select_kernel(pos0_ref, n_ref, s_hbm, ts_ref, tc_ref, buf, keys, sem,
                   *, k, block, lanes):
    i = pl.program_id(0)
    n, pos0 = n_ref[i], pos0_ref[i]
    n_blocks, bq = keys.shape[0], keys.shape[1]
    C = n_blocks * block
    live = jnp.where(n > 0, (pos0 + n - 1) // block + 1, 0)

    def copy(b):
        return pltpu.make_async_copy(s_hbm.at[i, :, pl.ds(b * block, block)],
                                     buf.at[b], sem.at[0])

    def each_block(f):
        lax.fori_loop(0, live, lambda b, c: (f(b), c)[1], 0)

    @pl.when(live == 0)
    def _inert():
        ts_ref[0] = jnp.full((bq, 1), -jnp.inf, jnp.float32)
        tc_ref[0] = jnp.full((bq, 1), C - 1, jnp.int32)

    @pl.when(live > 0)
    def _search():
        each_block(lambda b: copy(b).start())
        each_block(lambda b: copy(b).wait())

        def to_keys(b):  # int32 whose signed order is the floats' order
            x = lax.bitcast_convert_type(buf[b], jnp.int32)
            keys[b] = x ^ ((x >> 31) & 0x7FFFFFFF)

        each_block(to_keys)

        def count(pred):
            """[bq, 1]: live positions whose key ``x`` at position ``c``
            satisfy ``pred(x, c)``, a [bq, lanes] slice at a time."""
            at = lax.broadcasted_iota(jnp.int32, (bq, lanes), 1)

            def blk(b, acc):
                for j in range(block // lanes):
                    x = keys[b, :, pl.ds(j * lanes, lanes)]
                    acc = acc + pred(x, at + (b * block + j * lanes)).astype(
                        jnp.int32)
                return acc

            acc = lax.fori_loop(0, live, blk, jnp.zeros((bq, lanes), jnp.int32))
            return jnp.sum(acc, axis=1, keepdims=True)

        def key_bit(j, t):  # t: the k-th key's leading bits, unsigned order
            cand = t | lax.shift_left(jnp.int32(1), 31 - j)
            signed = jnp.broadcast_to(cand ^ _INT_MIN, (bq, lanes))
            return jnp.where(count(lambda x, _: x >= signed) >= k, cand, t)

        t = lax.fori_loop(0, 32, key_bit, jnp.zeros((bq, 1), jnp.int32))
        t = t ^ _INT_MIN  # the k-th largest key
        t_b = jnp.broadcast_to(t, (bq, lanes))
        every = count(lambda x, _: x > _NEG_INF_KEY) <= k
        need = k - count(lambda x, _: x > t_b)  # equal keys to take, >= 1
        tie = (count(lambda x, _: x == t_b) > need) & ~every
        ts_ref[0] = jnp.where(every, -jnp.inf, lax.bitcast_convert_type(
            jnp.where(t < 0, t ^ 0x7FFFFFFF, t), jnp.float32))
        tc_ref[0] = jnp.full((bq, 1), C - 1, jnp.int32)

        @pl.when(jnp.sum(tie.astype(jnp.int32)) > 0)
        def _tie():
            width = max(1, (C - 1).bit_length())

            def pos_bit(j, p):  # p: the last equal key taken, its bits
                cand = p | lax.shift_left(jnp.int32(1), width - 1 - j)
                cand_b = jnp.broadcast_to(cand, (bq, lanes))
                fewer = count(lambda x, c: (x == t_b) & (c < cand_b)) < need
                return jnp.where(fewer, cand, p)

            p = lax.fori_loop(0, width, pos_bit, jnp.zeros((bq, 1), jnp.int32))
            tc_ref[0] = jnp.where(tie, p, C - 1)


def sparse_select(scores, items: SparseItems, k: int, *,
                  interpret: bool = False):
    """The exact top-``k`` of each query's indexer scores [N, bq, C] (as
    :func:`indexer_paged_scores` writes them) as thresholds ``(thr_s,
    thr_c)`` [N, bq, 1] (:func:`selection_mask`), a Pallas kernel: an
    item's live context blocks (those up to its last query) are fetched
    once into VMEM and searched there, the k-th largest score by
    bisection over its 32 ordered bits, then, only where a tie straddles
    it, the position of the last equal score taken, likewise.  Positions
    past an item's last query are never read.  Where a query has ``k``
    or fewer scored positions every one is chosen (``-inf``, ``C - 1``),
    as where ``C <= k``."""
    N, bq, C = scores.shape
    if C <= k:
        return (jnp.full((N, bq, 1), -jnp.inf, jnp.float32),
                jnp.full((N, bq, 1), C - 1, jnp.int32))
    block = math.gcd(C, SELECT_BLOCK)
    lanes = 128 if block % 128 == 0 else block
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(N,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((1, bq, 1), lambda i, *_: (i, 0, 0)),
                   pl.BlockSpec((1, bq, 1), lambda i, *_: (i, 0, 0))],
        scratch_shapes=[pltpu.VMEM((C // block, bq, block), jnp.float32),
                        pltpu.VMEM((C // block, bq, block), jnp.int32),
                        pltpu.SemaphoreType.DMA((1,))],
    )
    return pl.pallas_call(
        functools.partial(_select_kernel, k=k, block=block, lanes=lanes),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((N, bq, 1), jnp.float32),
                   jax.ShapeDtypeStruct((N, bq, 1), jnp.int32)),
        interpret=interpret,
        name="sparse_select",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=SPARSE_VMEM_LIMIT_BYTES),
    )(items.pos0, items.n, scores.astype(jnp.float32))


# -- attention over the chosen positions ------------------------------------


def to_items(x, items: SparseItems):
    """Flat per-token rows [T, ...] → [N, bq, ...] (zeros where no query)."""
    out = x[items.tok]
    return jnp.where(items.live.reshape(items.live.shape + (1,) * (x.ndim - 1)),
                     out, 0)


def from_items(y, items: SparseItems):
    """[N, bq, ...] → flat per-token rows [T, ...] (zeros off every row)."""
    out = y[items.item_of, items.slot_of]
    return jnp.where(items.tok_live.reshape((-1,) + (1,) * (y.ndim - 2)),
                     out, 0)


def reference_sparse_paged_attention(q, k_pages, v_pages, scores, thr_s,
                                     thr_c, page_tables, items: SparseItems,
                                     *, layer):
    """Gathered-context oracle of :func:`sparse_paged_attention`: float32
    softmax over each query's chosen positions of its row."""
    N, KV, GB, Hd = q.shape
    bq = scores.shape[1]
    G = GB // bq
    mp = page_tables.shape[1]
    ps = k_pages.shape[3]
    C = mp * ps
    k_l = lax.dynamic_index_in_dim(k_pages, layer, 0, keepdims=False)
    v_l = lax.dynamic_index_in_dim(v_pages, layer, 0, keepdims=False)
    tables = page_tables[items.row]  # [N, mp]
    k_ctx = k_l[:, tables].reshape(KV, N, C, Hd)
    v_ctx = v_l[:, tables].reshape(KV, N, C, Hd)
    keep = selection_mask(scores.reshape(N * bq, C), thr_s.reshape(-1),
                          thr_c.reshape(-1)).reshape(N, bq, C)
    keep = keep & _item_valid(items, C)
    qg = q.reshape(N, KV, G, bq, Hd)
    s = jnp.einsum("nkgqd,kncd->nkgqc", qg, k_ctx,
                   preferred_element_type=jnp.float32) * (Hd ** -0.5)
    mask = keep[:, None, None]
    s = jnp.where(mask, s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(denom == 0, 1.0, denom)
    out = jnp.einsum("nkgqc,kncd->nkgqd", p, v_ctx.astype(jnp.float32))
    return out.reshape(N, KV, GB, Hd).astype(q.dtype)


def _sparse_attn_kernel(layer_ref, row_ref, pos0_ref, n_ref, tables_ref,
                        q_ref, s_ref, ts_ref, tc_ref, k_hbm, v_hbm, out_ref,
                        kbuf, vbuf, sem, m_sc, l_sc, acc_sc, *, page_size,
                        pages, groups):
    i, j = pl.program_id(0), pl.program_id(1)
    bq, tc = s_ref.shape[1], s_ref.shape[2]
    KV, Hd = kbuf.shape[1], kbuf.shape[3]
    n, pos0 = n_ref[i], pos0_ref[i]
    last = pos0 + n - 1
    first = j * tc

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, _MASKED, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(first <= last)
    def _attend():
        r = row_ref[i]

        def copies(p):
            page = tables_ref[r, j * pages + p]
            return (pltpu.make_async_copy(k_hbm.at[layer_ref[0], :, page],
                                          kbuf.at[p], sem.at[0, p]),
                    pltpu.make_async_copy(v_hbm.at[layer_ref[0], :, page],
                                          vbuf.at[p], sem.at[1, p]))

        # every page of the block, those past the row's last position
        # too (trash-padded: finite), so no stale buffer meets a zero weight
        for p in range(pages):
            for cp in copies(p):
                cp.start()
        for p in range(pages):
            for cp in copies(p):
                cp.wait()
        sc = s_ref[0]  # [bq, tc]
        slot = lax.broadcasted_iota(jnp.int32, (bq, tc), 0)
        c = first + lax.broadcasted_iota(jnp.int32, (bq, tc), 1)
        keep = ((sc > ts_ref[0]) | ((sc == ts_ref[0]) & (c <= tc_ref[0])))
        keep = keep & (slot < n) & (c <= pos0 + slot)
        keep = jnp.concatenate([keep.astype(jnp.float32)] * groups,
                               axis=0) > 0  # [G * bq, tc]
        scale = Hd ** -0.5
        for h in range(KV):
            k = kbuf[:, h].reshape(tc, Hd)
            v = vbuf[:, h].reshape(tc, Hd)
            s = lax.dot_general(q_ref[0, h], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep, s, _MASKED)
            m_prev = m_sc[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_sc[h] = alpha * l_sc[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_sc[h] = alpha * acc_sc[h] + lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_sc[h] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        l = l_sc[...]
        out_ref[0] = (acc_sc[...] / jnp.where(l == 0, 1.0, l)).astype(
            out_ref.dtype)


def sparse_paged_attention(q, k_pages, v_pages, scores, thr_s, thr_c,
                           page_tables, items: SparseItems, *, layer,
                           interpret: bool = False):
    """GQA attention of each item's queries over the positions its
    selection chose → [N, KV, G * bq, Hd].  q [N, KV, G * bq, Hd] (row
    ``g * bq + slot`` of a KV head's group), k_pages / v_pages the pools
    [L, KV, n_pages, ps, Hd] read in place, scores [N, bq, C] the
    indexer's, thr_s / thr_c [N, bq] the selection
    (:func:`sparse_threshold`).  Context blocks past an item's last
    query are neither fetched nor scored."""
    N, KV, GB, Hd = q.shape
    bq = scores.shape[1]
    mp = page_tables.shape[1]
    ps = k_pages.shape[3]
    P = pages_per_block(mp)
    tc = P * ps

    def scores_block(i, j, layer, row, pos0, n, tables):
        last_block = jnp.maximum(pos0[i] + n[i] - 1, 0) // tc
        return (i, 0, jnp.minimum(j, last_block))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(N, mp // P),
        in_specs=[
            pl.BlockSpec((1, KV, GB, Hd), lambda i, j, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, bq, tc), scores_block),
            pl.BlockSpec((1, bq, 1), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec((1, bq, 1), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, KV, GB, Hd), lambda i, j, *_: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((P, KV, ps, Hd), k_pages.dtype),
            pltpu.VMEM((P, KV, ps, Hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, P)),
            pltpu.VMEM((KV, GB, 1), jnp.float32),
            pltpu.VMEM((KV, GB, 1), jnp.float32),
            pltpu.VMEM((KV, GB, Hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_sparse_attn_kernel, page_size=ps, pages=P,
                          groups=GB // bq),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, KV, GB, Hd), q.dtype),
        interpret=interpret,
        name="sparse_paged_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=SPARSE_VMEM_LIMIT_BYTES),
    )(jnp.asarray(layer, jnp.int32).reshape(1), items.row, items.pos0,
      items.n, page_tables.astype(jnp.int32), q, scores,
      thr_s.reshape(N, bq, 1).astype(jnp.float32),
      thr_c.reshape(N, bq, 1).astype(jnp.int32), k_pages, v_pages)
