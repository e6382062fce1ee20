"""Ragged paged attention over LATENT pages (multi-head latent attention,
DeepSeek-V2) as a Pallas TPU kernel, with its jnp oracle.

A position's cache is one row shared by every head: the compressed KV
(after its norm, ``rank`` values) and the one rotated rope key (``rope``
values), stored in whole 128-lane tiles (``W`` >= rank + rope, zeros
past them) so that a page moves as whole tiles.  In the
absorbed form a query head is ``[q_nope W_UK^T | q_rope]`` (softmax
scale folded in by the caller), its score against a row is one dot over
``rank + rope``, and the values are the row's first ``rank`` columns —
so a page is read once and serves both products, and all ``H`` heads
share it (``H`` x the arithmetic per byte of a GQA page: the kernel is
bound by compute, not by reading the cache).

Descriptors are the one ragged kernel's
(:func:`fusioninfer_tpu.ops.paged_attention.ragged_paged_attention`):
flat ragged-concat tokens, per-row ``(row_start, q_begin, q_len)`` and
page tables, decode rows and prefill chunks in one grid.  Grid
``(tiles,)`` over tiles of ``block_q`` flat tokens, run in order; the
wrapper lists each tile's walks (the rows that have tokens in it, each
with its causal page span: :func:`mla_walk_lists`, the ragged family's
builder with one column and no window; inert rows and empty tiles give
no walk) and the kernel scores its tile's slice of the list with an
online softmax.  The pages of ALL the call's walks are one page stream
(the ragged family's protocol, :class:`_LatentPageStream`): a ring of
``MLA_RING_SLOTS`` slots whose fetch cursor runs down the flat list
ahead of the scorer, through page, row and tile boundaries, so the
first pages of the next row, and of the next tile's first row, are in
flight while the current row is still being scored; the only cold wait
of a call is its first slot.  A row that started its own first copy
waited for all of it, 64 rows x 5 layers a decode pass.

A slot holds ``MLA_PAGES_PER_UPDATE`` = 2 consecutive pages of a walk,
side by side, and one softmax update scores them: a page is 160 KiB,
0.2 us of HBM time, and a decode row's dots on it 0.18 us of the MXU,
but what a page costs is the update itself (the ``[N, ps]`` softmax and
the float32 ``[N, rank]`` accumulator rescaled once a page), which no
ring depth hides (PERF.md section 6, PR 33: the ring's depth moved 64
decode rows at 3.8 k by 4 %, two pages an update by 23 %).  A walk of an
odd page count copies only its pages; the rest of its last slot holds an
earlier walk's page (zeros before any), all of it past the row's
context, so the causal mask gives it exactly zero weight.

A row that has ONE token in the tile (a decode row) scores only that
token's ``H`` head rows; a row with more (a chunk) scores the whole
``[block_q * H]`` tile under its live mask.  Dots take bfloat16 operands
and accumulate in float32; the softmax state is float32.  Each walk
starts fresh accumulators, both row kinds pair a walk's pages the same
way from its first, and every dot and reduction is row-wise, so a
token's output bits depend on its row alone: not on its flat offset, its
neighbours, or which ring slot held a page.

There is no KV-split grid: a v5e chip has one TensorCore, so programs of
a split run one after another and the combine would only add work.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fusioninfer_tpu.ops.paged_attention import (
    _F_PAGE,
    _F_WALK,
    _STARTED,
    _STREAM_STATE,
    _TAKEN,
    NEG_INF,
    RAGGED_RING_SLOTS,
    _check_walks,
    _div,
    _ragged_walks,
    ragged_token_rows,
)

MLA_BLOCK_Q = 8
# the q and out tiles ([block_q, H, rank] twice, double-buffered), the
# float32 accumulator of a [block_q * H, rank] tile and the score
# temporaries pass Mosaic's default scoped limit at published widths
MLA_VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# pages an online-softmax update scores (a ring slot holds them side by
# side): what a page costs is the update, not its copy (module docstring)
MLA_PAGES_PER_UPDATE = 2
# slots of the page ring, the ragged family's depth: 3 x 2 pages of
# [ps, W], 960 KiB at published widths
MLA_RING_SLOTS = RAGGED_RING_SLOTS


class _LatentPageStream:
    """The ragged family's page stream (protocol, cursor and SMEM state:
    the comment above :class:`fusioninfer_tpu.ops.paged_attention.
    _PageStream`) over latent pages, as trace-time glue of its own: what
    differs is the copy (``[ps, W]`` pages, no K/V pair, no scales, no
    head axis, one walk list a call) and the unit.  A ring slot holds
    the ``unit`` consecutive pages of a walk that one softmax update
    scores, side by side; the copies of a walk's last slot stop at its
    last page, and what an earlier walk left in the rest of the slot is
    past the row's context, so causality masks it."""

    def __init__(self, state, tile_walks_ref, walk_refs, table_ref,
                 layer_ref, pages_ref, kv_buf, sem, page_size):
        self.state = state
        self.tile_walks_ref = tile_walks_ref
        self.w_row, self.w_first, self.w_end = walk_refs
        self.n_walks = tile_walks_ref[tile_walks_ref.shape[0] - 1]
        self.table_ref = table_ref
        self.layer_ref = layer_ref
        self.pages_ref = pages_ref
        self.kv_buf = kv_buf
        self.sem = sem
        self.ps = page_size
        self.slots = kv_buf.shape[0]
        self.unit = kv_buf.shape[1] // page_size

    def _each_copy(self, slot, r, p, end, do):
        """``do`` every copy of the slot that starts at page ``p`` of row
        ``r``: the first always, a later one where the walk has it."""
        for i in range(self.unit):
            def one(i=i):
                do(pltpu.make_async_copy(
                    self.pages_ref.at[self.layer_ref[0], 0,
                                      self.table_ref[r, p + i]],
                    self.kv_buf.at[slot, pl.ds(i * self.ps, self.ps)],
                    self.sem.at[slot, i]))
            one() if i == 0 else pl.when(p + i < end)(one)

    def fetch(self):
        """Start the copies of the cursor's slot, if the call has one
        left, and move the cursor one slot on."""
        st = self.state
        w = st[_F_WALK]

        @pl.when(w < self.n_walks)
        def _start():
            p, k, end = st[_F_PAGE], st[_STARTED], self.w_end[w]
            self._each_copy(jax.lax.rem(k, self.slots), self.w_row[w], p,
                            end, lambda cp: cp.start())
            st[_STARTED] = k + 1
            last = p + self.unit >= end
            nxt = jnp.minimum(w + 1, self.w_row.shape[0] - 1)
            st[_F_WALK] = jax.lax.select(last, w + 1, w)
            st[_F_PAGE] = jax.lax.select(last, self.w_first[nxt],
                                         p + self.unit)

    def prime(self):
        """The call's first program: ``slots - 1`` slots in flight; every
        later one is fetched by the scorer, one per slot taken."""
        st = self.state
        st[_STARTED] = 0
        st[_TAKEN] = 0
        st[_F_WALK] = 0
        st[_F_PAGE] = self.w_first[0]
        jax.lax.fori_loop(0, self.slots - 1,
                          lambda _, c: (self.fetch(), c)[1], 0)

    def take(self, r, p, end):
        """The scorer's side, once per slot of a walk in order: keep the
        stream ``slots - 1`` ahead, wait for the slot that holds pages
        ``[p, min(p + unit, end))`` of row ``r`` and name it."""
        self.fetch()
        k = self.state[_TAKEN]
        self.state[_TAKEN] = k + 1
        slot = jax.lax.rem(k, self.slots)
        self._each_copy(slot, r, p, end, lambda cp: cp.wait())
        return slot


def _mla_kernel(
    # scalar prefetch
    page_tables_ref,  # [R, mp] int32 (SMEM)
    row_starts_ref,  # [R] int32: global position of each row's token 0
    q_begins_ref,  # [R] int32: flat offset of each row's segment
    q_lens_ref,  # [R] int32: row token count (0 = inert row)
    tile_walks_ref,  # [nb + 1] int32: mla_walk_lists
    w_row_ref,  # [nb + R] int32
    w_first_ref,  # [nb + R] int32
    w_end_ref,  # [nb + R] int32
    layer_ref,  # [1] int32
    # inputs
    qc_ref,  # [block_q, H, rank] VMEM tile: queries against the latent
    qr_ref,  # [block_q, H, rope] VMEM tile: rope queries
    pages_ref,  # [L, 1, n_pages, ps, W] in HBM
    # output, scratch
    o_ref,  # [block_q, H, rank]
    kv_buf,  # [MLA_RING_SLOTS, MLA_PAGES_PER_UPDATE * ps, W]: the ring
    sem,  # DMA semaphores [MLA_RING_SLOTS, MLA_PAGES_PER_UPDATE]
    state,  # the stream's cursor (SMEM)
    *,
    block_q: int,
    page_size: int,
    rank: int,
    rope: int,
):
    t = pl.program_id(0)
    t0 = t * block_q
    H = qc_ref.shape[1]
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    stream = _LatentPageStream(
        state, tile_walks_ref, (w_row_ref, w_first_ref, w_end_ref),
        page_tables_ref, layer_ref, pages_ref, kv_buf, sem, page_size)
    span = kv_buf.shape[1]  # positions a slot holds

    @pl.when(t == 0)
    def _first_program():
        # a slot's rest past a walk's last page is scored under the
        # causal mask: make it numbers before anything reads it
        kv_buf[...] = jnp.zeros(kv_buf.shape, kv_buf.dtype)
        stream.prime()

    def score(w, r, qc, qr, pos, live):
        """Online softmax of query rows ``qc``/``qr`` [N, .] (positions
        ``pos`` [N, 1], liveness ``live`` [N, 1] or None) over the pages
        of walk ``w`` (row ``r``), a ring slot an update, taken from the
        stream in list order → normalized [N, rank] float32."""
        N = qc.shape[0]
        first, end = w_first_ref[w], w_end_ref[w]

        def body(j, carry):
            m, l, acc = carry
            p = first + j * stream.unit
            slot = stream.take(r, p, end)
            kc = kv_buf[slot, :, :rank]  # [span, rank]: keys AND values
            kr = kv_buf[slot, :, rank:rank + rope]  # [span, rope]
            s = jax.lax.dot_general(
                qc, kc, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = s + jax.lax.dot_general(
                qr, kr, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [N, span]
            ctx = p * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (N, span), 1)
            keep = ctx <= pos
            if live is not None:
                keep = keep & live
            s = jnp.where(keep, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            pexp = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(pexp, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                pexp.astype(kc.dtype), kc, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)  # [N, rank]
            return m_new, l_new, acc * alpha + pv

        m0 = jnp.full((N, 1), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((N, 1), jnp.float32)
        a0 = jnp.zeros((N, rank), jnp.float32)
        _, l, acc = jax.lax.fori_loop(
            0, _div(end - first + (stream.unit - 1), stream.unit), body,
            (m0, l0, a0))
        return acc / jnp.maximum(l, 1e-20)

    def walk_body(w, carry):
        r = w_row_ref[w]
        qb, ql, st = q_begins_ref[r], q_lens_ref[r], row_starts_ref[r]
        lo = jnp.maximum(qb, t0)
        hi = jnp.minimum(qb + ql, t0 + block_q)

        @pl.when(hi - lo == 1)
        def _one_token():  # a decode row: H head rows, not the tile's
            i = lo - t0
            out = score(w, r, qc_ref[i], qr_ref[i], st + lo - qb, None)
            o_ref[i] = out.astype(o_ref.dtype)

        @pl.when(hi - lo > 1)
        def _chunk():
            N = block_q * H
            tok = t0 + _div(
                jax.lax.broadcasted_iota(jnp.int32, (N, 1), 0), H)
            live = (tok >= qb) & (tok < qb + ql)
            out = score(w, r, qc_ref[...].reshape(N, rank),
                        qr_ref[...].reshape(N, qr_ref.shape[2]),
                        st + tok - qb, live)
            out = out.astype(o_ref.dtype).reshape(block_q, H, rank)
            o_ref[...] = jnp.where(
                live.reshape(block_q, H, 1), out, o_ref[...])

        return carry

    jax.lax.fori_loop(tile_walks_ref[t], tile_walks_ref[t + 1], walk_body, 0)


def mla_walk_lists(n_tokens: int, pages, row_starts, q_begins, q_lens, *,
                   block_q: int = MLA_BLOCK_Q):
    """The walk lists :func:`mla_ragged_paged_attention` prefetches for
    these rows over ``n_tokens`` flat tokens: per tile of ``block_q``
    tokens the rows that have tokens in it, each with its causal page
    span; inert rows and tiles no row touches give no walk (the ragged
    family's builder, one column, no window).  A caller that scores the
    same rows many times — every layer of a scan — builds them ONCE and
    passes ``walks=``."""
    i32 = jnp.int32
    return _ragged_walks(
        q_begins.astype(i32), q_lens.astype(i32), row_starts.astype(i32),
        nb=-(-n_tokens // block_q), block_q=block_q,
        page_size=pages.shape[3], window=None)


@functools.partial(jax.jit,
                   static_argnames=("rank", "interpret", "block_q"))
def mla_ragged_paged_attention(
    q_lat: jax.Array,  # [T, H, rank]: q_nope W_UK^T, softmax scale folded in
    q_rope: jax.Array,  # [T, H, rope], likewise scaled
    pages: jax.Array,  # [L, 1, n_pages, ps, W] latent pool
    page_tables: jax.Array,  # [R, max_pages] int32: per-ROW tables
    row_starts: jax.Array,  # [R] int32: global position of row's token 0
    q_begins: jax.Array,  # [R] int32: flat offset of each row's segment
    q_lens: jax.Array,  # [R] int32: row token count (0 = inert row)
    *,
    layer: jax.Array | int,
    rank: int,
    interpret: bool = False,
    block_q: int = MLA_BLOCK_Q,
    walks=None,
) -> jax.Array:
    """Absorbed-form attention of flat ragged tokens over their rows'
    latent pages → the attention-weighted latent rows [T, H, rank]
    (``W_UV`` and the output projection are the caller's).  Token ``t``
    of row ``r`` sits at ``row_starts[r] + (t - q_begins[r])`` and
    attends causally over row ``r``'s pages, its own row included (the
    caller writes before it attends).  Rows must be packed in flat order
    (``q_begins`` non-decreasing, segments disjoint).  Tokens of no row
    give zeros.  ``walks``: :func:`mla_walk_lists` of the same rows."""
    T, H, _ = q_lat.shape
    rope = q_rope.shape[2]
    page_size = pages.shape[3]
    Tp = -(-T // block_q) * block_q
    if Tp != T:
        q_lat = jnp.pad(q_lat, ((0, Tp - T), (0, 0), (0, 0)))
        q_rope = jnp.pad(q_rope, ((0, Tp - T), (0, 0), (0, 0)))
    nb = Tp // block_q
    if walks is None:
        walks = mla_walk_lists(Tp, pages, row_starts, q_begins, q_lens,
                               block_q=block_q)
    walks = _check_walks(walks, 1, nb)

    def tile(width):
        return pl.BlockSpec((block_q, H, width), lambda t, *_: (t, 0, 0),
                            memory_space=pltpu.VMEM)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=(nb,),
        in_specs=[tile(rank), tile(rope), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tile(rank),
        scratch_shapes=[
            pltpu.VMEM((MLA_RING_SLOTS, MLA_PAGES_PER_UPDATE * page_size,
                        pages.shape[4]), pages.dtype),
            pltpu.SemaphoreType.DMA((MLA_RING_SLOTS, MLA_PAGES_PER_UPDATE)),
            _STREAM_STATE],
    )
    # the tile axis carries the page stream from step to step: it runs
    # in order on one core
    out = pl.pallas_call(
        functools.partial(_mla_kernel, block_q=block_q, page_size=page_size,
                          rank=rank, rope=rope),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, H, rank), q_lat.dtype),
        interpret=interpret,
        name="mla_ragged_paged_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=MLA_VMEM_LIMIT_BYTES),
    )(page_tables.astype(jnp.int32), row_starts.astype(jnp.int32),
      q_begins.astype(jnp.int32), q_lens.astype(jnp.int32), *walks,
      jnp.asarray(layer, jnp.int32).reshape(1), q_lat, q_rope, pages)
    return out[:T]


def reference_mla_ragged_paged_attention(q_lat, q_rope, pages, page_tables,
                                         row_starts, q_begins, q_lens, *,
                                         layer, rank):
    """Gathered-context jnp oracle of :func:`mla_ragged_paged_attention`
    (and the portable path off the TPU): float32 softmax over each
    token's own row's pages.  Tokens of no row give zeros."""
    T = q_lat.shape[0]
    ps = pages.shape[3]
    mp = page_tables.shape[1]
    row_of, off, live = ragged_token_rows(q_begins, q_lens, T)
    pos = row_starts[row_of] + off
    pool = jax.lax.dynamic_index_in_dim(pages, layer, 0, keepdims=False)[0]
    q = jnp.concatenate([q_lat, q_rope], axis=-1)
    ctx = pool[page_tables[row_of]].reshape(T, mp * ps, -1)[
        ..., :q.shape[-1]]  # [T, S, rank + rope]
    s = jnp.einsum("thd,tsd->ths", q, ctx,
                   preferred_element_type=jnp.float32)
    keep = (jnp.arange(mp * ps)[None, :] <= pos[:, None]) & live[:, None]
    s = jnp.where(keep[:, None, :], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1) * live[:, None, None]
    out = jnp.einsum("ths,tsr->thr", probs.astype(ctx.dtype),
                     ctx[..., :rank], preferred_element_type=jnp.float32)
    return out.astype(q_lat.dtype)
