"""Paged attention as Pallas TPU kernels: one ragged family.

Each sequence's KV context lives in non-contiguous cache pages
(:mod:`fusioninfer_tpu.engine.kv_cache`); the kernels stream exactly the
live pages HBM→VMEM ahead of an online softmax, as one page stream a
program column — no materialized ``cache[page_tables]`` gather (which
copies the whole context through HBM every step, the portable-baseline
cost in :mod:`fusioninfer_tpu.engine.model_runner`).

Every paged forward of the engine scores through
:func:`ragged_paged_attention` or :func:`ragged_paged_attention_kvsplit`:
a flat ragged-concat token axis whose per-row ``(start, q_begin,
q_len)`` descriptors cover decode rows, speculative verify windows,
budgeted prefill chunks and cache-hit suffixes with no per-row rectangle
padding and no kernel switch between row kinds (the Ragged Paged
Attention layout, PAPERS.md).  Three grids share one walk body
(:func:`_ragged_walk`) and one page stream (:class:`_PageStream`);
:func:`resolve_ragged_grid` picks among them from what it can observe
(the VMEM footprint of the shapes and dtypes) and
:func:`pick_kv_splits` from the cache's static context bound:

* **coalesced**, grid ``(tiles,)``: one program a q tile covers every KV
  head — one ``[KV, ps, Hd]`` copy a page, score and value dots batched
  over KV.
* **per-head**, grid ``(KV, tiles)``: the coalesced grid's VMEM
  fallback — ``[ps, Hd]`` copies and one head's dots, KV× less scratch.
* **KV-split**, grid ``(splits, tiles)``: flash-decode partials over
  fixed virtual page chunks, combined left to right by the wrapper.

Equivalent capability in the reference is vLLM's CUDA PagedAttention,
which FusionInfer only orchestrates (SURVEY §0); here it is an in-repo
TPU kernel.

Layout: pages are **head-major** ``[KV, n_pages, page_size, Hd]``.  That
matters for Mosaic: both page copies (``.at[g, page]`` and
``.at[:, page]``) slice only *leading* dims, so every copy is whole
``[page_size, Hd]`` tiles of the (8,128)-tiled memref.  The previous
``[n_pages, ps, KV, Hd]`` layout sliced the tiled second-to-minor dim to
width 1 per head, which Mosaic rejects ("Slice shape along dimension 2
must be aligned to tiling (8)").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fusioninfer_tpu.ops.masks import attend

NEG_INF = -1e30


def _page_dma(slot, layer, g, page, k_pages_ref, v_pages_ref, k_buf, v_buf,
              sem, scale_refs=None, scale_bufs=None):
    """Async copies for one page of K/V (+ their [1, ps] scale rows when
    the cache is int8) — the ONE place the quantized operand/semaphore
    layout lives for every grid.  Pages are layer-stacked head-major
    ``[L, KV, n_pages, ps, Hd]``: ``layer`` is the scan's layer scalar
    and ``g`` is either a head index (per-head grids:
    ``.at[layer, g, page]`` squeezes three leading dims) or
    ``slice(None)`` (coalesced grid: ``.at[layer, :, page]`` copies all
    KV heads at once); both slice only leading dims and copy whole
    trailing tiles — Mosaic-clean."""
    copies = [
        pltpu.make_async_copy(
            k_pages_ref.at[layer, g, page], k_buf.at[slot], sem.at[slot, 0]
        ),
        pltpu.make_async_copy(
            v_pages_ref.at[layer, g, page], v_buf.at[slot], sem.at[slot, 1]
        ),
    ]
    if scale_refs is not None:
        ks_ref, vs_ref = scale_refs
        ks_buf, vs_buf = scale_bufs
        copies += [
            pltpu.make_async_copy(
                ks_ref.at[layer, g, page], ks_buf.at[slot], sem.at[slot, 2]
            ),
            pltpu.make_async_copy(
                vs_ref.at[layer, g, page], vs_buf.at[slot], sem.at[slot, 3]
            ),
        ]
    return copies


def _as_stacked(k_pages, v_pages, k_scales, v_scales, layer):
    """Normalize page operands to the layer-stacked ``[L, KV, …]`` form
    the kernels use internally.  4-d single-layer arrays (oracles,
    tests, probes) wrap to ``L=1`` with ``layer=0`` — a free
    reshape; 5-d arrays require an explicit ``layer``."""
    if k_pages.ndim == 4:
        if layer is not None:
            raise ValueError("layer= only applies to stacked 5-d pages")
        k_pages, v_pages = k_pages[None], v_pages[None]
        if k_scales is not None:
            k_scales, v_scales = k_scales[None], v_scales[None]
        layer = 0
    elif layer is None:
        raise ValueError("stacked [L, ...] pages require layer=")
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    return k_pages, v_pages, k_scales, v_scales, layer_arr


def _split_rest(rest, quantized):
    """Unpack a paged kernel's trailing refs: (scale_refs, o_ref, value
    bufs, scale_bufs, sem) — the one place the quantized ref layout lives."""
    if quantized:
        ks_ref, vs_ref, o_ref, k_buf, v_buf, ks_buf, vs_buf, sem = rest
        return (ks_ref, vs_ref), o_ref, k_buf, v_buf, (ks_buf, vs_buf), sem
    o_ref, k_buf, v_buf, sem = rest
    return None, o_ref, k_buf, v_buf, None, sem


# VMEM ceiling for the coalesced grids' page ring plus their q/out
# tiles and partial blocks, as the *_fits_vmem guards
# count them.  No pallas_call here sets ``vmem_limit_bytes``, so the
# bound that matters is Mosaic's default scoped-VMEM limit: compiled
# for a v5e target (libtpu 0.0.34), footprints of 8.5 MiB by this
# arithmetic are accepted and 16 MiB is refused ("Ran out of memory in
# memory space vmem") — 8 MiB leaves the compiler's own temporaries
# their room.  Oversized configurations (huge page_size × Hd × KV
# products) fall back to the per-head grid, whose per-slot scratch is
# KV× smaller, instead of failing Mosaic allocation at trace time.
_COALESCE_VMEM_SCRATCH_BUDGET = 8 * 1024 * 1024


def coalesced_scratch_bytes(page_size: int, Hd: int, kv_heads: int,
                            k_dtype, v_dtype, quantized: bool,
                            slots: int) -> int:
    """Bytes of VMEM scratch a coalesced grid allocates: ``slots`` ring
    slots (``RAGGED_RING_SLOTS``) of ``[KV, ps, Hd]`` K and V page
    buffers (+ two f32 ``[KV, 1, ps]`` scale rows per slot when the
    cache is int8)."""
    per_slot = kv_heads * page_size * Hd * (
        jnp.dtype(k_dtype).itemsize + jnp.dtype(v_dtype).itemsize)
    if quantized:
        per_slot += 2 * kv_heads * page_size * jnp.dtype(jnp.float32).itemsize
    return slots * per_slot


def _page_specs_scratch(page_size, Hd, k_dtype, v_dtype, quantized,
                        slots: int, heads: int | None = None):
    """(in_specs for page operands, scratch shapes) shared by the three
    ragged grids — quantized adds scale operands, scale buffers, and
    two more DMA semaphores per slot.  ``slots``: the page ring
    (``RAGGED_RING_SLOTS``).  ``heads``: the coalesced grids buffer all
    KV heads of a page per slot (``[slots, KV, ps, Hd]``); the per-head
    grid passes None (``[slots, ps, Hd]``)."""
    lead = () if heads is None else (heads,)
    page_specs = [pl.BlockSpec(memory_space=pl.ANY)] * (4 if quantized else 2)
    scratch = [
        pltpu.VMEM((slots, *lead, page_size, Hd), k_dtype),
        pltpu.VMEM((slots, *lead, page_size, Hd), v_dtype),
    ]
    if quantized:
        scratch += [
            pltpu.VMEM((slots, *lead, 1, page_size), jnp.float32),
            pltpu.VMEM((slots, *lead, 1, page_size), jnp.float32),
        ]
    scratch.append(pltpu.SemaphoreType.DMA((slots, 4 if quantized else 2)))
    return page_specs, scratch


def _scores(q, k, k_scale, sm_scale=None):
    """q·kᵀ with the int8 page scale folded in AFTER the dot
    (q·(s·k8) == s·(q·k8)) — pages never materialize dequantized.
    ``q`` and ``k`` are 2-d (one head) or carry a leading KV axis the
    dot batches over.  ``sm_scale`` (the ragged grids' native-dtype
    path, :func:`_ragged_q`): ``q`` comes unscaled in the pages' own
    16-bit dtype, the dot takes both as stored and accumulates in
    float32, and the scale multiplies the float32 scores."""
    if sm_scale is None and k.dtype != jnp.float32:
        k = k.astype(jnp.float32)
    lead = tuple(range(q.ndim - 2))
    s = jax.lax.dot_general(
        q, k, (((q.ndim - 1,), (k.ndim - 1,)), (lead, lead)),
        preferred_element_type=jnp.float32,
    )
    if sm_scale is not None:
        s = s * sm_scale
    if k_scale is not None:
        s = s * k_scale  # [(KV,) 1, ps] broadcasts over rows
    return s


def _weighted_values(pexp, v, v_scale):
    """pexp·v with the int8 value scale folded into the probabilities;
    2-d operands or a leading KV axis the dot batches over."""
    if v_scale is not None:
        pexp = pexp * v_scale  # [(KV,) 1, ps] broadcast
        v = v.astype(jnp.float32)
    else:
        pexp = pexp.astype(v.dtype)
    lead = tuple(range(pexp.ndim - 2))
    return jax.lax.dot_general(
        pexp, v, (((pexp.ndim - 1,), (v.ndim - 2,)), (lead, lead)),
        preferred_element_type=jnp.float32,
    )


# -- the ragged grids --------------------------------------------------

# q-tile length over the FLAT token axis.  Per (tile, row) the kernel
# scores all block_q tokens of the tile against the row's pages and
# masks the tokens outside the row, so the MXU waste per decode-heavy
# tile is bounded by block_q; larger tiles amortize the page loop for
# long chunk rows.  8 = one f32 sublane tile: the decode-heavy default.
# Static per process — per-row results are independent of tile
# composition (see _ragged_walk below), so one value per process keeps
# split and fused dispatches bit-identical.
RAGGED_BLOCK_Q = 8


def ragged_fits_vmem(block_q: int, page_size: int, Hd: int, kv_heads: int,
                     group: int, q_dtype, k_dtype, v_dtype,
                     quantized: bool, budget: int | None = None) -> bool:
    """True when the coalesced ragged grid's VMEM footprint — the page
    ring [RAGGED_RING_SLOTS, KV, ps, Hd] PLUS the q and out
    tiles [block_q, KV, G, Hd] — fits the conservative budget; callers
    fall back to the per-head grid (page scratch KV× smaller, tiles
    per-head) otherwise.  ``budget`` resolves at CALL time so tests
    (and future per-generation tables) can tune the module default."""
    if budget is None:
        budget = _COALESCE_VMEM_SCRATCH_BUDGET
    pages = coalesced_scratch_bytes(page_size, Hd, kv_heads,
                                    k_dtype, v_dtype, quantized,
                                    slots=RAGGED_RING_SLOTS)
    tiles = 2 * block_q * kv_heads * group * Hd * jnp.dtype(q_dtype).itemsize
    return pages + tiles <= budget


def _ragged_block_rows(q_begins: jax.Array, q_lens: jax.Array,
                       nb: int, block_q: int) -> jax.Array:
    """Per-tile ``(first_row, n_rows)`` map [nb, 2]: the rows whose flat
    segments ``[q_begins[r], q_begins[r] + q_lens[r])`` intersect tile
    ``t``'s token span.  Rows must be packed in flat order (``q_begins``
    non-decreasing, segments disjoint); zero-length rows inside the
    range are harmless (their tile intersection is empty)."""
    R = q_begins.shape[0]
    ends = q_begins + q_lens
    t0s = jnp.arange(nb, dtype=jnp.int32) * block_q
    first = jnp.searchsorted(ends, t0s, side="right").astype(jnp.int32)
    last = (jnp.searchsorted(q_begins, t0s + block_q, side="left")
            .astype(jnp.int32) - 1)
    first = jnp.minimum(first, R - 1)
    n = jnp.clip(last - first + 1, 0, R)
    return jnp.stack([first, n], axis=1)


# -- the ragged grids' page stream -------------------------------------
#
# A ragged grid's work is a sequence of WALKS: one per (q tile, row of
# the tile, page chunk of the program), each over pages ``[first, end)``
# of its row's table, most of them short (a decode row at 800 tokens is
# 7 pages over two chunks).  A walk that starts its own first copy and
# waits for it pays the DMA's whole latency with nothing to score, and
# on one TensorCore nothing else hides it.  So the walks of a COLUMN —
# the programs that share the leading grid index (a KV split, a head)
# and run their tiles in order — are one page stream:
#
# * The wrapper lists each column's NON-EMPTY walks in program order
#   (:func:`_ragged_walks`, scalar-prefetched): the scorer loops over its
#   tile's slice of the list, the fetch cursor runs down the same list,
#   so both sides see one sequence and the kernel enumerates nothing.
# * The cursor runs ``slots - 1`` pages ahead of the scorer THROUGH walk,
#   row, tile and program boundaries: ring, DMA semaphores and cursor
#   live in scratch, which persists across the sequential steps of the
#   tile axis.  The column's first program primes it; the leading axis
#   stays ``"parallel"`` and the stream never crosses it.  The only cold
#   wait of a column is its first page.
# * The k-th copy started is the k-th waited for, in slot ``k % slots``;
#   every copy started is waited exactly once by the column's last tile.
#   Only pages a listed walk will score are fetched: nothing past a row's
#   live pages, nothing for inert rows or empty chunks.
# * Arithmetic is untouched: a walk scores the same pages in the same
#   order with fresh accumulators; which slot held a page decides no bit.

# ring slots of the stream: the scorer waits for a page with up to
# ``RAGGED_RING_SLOTS - 1`` later pages in flight.  A slot is one page of
# K and V for the program's heads (512 KiB at 8 KV heads x 128 x 128
# bfloat16); the *_fits_vmem guards count the ring.
RAGGED_RING_SLOTS = 3

# words of a column's stream state (SMEM scratch): copies started, pages
# taken by the scorer, and the fetch cursor (its walk, its next page)
_STARTED, _TAKEN, _F_WALK, _F_PAGE = range(4)
_STREAM_STATE = pltpu.SMEM((4,), jnp.int32)


def _div(a, b: int):
    """``a // b`` for non-negative ``a``: one truncating division, where
    ``//`` traces a sign-correcting dozen operations."""
    return jax.lax.div(a, jnp.int32(b))


def _ragged_walks(q_begins, q_lens, row_starts, *, nb: int, block_q: int,
                  page_size: int, window, n_cols: int = 1, cpp: int = 1,
                  chunk_pages: int | None = None):
    """Each column's non-empty walks in program order, as flat int32
    arrays for scalar prefetch: ``tile_walks`` [n_cols * (nb + 1)] —
    column ``s``'s walks of tile ``t`` are entries ``[tile_walks[s *
    (nb + 1) + t], tile_walks[s * (nb + 1) + t + 1])`` of its list — and
    the lists ``w_row`` (``row * cpp + chunk slot``), ``w_first``,
    ``w_end`` [n_cols * W], ``W = (nb + R) * cpp`` entries a column.

    A walk is (tile, row of the tile, chunk of the column): column ``s``
    owns page chunks ``[s * cpp, (s + 1) * cpp)`` of ``chunk_pages``
    pages each (None: one unclipped chunk).  Entries past a column's
    count are zeros and never read as walks.  Compare, select and sum
    over small index grids only: nothing here gathers, sorts or loops."""
    R = q_begins.shape[0]
    # (tile, row) pairs in program order.  Rows lie in flat order, so
    # tile-major order is row-major order: row r's pairs are the
    # n_tiles[r] entries after those of the rows before it; consecutive
    # rows share at most one tile, so nb + R bounds their number
    P = nb + R
    t_first = _div(q_begins, block_q)
    n_tiles = jnp.where(
        q_lens > 0,
        _div(jnp.maximum(q_begins + q_lens - 1, 0), block_q) - t_first + 1,
        0)
    after = jax.lax.cumsum(n_tiles, axis=0)
    p = jax.lax.iota(jnp.int32, P)[:, None]
    mine = (p >= (after - n_tiles)[None, :]) & (p < after[None, :])  # [P, R]
    qb, ql, st, r, t = jnp.sum(jnp.where(mine[None], jnp.stack([
        q_begins, q_lens, row_starts, jax.lax.iota(jnp.int32, R),
        t_first - (after - n_tiles)])[:, None, :], 0), axis=2)
    t = t + p[:, 0]  # a pair past the last has no row: q_len 0, no pages
    # the pages the row's tokens inside the tile attend: the causal page
    # span, cut below by the sliding window
    lo = jnp.maximum(qb, t * block_q)
    hi = jnp.minimum(qb + ql, (t + 1) * block_q)
    end = jnp.where(hi > lo, _div(st + hi - qb + (page_size - 1), page_size),
                    0)[:, None]
    first = (jnp.zeros_like(end) if window is None else _div(
        jnp.maximum(st + lo - qb - (window - 1), 0), page_size)[:, None])
    if chunk_pages is not None:
        lo = jax.lax.iota(jnp.int32, n_cols * cpp) * chunk_pages
        first = jnp.maximum(first, lo)
        end = jnp.minimum(end, lo + chunk_pages)
    W = P * cpp

    def by_column(x):  # [P, n_cols * cpp] -> [n_cols, W]
        return jnp.swapaxes(jnp.broadcast_to(
            x, (P, n_cols * cpp)).reshape(P, n_cols, cpp), 0, 1).reshape(
                n_cols, W)

    first, end, tile = by_column(first), by_column(end), by_column(t[:, None])
    row_slot = by_column(
        r[:, None] * cpp
        + jax.lax.rem(jax.lax.iota(jnp.int32, n_cols * cpp), jnp.int32(cpp)))
    some = end > first  # [n_cols, W]
    # the k-th entry of a column's list is its k-th non-empty walk
    nth = jax.lax.cumsum(some.astype(jnp.int32), axis=1)
    k = jax.lax.iota(jnp.int32, W)[:, None]
    pick = some[:, None, :] & (nth[:, None, :] == k + 1)  # [n_cols, W, W]
    w_row, w_first, w_end = jnp.sum(jnp.where(
        pick[None], jnp.stack([row_slot, first, end])[:, :, None, :], 0),
        axis=3)
    tiles = jax.lax.iota(jnp.int32, nb + 1)[:, None]
    tile_walks = jnp.sum(
        some[:, None, :] & (tile[:, None, :] < tiles), axis=2,
        dtype=jnp.int32)  # [n_cols, nb + 1]
    return (tile_walks.reshape(-1), w_row.reshape(-1), w_first.reshape(-1),
            w_end.reshape(-1))


class _PageStream:
    """One column's page stream (comment above): trace-time glue over the
    column's walk list, the ring and the SMEM cursor, shared by the three
    ragged kernels.  ``col`` of ``n_cols``: which list of the walk arrays
    is the column's (0 of 1 where every column shares one); ``heads``: a
    head index for the per-head grid, ``slice(None)`` for the coalesced
    grids; ``cpp``: chunk slots a row has in this column."""

    def __init__(self, state, tile_walks_ref, walk_refs, col, n_cols,
                 table_ref, layer_ref, page_refs, bufs, sem, heads, cpp):
        self.state = state
        self.tile_walks_ref = tile_walks_ref
        self.w_row, self.w_first, self.w_end = walk_refs
        tiles1 = tile_walks_ref.shape[0] // n_cols  # tiles + 1
        self.per_col = self.w_row.shape[0] // n_cols
        self.tiles0 = col * tiles1
        self.walks0 = col * self.per_col
        self.n_walks = tile_walks_ref[self.tiles0 + tiles1 - 1]
        self.table_ref = table_ref
        self.layer_ref = layer_ref
        self.page_refs = page_refs
        self.bufs = bufs
        self.sem = sem
        self.heads = heads
        self.cpp = cpp
        self.slots = bufs[0].shape[0]

    def tile(self, t):
        """The walks ``[lo, hi)`` of tile ``t`` in the column's list."""
        return (self.tile_walks_ref[self.tiles0 + t],
                self.tile_walks_ref[self.tiles0 + t + 1])

    def row_slot(self, w):
        """``(row, chunk slot)`` of walk ``w``."""
        rs = self.w_row[self.walks0 + w]
        if self.cpp == 1:
            return rs, 0
        return _div(rs, self.cpp), jax.lax.rem(rs, self.cpp)

    def dma(self, slot, r, p):
        """The copies of page ``p`` of row ``r`` into ring slot ``slot``."""
        k_pages_ref, v_pages_ref, scale_refs = self.page_refs
        k_buf, v_buf, scale_bufs = self.bufs
        return _page_dma(slot, self.layer_ref[0], self.heads,
                         self.table_ref[r, p], k_pages_ref, v_pages_ref,
                         k_buf, v_buf, self.sem, scale_refs, scale_bufs)

    def fetch(self):
        """Start the copies of the cursor's page, if the column has one
        left, and move the cursor one page on."""
        st = self.state
        w = st[_F_WALK]

        @pl.when(w < self.n_walks)
        def _start():
            p, k = st[_F_PAGE], st[_STARTED]
            for cp in self.dma(jax.lax.rem(k, self.slots),
                               self.row_slot(w)[0], p):
                cp.start()
            st[_STARTED] = k + 1
            last = p + 1 >= self.w_end[self.walks0 + w]
            nxt = jnp.minimum(w + 1, self.per_col - 1)
            st[_F_WALK] = jax.lax.select(last, w + 1, w)
            st[_F_PAGE] = jax.lax.select(
                last, self.w_first[self.walks0 + nxt], p + 1)

    def prime(self):
        """The column's first program: ``slots - 1`` pages in flight;
        every later page is fetched by the scorer, one per page taken."""
        st = self.state
        st[_STARTED] = 0
        st[_TAKEN] = 0
        st[_F_WALK] = 0
        st[_F_PAGE] = self.w_first[self.walks0]
        jax.lax.fori_loop(0, self.slots - 1,
                          lambda _, c: (self.fetch(), c)[1], 0)

    def take(self, r, p):
        """The scorer's side, once per page of a walk in order: keep the
        stream ``slots - 1`` ahead, then name the slot that holds page
        ``p`` of row ``r`` and its copies to wait on."""
        self.fetch()
        k = self.state[_TAKEN]
        self.state[_TAKEN] = k + 1
        slot = jax.lax.rem(k, self.slots)
        return slot, self.dma(slot, r, p)


def _native_scores(q_dtype, k_dtype) -> bool:
    """True when the score dot takes its operands as stored: the query
    and the K pages share a 16-bit float dtype.  Products of two such
    values are exact in float32 and the MXU accumulates in float32, so
    it is the float32 path's mathematics with one rounding fewer (``q *
    sm_scale`` is never rounded) at a fraction of its MXU passes.
    float32 pages keep the float32 path bit for bit; int8 pages keep
    theirs (their dtype differs from the query's)."""
    return (jnp.dtype(q_dtype) == jnp.dtype(k_dtype)
            and jnp.issubdtype(q_dtype, jnp.floating)
            and jnp.dtype(q_dtype).itemsize == 2)


def _ragged_q(q_ref, k_dtype, sm_scale, shape):
    """The q tile as the score dot takes it, ``shape``d for the grid:
    ``(q, None)`` pre-scaled in float32, or ``(q, sm_scale)`` as stored
    where :func:`_native_scores` holds (the scale then multiplies the
    float32 scores)."""
    if _native_scores(q_ref.dtype, k_dtype):
        return shape(q_ref[...]), sm_scale
    return shape(q_ref[...].astype(jnp.float32) * sm_scale), None


def _ragged_walk(stream, w, t0, q, o_ref, *, block_q, page_size, quantized,
                 window, row_refs, sm_scale=None, partial=None):
    """Score walk ``w`` of the column's list against the current q tile
    and merge the row's live token rows into ``o_ref`` — the shared body
    of the three ragged grids (per-head: ``q`` is [R, Hd] and the dots
    are one head's; coalesced: ``q`` is [KV, R, Hd] and they batch over
    KV).

    Per-token bit-identity across tile compositions is load-bearing
    (split and fused engine dispatches pack the same row at different
    flat offsets): each token row's accumulators are fresh per
    (tile, row, chunk), fully-masked pages contribute exactly 0 (``exp``
    underflows to +0.0 and the first real page's ``alpha`` is exactly
    0.0), and every dot/reduction is row-wise — so a token's output
    bits depend only on its row's content, never on tile neighbors, nor
    on which ring slot the stream brought a page in.  A walk with no
    page is not in the list: scoring none would merge exactly what the
    program initialised its tokens to.

    ``sm_scale``: set when ``q`` is unscaled in the pages' own dtype
    (:func:`_ragged_q`).  ``partial=(m_ref, l_ref, acc_ref)`` redirects
    the epilogue to emit the walk's raw ``(m, l, unnormalized acc)`` at
    the walk's chunk slot instead of the normalized output — the
    KV-split grid's flash-decode partials (coalesced layout only)."""
    row_starts_ref, q_begins_ref, q_lens_ref = row_refs
    k_buf, v_buf, scale_bufs = stream.bufs
    ks_buf, vs_buf = scale_bufs if quantized else (None, None)
    per_head = q.ndim == 2
    r, c = stream.row_slot(w)
    first = stream.w_first[stream.walks0 + w]
    end = stream.w_end[stream.walks0 + w]
    qb = q_begins_ref[r]
    ql = q_lens_ref[r]
    st = row_starts_ref[r]
    G, Hd = (o_ref if partial is None else partial[2]).shape[-2:]
    R = block_q * G
    # flat token id of each of the R q rows (G head rows per token)
    tok = t0 + _div(
        jax.lax.broadcasted_iota(jnp.int32, (R, page_size), 0), G)
    live = (tok >= qb) & (tok < qb + ql)  # [R, ps]
    pos = st + tok - qb

    def body(p, carry):
        m, l, acc = carry
        slot, copies = stream.take(r, p)
        # split waits (VERDICT #8): K (+ its scale row) lands first and
        # the score matmul + online-softmax update run while V's copy is
        # still in flight
        copies[0].wait()
        if quantized:
            copies[2].wait()
        # coalesced: ONE batched dot over all KV heads ([KV, R, Hd] x
        # [KV, ps, Hd] -> [KV, R, ps]) instead of KV tiny per-head dots
        # (VERDICT #8); per-head: [R, Hd] x [ps, Hd] -> [R, ps]
        s = _scores(q, k_buf[slot], ks_buf[slot] if quantized else None,
                    sm_scale)
        ctx = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (R, page_size), 1)
        keep = live & attend(pos, ctx, window)
        s = jnp.where(keep if per_head else keep[None], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        pexp = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(pexp, axis=-1, keepdims=True)
        copies[1].wait()
        if quantized:
            copies[3].wait()
        pv = _weighted_values(pexp, v_buf[slot],
                              vs_buf[slot] if quantized else None)
        return m_new, l_new, acc * alpha + pv

    lead = q.shape[:-2]
    m0 = jnp.full((*lead, R, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((*lead, R, 1), jnp.float32)
    a0 = jnp.zeros((*lead, R, Hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(first, end, body, (m0, l0, a0))
    lt = live[:, 0].reshape(block_q, G)[:, :1]  # [bq, 1] token liveness
    if partial is not None:
        # KV-split partials: the walk's raw (m, l, unnormalized acc) at
        # chunk slot `c` — normalization happens after the cross-chunk
        # log-sum-exp combine in the wrapper (coalesced layout only)
        m_ref, l_ref, acc_ref = partial
        KV = q.shape[0]
        accw = jnp.moveaxis(acc.reshape(KV, block_q, G, Hd), 0, 1)
        mw = jnp.moveaxis(m.reshape(KV, block_q, G), 0, 1)  # [bq, KV, G]
        lw = jnp.moveaxis(l.reshape(KV, block_q, G), 0, 1)
        acc_ref[c] = jnp.where(lt[:, None, :, None], accw, acc_ref[c])
        m_ref[c] = jnp.where(lt[:, :, None], mw, m_ref[c])
        l_ref[c] = jnp.where(lt[:, :, None], lw, l_ref[c])
        return
    out = (acc / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)
    if per_head:
        out = out.reshape(block_q, G, Hd)
        o_ref[:, 0] = jnp.where(lt[:, :, None], out, o_ref[:, 0])
    else:
        KV = q.shape[0]
        out = jnp.moveaxis(out.reshape(KV, block_q, G, Hd), 0, 1)
        o_ref[...] = jnp.where(lt[:, None, :, None], out, o_ref[...])


def _ragged_tile(stream, t, t0, q, o_ref, **walk_kw):
    """One program of a column: prime the stream at the column's first
    tile, then score the tile's walks in list order."""
    pl.when(t == 0)(stream.prime)
    lo, hi = stream.tile(t)

    def walk_body(w, carry):
        _ragged_walk(stream, w, t0, q, o_ref, **walk_kw)
        return carry

    jax.lax.fori_loop(lo, hi, walk_body, 0)


def _ragged_kernel_coalesced(
    # scalar prefetch
    page_tables_ref,  # [R, mp] int32 (SMEM) — per-ROW page tables
    row_starts_ref,  # [R] int32 — global position of each row's token 0
    q_begins_ref,  # [R] int32 — flat offset of each row's segment
    q_lens_ref,  # [R] int32 — row token count (0 = inert row)
    tile_walks_ref,  # [nb + 1] int32 — _ragged_walks: one column
    w_row_ref,  # [W] int32
    w_first_ref,  # [W] int32
    w_end_ref,  # [W] int32
    layer_ref,  # [1] int32
    # inputs: q_ref [block_q, KV, G, Hd] VMEM tile of the flat axis
    q_ref,
    k_pages_ref,
    v_pages_ref,
    *rest,
    block_q: int,
    page_size: int,
    sm_scale: float,
    quantized: bool,
    window: int | None,
):
    """Ragged grid ``(nb,)``: one program per flat q tile covers every
    KV head (one ``[KV, ps, Hd]`` DMA per page, batched score/value
    dots), scoring the tile's walks; one page stream runs through all
    the tiles."""
    *rest, state = rest
    scale_refs, o_ref, k_buf, v_buf, scale_bufs, sem = _split_rest(
        rest, quantized)
    t = pl.program_id(0)
    KV, G, Hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    q, score_scale = _ragged_q(
        q_ref, k_pages_ref.dtype, sm_scale,
        lambda x: jnp.moveaxis(x, 1, 0).reshape(KV, block_q * G, Hd))
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    stream = _PageStream(
        state, tile_walks_ref, (w_row_ref, w_first_ref, w_end_ref), 0, 1,
        page_tables_ref, layer_ref,
        (k_pages_ref, v_pages_ref, scale_refs), (k_buf, v_buf, scale_bufs),
        sem, slice(None), 1)
    _ragged_tile(stream, t, t * block_q, q, o_ref, block_q=block_q,
                 page_size=page_size, quantized=quantized, window=window,
                 row_refs=(row_starts_ref, q_begins_ref, q_lens_ref),
                 sm_scale=score_scale)


def _ragged_kernel(
    # scalar prefetch (same layout as the coalesced grid)
    page_tables_ref,
    row_starts_ref,
    q_begins_ref,
    q_lens_ref,
    tile_walks_ref,
    w_row_ref,
    w_first_ref,
    w_end_ref,
    layer_ref,
    # inputs: q_ref [block_q, 1, G, Hd] VMEM tile
    q_ref,
    k_pages_ref,
    v_pages_ref,
    *rest,
    block_q: int,
    page_size: int,
    sm_scale: float,
    quantized: bool,
    window: int | None,
):
    """Ragged grid ``(KV, nb)``: the VMEM-guard escape hatch — per-head
    ``[ps, Hd]`` page copies and per-head dots, KV× smaller scratch; a
    head's tiles run in order and share one page stream over the one
    walk list every head has."""
    *rest, state = rest
    scale_refs, o_ref, k_buf, v_buf, scale_bufs, sem = _split_rest(
        rest, quantized)
    g = pl.program_id(0)
    t = pl.program_id(1)
    G, Hd = q_ref.shape[2], q_ref.shape[3]
    q, score_scale = _ragged_q(
        q_ref, k_pages_ref.dtype, sm_scale,
        lambda x: x[:, 0].reshape(block_q * G, Hd))
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)
    stream = _PageStream(
        state, tile_walks_ref, (w_row_ref, w_first_ref, w_end_ref), 0, 1,
        page_tables_ref, layer_ref,
        (k_pages_ref, v_pages_ref, scale_refs), (k_buf, v_buf, scale_bufs),
        sem, g, 1)
    _ragged_tile(stream, t, t * block_q, q, o_ref, block_q=block_q,
                 page_size=page_size, quantized=quantized, window=window,
                 row_refs=(row_starts_ref, q_begins_ref, q_lens_ref),
                 sm_scale=score_scale)


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "interpret", "window", "block_q",
                              "coalesce", "name")
)
def ragged_paged_attention(
    q: jax.Array,  # [T, H, Hd] — flat ragged-concat query tokens
    k_pages: jax.Array,  # [KV, n_pages, ps, Hd] or stacked [L, KV, …]
    v_pages: jax.Array,
    page_tables: jax.Array,  # [R, max_pages] int32 — per-ROW tables
    row_starts: jax.Array,  # [R] int32 — global position of row's token 0
    q_begins: jax.Array,  # [R] int32 — flat offset of each row's segment
    q_lens: jax.Array,  # [R] int32 — row token count (0 = inert row)
    k_scales: jax.Array | None = None,  # [(L,) KV, n_pages, 1, ps] (int8)
    v_scales: jax.Array | None = None,
    *,
    sm_scale: float | None = None,
    interpret: bool = False,
    window: int | None = None,
    block_q: int = RAGGED_BLOCK_Q,
    coalesce: bool | None = None,
    layer: jax.Array | int | None = None,
    walks=None,
    name: str | None = None,
) -> jax.Array:
    """The one true ragged paged-attention kernel → [T, H·Hd].

    Token ``t`` belongs to the row ``r`` whose flat segment
    ``[q_begins[r], q_begins[r] + q_lens[r])`` contains it, sits at
    global position ``row_starts[r] + (t - q_begins[r])``, and attends
    causally over row ``r``'s pages.  Decode rows (q_len=1), spec-verify
    windows (q_len=1+drafts), budgeted prefill chunks (q_len=chunk) and
    cache-hit suffixes all ride this one grid — no per-row rectangle
    padding, no kernel switch between row kinds.  Rows must be packed
    in flat order (``q_begins`` non-decreasing, segments disjoint);
    tokens covered by no row (inter-segment padding, the tile-multiple
    tail) produce unspecified output the caller discards.

    ``coalesce``: one ``[KV, ps, Hd]`` DMA per page with batched
    score/value dots over KV (default; ``None`` defers to
    :func:`fusioninfer_tpu.ops.dispatch.decode_coalesce` — resolved at
    TRACE time and latched per jit signature, so pass the resolved
    bool explicitly when a mid-process env flip must retrace, as the
    engine does at every dispatch) vs the per-(tile, head) grid — the
    VMEM guard (:func:`ragged_fits_vmem`) demotes oversized
    configurations automatically.  Per-token output
    bits are independent of tile composition and flat offset (see
    ``_ragged_walk``), so split and fused engine dispatches scoring the
    same row are bit-identical.  ``walks``: the rows' walk lists where
    the caller built them once for many calls (:func:`ragged_walk_lists`).
    ``name``: the call's name in a device trace where the caller tells
    its calls apart (a layer kind's; None = the default name).
    """
    T, H, Hd = q.shape
    k_pages, v_pages, k_scales, v_scales, layer_arr = _as_stacked(
        k_pages, v_pages, k_scales, v_scales, layer)
    KV, _, page_size, _ = k_pages.shape[1:]
    G = H // KV
    sm_scale = sm_scale if sm_scale is not None else Hd ** -0.5
    quantized = k_scales is not None
    if coalesce is None:
        from fusioninfer_tpu.ops import dispatch

        coalesce = dispatch.decode_coalesce()
    coalesce = resolve_ragged_grid(
        page_size, Hd, KV, G, q.dtype, k_pages.dtype, v_pages.dtype,
        quantized, coalesce=coalesce, block_q=block_q)[0] == "coalesced"
    # pad the flat axis to a tile multiple; padding tokens belong to no
    # row (their output is sliced off below)
    Tp = -(-T // block_q) * block_q
    if Tp != T:
        q = jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0)))
    nb = Tp // block_q
    qg = q.reshape(Tp, KV, G, Hd)
    if walks is None:
        walks = ragged_walk_lists(
            q, k_pages, v_pages, page_tables, row_starts, q_begins, q_lens,
            k_scales, window=window, block_q=block_q)
    walks = _check_walks(walks, 1, nb)

    if coalesce:
        page_specs, scratch = _page_specs_scratch(
            page_size, Hd, k_pages.dtype, v_pages.dtype, quantized,
            heads=KV, slots=RAGGED_RING_SLOTS)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=9,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec(
                    (block_q, KV, G, Hd), lambda t, *_: (t, 0, 0, 0),
                    memory_space=pltpu.VMEM,
                ),
                *page_specs,
            ],
            out_specs=pl.BlockSpec(
                (block_q, KV, G, Hd), lambda t, *_: (t, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            scratch_shapes=[*scratch, _STREAM_STATE],
        )
        body = _ragged_kernel_coalesced
        semantics = ("arbitrary",)
    else:
        # heads lead, tiles run innermost: a head's tiles are sequential
        # grid steps, which is what carries its page stream across them
        page_specs, scratch = _page_specs_scratch(
            page_size, Hd, k_pages.dtype, v_pages.dtype, quantized,
            slots=RAGGED_RING_SLOTS)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=9,
            grid=(KV, nb),
            in_specs=[
                pl.BlockSpec(
                    (block_q, 1, G, Hd), lambda g, t, *_: (t, g, 0, 0),
                    memory_space=pltpu.VMEM,
                ),
                *page_specs,
            ],
            out_specs=pl.BlockSpec(
                (block_q, 1, G, Hd), lambda g, t, *_: (t, g, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            scratch_shapes=[*scratch, _STREAM_STATE],
        )
        body = _ragged_kernel
        semantics = ("parallel", "arbitrary")
    kernel = functools.partial(
        body,
        block_q=block_q, page_size=page_size, sm_scale=sm_scale,
        quantized=quantized, window=window,
    )
    operands = [page_tables.astype(jnp.int32), row_starts.astype(jnp.int32),
                q_begins.astype(jnp.int32), q_lens.astype(jnp.int32),
                *walks, layer_arr, qg, k_pages, v_pages]
    if quantized:
        operands += [k_scales, v_scales]
    # the tile axis carries the page stream from step to step: it runs
    # in order on one core
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, KV, G, Hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        **({"name": name} if name else {}),
    )(*operands)
    return out.reshape(Tp, H * Hd)[:T]


# -- flash-decode KV-split grid ---------------------------------------
#
# A 32k-context decode row through the single-walk grid above is one
# sequential chain of ~256 page tiles on ONE grid program while the
# rest of the chip idles — the "one-page-walk wall" (ROADMAP item 3).
# ``ragged_paged_attention_kvsplit`` parallelizes over the KV axis: a
# second grid dimension of ``kv_splits`` programs each walks a slice of
# the page range and emits flash-decode partials ``(m, l, unnormalized
# acc)``; a cross-split log-sum-exp combine reduces them to the
# attention output.
#
# Bit-identity across split counts is BY CONSTRUCTION, not luck: float
# online-softmax is not associative, so partials are always emitted at
# a FIXED virtual-chunk granularity (``KV_SPLIT_CHUNKS`` page windows,
# boundaries a static function of the table width alone) and the
# combine always folds the chunk partials left-to-right.  ``kv_splits``
# only chooses how many grid programs share the chunks — every chunk
# partial is a fresh walk over the same pages with the same ops
# whichever program computes it, so splits 1, 2, 4 and 8 produce the
# same bits (pinned by the split-axis extension of
# ``test_offset_and_neighbor_invariance_bit_identity``).  Empty chunks
# keep the exact +0.0 masked-page algebra: their (m=-inf, l=0, acc=0)
# partial merges as an exact identity (alpha = exp(0) = 1.0, beta =
# exp(-inf) = +0.0), so a short row — whose pages all land in chunk 0 —
# costs one walk plus exact no-op merges, and a token's output bits
# never depend on its tile neighbors or flat offset.

# fixed virtual-chunk count: the page range always partitions into this
# many accumulation windows whatever ``kv_splits`` is (the bit-identity
# construction above).  8 matches the deepest useful split on a v5e
# core's compute units without inflating short-row combine overhead.
KV_SPLIT_CHUNKS = 8

# the dispatch heuristic's context floor: engines whose max context
# (max_pages_per_seq × page_size) is below this keep the single-walk
# grid — its compile-signature families and decode latency untouched.
# The threshold is STATIC engine config, never runtime batch content:
# a per-batch choice would make a short row's bits depend on whether a
# long neighbor shares its dispatch, re-breaking the neighbor
# invariance PR 6 established.
KV_SPLIT_MIN_CTX_TOKENS = 4096


def pick_kv_splits(max_pages_per_seq: int, page_size: int,
                   window_reach: int | None = None) -> int:
    """The ragged_fits_vmem-style dispatch heuristic: 0 (single-walk
    grid, existing signature families) below the long-context floor,
    else the full ``KV_SPLIT_CHUNKS`` split fan-out.  A pure function
    of static cache config so every process of a multi-host lockstep
    group — and every dispatch of one engine — resolves identically.

    ``window_reach``: for a WINDOWED layer kind, the most positions a
    row's walk ever covers (the window plus the longest row span a step
    writes), judged by ``min(context bound, reach)``: where the window
    binds, the kind keeps the single walk.  The split's chunks partition
    the page TABLE, so a window's walk falls into the two or three of
    the eight that hold its pages whatever the context, and the others'
    programs only cost: at a 4096 window under a 16 k table the single
    walk is faster at every probed shape (32 decode rows at 8 k / 12 k:
    629 / 632 us against 727 / 730; a 1024-token chunk at 8 k: 2585
    against 3886; PERF.md section 6, PR 36)."""
    ctx = max_pages_per_seq * page_size
    if window_reach is not None and window_reach < ctx:
        return 0
    if ctx < KV_SPLIT_MIN_CTX_TOKENS:
        return 0
    return KV_SPLIT_CHUNKS


def kvsplit_fits_vmem(block_q: int, page_size: int, Hd: int, kv_heads: int,
                      group: int, q_dtype, k_dtype, v_dtype,
                      quantized: bool, kv_splits: int,
                      budget: int | None = None) -> bool:
    """True when one KV-split program's VMEM footprint — the coalesced
    page ring, the q tile, and its ``chunks_per_program`` f32 partial
    blocks (acc + m + l) — fits the conservative budget; the wrapper
    demotes to the single-walk grid otherwise."""
    if budget is None:
        budget = _COALESCE_VMEM_SCRATCH_BUDGET
    pages = coalesced_scratch_bytes(page_size, Hd, kv_heads,
                                    k_dtype, v_dtype, quantized,
                                    slots=RAGGED_RING_SLOTS)
    q_tile = block_q * kv_heads * group * Hd * jnp.dtype(q_dtype).itemsize
    cpp = KV_SPLIT_CHUNKS // max(1, kv_splits)
    partials = cpp * block_q * kv_heads * group * (Hd + 2) * 4
    return pages + q_tile + partials <= budget


def resolve_ragged_grid(page_size: int, Hd: int, kv_heads: int, group: int,
                        q_dtype, k_dtype, v_dtype, quantized: bool, *,
                        coalesce: bool, kv_splits: int = 0,
                        block_q: int = RAGGED_BLOCK_Q) -> tuple[str, int]:
    """The grid a ragged dispatch takes after the VMEM guards:
    ``(layout, splits)`` with layout ``"coalesced"`` or ``"per-head"``
    and ``splits`` the KV-split program count (0 = single walk).  The
    ONE place the demotions are decided — the kernel wrappers dispatch
    on it and the engine reports it, so what a server says it runs is
    what it traced."""
    shape = (block_q, page_size, Hd, kv_heads, group, q_dtype, k_dtype,
             v_dtype, quantized)
    if kv_splits > 0:
        # the KV-split grid is coalesced-only; configurations its
        # scratch + partials would blow demote to the single-walk grid
        # (whose own guard may further demote to per-head)
        S = max(1, min(int(kv_splits), KV_SPLIT_CHUNKS))
        while KV_SPLIT_CHUNKS % S:
            S -= 1
        if kvsplit_fits_vmem(*shape, S):
            return "coalesced", S
        coalesce = True
    if coalesce and ragged_fits_vmem(*shape):
        return "coalesced", 0
    return "per-head", 0


def ragged_walk_lists(q, k_pages, v_pages, page_tables, row_starts,
                      q_begins, q_lens, k_scales=None, *, window=None,
                      kv_splits: int = 0, block_q: int = RAGGED_BLOCK_Q):
    """The walk lists (:func:`_ragged_walks`) a ragged dispatch of these
    rows prefetches, for the grid the same operands resolve to
    (``kv_splits`` as requested: 0 for :func:`ragged_paged_attention`).
    Only shapes and dtypes of ``q`` [T, H, Hd] and of the pages are read.
    A caller that scores the same rows many times — every layer of a
    scan — builds them ONCE and passes ``walks=``: what a scan body
    computes stays inside XLA's loop, layer after layer."""
    T, H, Hd = q.shape
    KV, page_size = k_pages.shape[-4], k_pages.shape[-2]
    S = 0
    if kv_splits > 0:
        S = resolve_ragged_grid(
            page_size, Hd, KV, H // KV, q.dtype, k_pages.dtype,
            v_pages.dtype, k_scales is not None, coalesce=True,
            kv_splits=kv_splits, block_q=block_q)[1]
    i32 = jnp.int32
    return _ragged_walks(
        q_begins.astype(i32), q_lens.astype(i32), row_starts.astype(i32),
        nb=-(-T // block_q), block_q=block_q, page_size=page_size,
        window=window, n_cols=max(S, 1),
        cpp=KV_SPLIT_CHUNKS // S if S else 1,
        chunk_pages=-(-page_tables.shape[1] // KV_SPLIT_CHUNKS) if S else None)


def _check_walks(walks, n_cols: int, nb: int):
    if walks[0].shape != (n_cols * (nb + 1),):
        raise ValueError(
            f"walks= were built for another grid: {walks[0].shape[0]} tile "
            f"offsets, this dispatch has {n_cols} x ({nb} + 1)")
    return tuple(walks)


def _ragged_kernel_kvsplit(
    # scalar prefetch (the single-walk ragged layout, a walk list a split)
    page_tables_ref,  # [R, mp] int32 (SMEM)
    row_starts_ref,  # [R] int32
    q_begins_ref,  # [R] int32
    q_lens_ref,  # [R] int32
    tile_walks_ref,  # [S * (nb + 1)] int32 — _ragged_walks
    w_row_ref,  # [S * W] int32
    w_first_ref,  # [S * W] int32
    w_end_ref,  # [S * W] int32
    layer_ref,  # [1] int32
    # inputs: q_ref [block_q, KV, G, Hd] VMEM tile of the flat axis
    q_ref,
    k_pages_ref,
    v_pages_ref,
    *rest,
    block_q: int,
    page_size: int,
    sm_scale: float,
    quantized: bool,
    window: int | None,
    n_splits: int,
    chunks_per_prog: int,
):
    """KV-split grid ``(S, nb)``: program ``(s, t)`` scores split ``s``'s
    walks of tile ``t`` — each a row's pages inside one of the split's
    ``chunks_per_prog`` virtual page-chunks — and emits per-chunk ``(m,
    l, acc)`` partials: the same coalesced page streaming and per-page
    math as the single walk, restricted to each chunk's page window with
    fresh accumulators.  A split's tiles share one page stream."""
    if quantized:
        (ks_ref, vs_ref, acc_ref, m_ref, l_ref,
         k_buf, v_buf, ks_buf, vs_buf, sem, state) = rest
        scale_refs, scale_bufs = (ks_ref, vs_ref), (ks_buf, vs_buf)
    else:
        acc_ref, m_ref, l_ref, k_buf, v_buf, sem, state = rest
        scale_refs = scale_bufs = None
    s = pl.program_id(0)
    t = pl.program_id(1)
    KV, G, Hd = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    q, score_scale = _ragged_q(
        q_ref, k_pages_ref.dtype, sm_scale,
        lambda x: jnp.moveaxis(x, 1, 0).reshape(KV, block_q * G, Hd))
    acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, m_ref.dtype)
    l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)
    stream = _PageStream(
        state, tile_walks_ref, (w_row_ref, w_first_ref, w_end_ref), s,
        n_splits, page_tables_ref, layer_ref,
        (k_pages_ref, v_pages_ref, scale_refs), (k_buf, v_buf, scale_bufs),
        sem, slice(None), chunks_per_prog)
    _ragged_tile(stream, t, t * block_q, q, None, block_q=block_q,
                 page_size=page_size, quantized=quantized, window=window,
                 row_refs=(row_starts_ref, q_begins_ref, q_lens_ref),
                 sm_scale=score_scale, partial=(m_ref, l_ref, acc_ref))


@functools.partial(
    jax.jit, static_argnames=("sm_scale", "interpret", "window", "block_q",
                              "kv_splits", "name")
)
def ragged_paged_attention_kvsplit(
    q: jax.Array,  # [T, H, Hd] — flat ragged-concat query tokens
    k_pages: jax.Array,  # [KV, n_pages, ps, Hd] or stacked [L, KV, …]
    v_pages: jax.Array,
    page_tables: jax.Array,  # [R, max_pages] int32 — per-ROW tables
    row_starts: jax.Array,  # [R] int32
    q_begins: jax.Array,  # [R] int32
    q_lens: jax.Array,  # [R] int32 (0 = inert row)
    k_scales: jax.Array | None = None,  # [(L,) KV, n_pages, 1, ps] (int8)
    v_scales: jax.Array | None = None,
    *,
    kv_splits: int = KV_SPLIT_CHUNKS,
    sm_scale: float | None = None,
    interpret: bool = False,
    window: int | None = None,
    block_q: int = RAGGED_BLOCK_Q,
    layer: jax.Array | int | None = None,
    walks=None,
    name: str | None = None,
) -> jax.Array:
    """Flash-decode ragged paged attention → [T, H·Hd]: the one true
    ragged kernel's descriptor contract with the serial page walk
    replaced by ``kv_splits`` parallel walks over fixed virtual page
    chunks plus a cross-chunk log-sum-exp combine (module comment above
    for the bit-identity construction).  ``kv_splits`` must divide
    ``KV_SPLIT_CHUNKS``; oversized VMEM configurations (and per-head
    fallback shapes) demote to the single-walk grid — a static,
    config-level decision so every dispatch of one engine takes the
    same path."""
    T, H, Hd = q.shape
    k_pages, v_pages, k_scales, v_scales, layer_arr = _as_stacked(
        k_pages, v_pages, k_scales, v_scales, layer)
    KV, _, page_size, _ = k_pages.shape[1:]
    G = H // KV
    sm_scale = sm_scale if sm_scale is not None else Hd ** -0.5
    quantized = k_scales is not None
    _, S = resolve_ragged_grid(
        page_size, Hd, KV, G, q.dtype, k_pages.dtype, v_pages.dtype,
        quantized, coalesce=True, kv_splits=max(1, int(kv_splits)),
        block_q=block_q)
    if not S:
        return ragged_paged_attention(
            q, k_pages, v_pages, page_tables, row_starts, q_begins,
            q_lens, k_scales, v_scales, sm_scale=sm_scale,
            interpret=interpret, window=window, block_q=block_q,
            coalesce=True, layer=layer_arr, walks=walks, name=name)
    chunks = KV_SPLIT_CHUNKS
    cpp = chunks // S

    Tp = -(-T // block_q) * block_q
    if Tp != T:
        q = jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0)))
    nb = Tp // block_q
    qg = q.reshape(Tp, KV, G, Hd)
    if walks is None:
        walks = ragged_walk_lists(
            q, k_pages, v_pages, page_tables, row_starts, q_begins, q_lens,
            k_scales, window=window, kv_splits=S, block_q=block_q)
    walks = _check_walks(walks, S, nb)

    page_specs, scratch = _page_specs_scratch(
        page_size, Hd, k_pages.dtype, v_pages.dtype, quantized, heads=KV,
        slots=RAGGED_RING_SLOTS)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=9,
        grid=(S, nb),
        in_specs=[
            pl.BlockSpec(
                (block_q, KV, G, Hd), lambda s, t, *_: (t, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            *page_specs,
        ],
        out_specs=(
            pl.BlockSpec(
                (cpp, block_q, KV, G, Hd),
                lambda s, t, *_: (s, t, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (cpp, block_q, KV, G), lambda s, t, *_: (s, t, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (cpp, block_q, KV, G), lambda s, t, *_: (s, t, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ),
        scratch_shapes=[*scratch, _STREAM_STATE],
    )
    kernel = functools.partial(
        _ragged_kernel_kvsplit,
        block_q=block_q, page_size=page_size, sm_scale=sm_scale,
        quantized=quantized, window=window,
        n_splits=S, chunks_per_prog=cpp,
    )
    operands = [page_tables.astype(jnp.int32), row_starts.astype(jnp.int32),
                q_begins.astype(jnp.int32), q_lens.astype(jnp.int32),
                *walks, layer_arr, qg, k_pages, v_pages]
    if quantized:
        operands += [k_scales, v_scales]
    # the split axis carries no cross-program dependency (each split owns
    # distinct chunk blocks and its own page stream): declare it parallel
    # so Mosaic may partition it across cores where the part exposes more
    # than one (megacore generations); the tile axis carries a split's
    # stream from step to step and runs in order
    acc_p, m_p, l_p = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((chunks, Tp, KV, G, Hd), jnp.float32),
            jax.ShapeDtypeStruct((chunks, Tp, KV, G), jnp.float32),
            jax.ShapeDtypeStruct((chunks, Tp, KV, G), jnp.float32),
        ),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        **({"name": name + "_kvsplit"} if name else {}),
    )(*operands)
    # the cross-chunk combine: a strict left-to-right fold at the fixed
    # chunk granularity (bit-identical whatever kv_splits computed the
    # partials).  Empty chunks merge as exact identities — alpha =
    # exp(0.0) = 1.0 and beta = exp(-inf) = +0.0 — preserving the
    # masked-page algebra; the double--inf lane (no live pages at all)
    # is the only case needing the `dead` guard (-inf minus -inf is
    # NaN), and it reduces to the single-walk epilogue's 0 / 1e-20.
    m, l, acc = m_p[0], l_p[0], acc_p[0]
    for c in range(1, chunks):
        m_new = jnp.maximum(m, m_p[c])
        dead = m_new == -jnp.inf
        alpha = jnp.where(dead, 0.0, jnp.exp(m - m_new))
        beta = jnp.where(dead, 0.0, jnp.exp(m_p[c] - m_new))
        l = alpha * l + beta * l_p[c]
        acc = alpha[..., None] * acc + beta[..., None] * acc_p[c]
        m = m_new
    out = (acc / jnp.maximum(l, 1e-20)[..., None]).astype(q.dtype)
    return out.reshape(Tp, H * Hd)[:T]


def ragged_token_rows(q_begins, q_lens, n_tokens: int):
    """Per-token (row, offset, live) maps for a flat ragged layout — the
    one definition of token→row resolution, shared by the kernel
    wrapper's oracle, the portable gather branch and tests.  Robust to
    zero-length rows sharing a begin with a neighbor."""
    ends = q_begins + q_lens
    t_idx = jnp.arange(n_tokens)
    row_of = jnp.clip(jnp.searchsorted(ends, t_idx, side="right"),
                      0, q_begins.shape[0] - 1)
    off = t_idx - q_begins[row_of]
    live = (t_idx >= q_begins[row_of]) & (t_idx < ends[row_of])
    return row_of, off, live


def reference_ragged_paged_attention(q, k_pages, v_pages, page_tables,
                                     row_starts, q_begins, q_lens,
                                     window=None):
    """Flat gathered-context jnp oracle for the ragged kernel.  Tokens
    covered by no row are zeroed for deterministic comparison."""
    T, H, Hd = q.shape
    KV, _, ps, _ = k_pages.shape
    G = H // KV
    mp = page_tables.shape[1]
    row_of, off, live = ragged_token_rows(q_begins, q_lens, T)
    pos = row_starts[row_of] + off
    tables = page_tables[row_of]  # [T, mp]
    k_ctx = k_pages[:, tables].reshape(KV, T, mp * ps, Hd)
    v_ctx = v_pages[:, tables].reshape(KV, T, mp * ps, Hd)
    qg = q.reshape(T, KV, G, Hd)
    s = jnp.einsum("tkgd,ktsd->ktgs", qg.astype(jnp.float32),
                   k_ctx.astype(jnp.float32)) / jnp.sqrt(Hd)
    ctx = jnp.arange(mp * ps)
    mask = attend(pos[:, None], ctx[None, :], window) & live[:, None]
    s = jnp.where(mask[None, :, None, :], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1) * live[None, :, None, None]
    out = jnp.einsum("ktgs,ktsd->tkgd", probs, v_ctx.astype(jnp.float32))
    return out.reshape(T, H * Hd).astype(q.dtype)


def reference_window_rows_attention(q, k_pages, v_pages, page_tables,
                                     starts, counts, window=None):
    """Gathered-context jnp oracle for window rows as a ``[B, C]``
    rectangle (row ``b``: ``counts[b]`` tokens from ``starts[b]``) — the
    tests' second oracle for speculative windows, written without the
    flat token axis.  Padding rows (``i >= counts[b]``) and inactive
    slots are zeroed."""
    B, C, H, Hd = q.shape
    KV, _, ps, _ = k_pages.shape
    G = H // KV
    mp = page_tables.shape[1]
    k_ctx = k_pages[:, page_tables].reshape(KV, B, mp * ps, Hd)
    v_ctx = v_pages[:, page_tables].reshape(KV, B, mp * ps, Hd)
    qg = q.reshape(B, C, KV, G, Hd)
    s = jnp.einsum("bckgd,kbtd->bkgct", qg.astype(jnp.float32),
                   k_ctx.astype(jnp.float32)) / jnp.sqrt(Hd)
    pos_q = starts[:, None] + jnp.arange(C)[None, :]  # [B, C]
    ctx = jnp.arange(mp * ps)
    mask = attend(pos_q[:, :, None], ctx[None, None, :], window)  # [B, C, T]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgct,kbtd->bckgd", probs, v_ctx.astype(jnp.float32))
    live = (jnp.arange(C)[None, :] < counts[:, None])  # [B, C]
    out = out * live[:, :, None, None, None]
    return out.reshape(B, C, H * Hd).astype(q.dtype)


def reference_chunk_row_attention(q, k_pages, v_pages, page_row, start,
                                      true_len, window=None):
    """Gathered-context jnp oracle for ONE chunk row (a cache-hit
    suffix or a chunk from the middle of a prompt: ``true_len`` tokens
    from position ``start`` over ``page_row``) — the tests' second
    oracle for chunk rows.  Padding rows are zeroed for deterministic
    comparison."""
    C, H, Hd = q.shape
    KV, _, ps, _ = k_pages.shape
    G = H // KV
    mp = page_row.shape[0]
    k_ctx = k_pages[:, page_row].reshape(KV, mp * ps, Hd)
    v_ctx = v_pages[:, page_row].reshape(KV, mp * ps, Hd)
    qg = q.reshape(C, KV, G, Hd)
    s = jnp.einsum("ckgd,ktd->kgct", qg.astype(jnp.float32),
                   k_ctx.astype(jnp.float32)) / jnp.sqrt(Hd)
    pos_q = start + jnp.arange(C)
    ctx = jnp.arange(mp * ps)
    mask = attend(pos_q[:, None], ctx[None, :], window)
    s = jnp.where(mask[None, None], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("kgct,ktd->ckgd", probs, v_ctx.astype(jnp.float32))
    out = out * (jnp.arange(C) < true_len)[:, None, None, None]
    return out.reshape(C, H * Hd).astype(q.dtype)


def reference_decode_rows_attention(q, k_pages, v_pages, page_tables, lengths,
                              window=None):
    """Gather-based jnp oracle for decode rows (one token a sequence at
    position ``lengths - 1``; same math as the engine's portable path) —
    the tests' second oracle for decode rows."""
    B, H, Hd = q.shape
    KV, _, ps, _ = k_pages.shape
    G = H // KV
    mp = page_tables.shape[1]
    # head-major pages: gather on axis 1 → [KV, B, mp·ps, Hd]
    k_ctx = k_pages[:, page_tables].reshape(KV, B, mp * ps, Hd)
    v_ctx = v_pages[:, page_tables].reshape(KV, B, mp * ps, Hd)
    qg = q.reshape(B, KV, G, Hd)
    s = jnp.einsum("bkgd,kbtd->bkgt", qg.astype(jnp.float32),
                   k_ctx.astype(jnp.float32)) / jnp.sqrt(Hd)
    pos = jnp.arange(mp * ps)[None, :]
    mask = attend((lengths - 1)[:, None], pos, window) & (lengths > 0)[:, None]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    # inactive slots (length 0) are fully masked: zero their output
    probs = jax.nn.softmax(s, axis=-1) * (lengths > 0)[:, None, None, None]
    out = jnp.einsum("bkgt,kbtd->bkgd", probs, v_ctx.astype(jnp.float32))
    return out.reshape(B, H * Hd).astype(q.dtype)
