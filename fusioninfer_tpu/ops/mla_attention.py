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
``(tiles,)`` over tiles of ``block_q`` flat tokens; a tile loops over
the rows that intersect it and walks each row's pages with a
double-buffered DMA and an online softmax.  A row that has ONE token in
the tile (a decode row) scores only that token's ``H`` head rows; a row
with more (a chunk) scores the whole ``[block_q * H]`` tile under its
live mask.  Dots take bfloat16 operands and accumulate in float32.

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
    NEG_INF,
    _ragged_block_rows,
    ragged_token_rows,
)

MLA_BLOCK_Q = 8
# the q and out tiles ([block_q, H, rank] twice, double-buffered), the
# float32 accumulator of a [block_q * H, rank] tile and the score
# temporaries pass Mosaic's default scoped limit at published widths
MLA_VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _mla_kernel(
    # scalar prefetch
    page_tables_ref,  # [R, mp] int32 (SMEM)
    row_starts_ref,  # [R] int32: global position of each row's token 0
    q_begins_ref,  # [R] int32: flat offset of each row's segment
    q_lens_ref,  # [R] int32: row token count (0 = inert row)
    block_rows_ref,  # [nb, 2] int32: (first_row, n_rows) per tile
    layer_ref,  # [1] int32
    # inputs
    qc_ref,  # [block_q, H, rank] VMEM tile: queries against the latent
    qr_ref,  # [block_q, H, rope] VMEM tile: rope queries
    pages_ref,  # [L, 1, n_pages, ps, W] in HBM
    # output, scratch
    o_ref,  # [block_q, H, rank]
    kv_buf,  # [2, ps, W]
    sem,  # DMA semaphores [2]
    *,
    block_q: int,
    page_size: int,
    rank: int,
    rope: int,
):
    t = pl.program_id(0)
    t0 = t * block_q
    first_row, n_rows = block_rows_ref[t, 0], block_rows_ref[t, 1]
    H = qc_ref.shape[1]
    o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def walk(r, qc, qr, pos, live, n_pages):
        """Online softmax of query rows ``qc``/``qr`` [N, .] (positions
        ``pos`` [N, 1], liveness ``live`` [N, 1] or None) over the first
        ``n_pages`` pages of row ``r`` → normalized [N, rank] float32."""
        N = qc.shape[0]

        def dma(slot, p):
            return pltpu.make_async_copy(
                pages_ref.at[layer_ref[0], 0, page_tables_ref[r, p]],
                kv_buf.at[slot], sem.at[slot])

        @pl.when(n_pages > 0)
        def _start_first():
            dma(0, 0).start()

        def body(p, carry):
            m, l, acc = carry
            slot = p % 2

            @pl.when(p + 1 < n_pages)
            def _prefetch_next():
                dma((p + 1) % 2, p + 1).start()

            dma(slot, p).wait()
            kc = kv_buf[slot, :, :rank]  # [ps, rank]: keys AND values
            kr = kv_buf[slot, :, rank:rank + rope]  # [ps, rope]
            s = jax.lax.dot_general(
                qc, kc, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            s = s + jax.lax.dot_general(
                qr, kr, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [N, ps]
            ctx = p * page_size + jax.lax.broadcasted_iota(
                jnp.int32, (N, page_size), 1)
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
        _, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, a0))
        return acc / jnp.maximum(l, 1e-20)

    def row_body(j, carry):
        r = first_row + j
        qb, ql, st = q_begins_ref[r], q_lens_ref[r], row_starts_ref[r]
        lo = jnp.maximum(qb, t0)
        hi = jnp.minimum(qb + ql, t0 + block_q)

        @pl.when(hi - lo == 1)
        def _one_token():  # a decode row: H head rows, not the tile's
            i = lo - t0
            pos = st + lo - qb
            out = walk(r, qc_ref[i], qr_ref[i], pos, None,
                       pl.cdiv(pos + 1, page_size))
            o_ref[i] = out.astype(o_ref.dtype)

        @pl.when(hi - lo > 1)
        def _chunk():
            N = block_q * H
            tok = t0 + jax.lax.broadcasted_iota(jnp.int32, (N, 1), 0) // H
            live = (tok >= qb) & (tok < qb + ql)
            out = walk(r, qc_ref[...].reshape(N, rank),
                       qr_ref[...].reshape(N, qr_ref.shape[2]),
                       st + tok - qb, live,
                       pl.cdiv(st + hi - qb, page_size))
            out = out.astype(o_ref.dtype).reshape(block_q, H, rank)
            o_ref[...] = jnp.where(
                live.reshape(block_q, H, 1), out, o_ref[...])

        return carry

    jax.lax.fori_loop(0, n_rows, row_body, 0)


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
) -> jax.Array:
    """Absorbed-form attention of flat ragged tokens over their rows'
    latent pages → the attention-weighted latent rows [T, H, rank]
    (``W_UV`` and the output projection are the caller's).  Token ``t``
    of row ``r`` sits at ``row_starts[r] + (t - q_begins[r])`` and
    attends causally over row ``r``'s pages, its own row included (the
    caller writes before it attends).  Tokens of no row give zeros."""
    T, H, _ = q_lat.shape
    rope = q_rope.shape[2]
    page_size = pages.shape[3]
    Tp = -(-T // block_q) * block_q
    if Tp != T:
        q_lat = jnp.pad(q_lat, ((0, Tp - T), (0, 0), (0, 0)))
        q_rope = jnp.pad(q_rope, ((0, Tp - T), (0, 0), (0, 0)))
    nb = Tp // block_q
    block_rows = _ragged_block_rows(q_begins.astype(jnp.int32),
                                    q_lens.astype(jnp.int32), nb, block_q)

    def tile(width):
        return pl.BlockSpec((block_q, H, width), lambda t, *_: (t, 0, 0),
                            memory_space=pltpu.VMEM)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(nb,),
        in_specs=[tile(rank), tile(rope), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=tile(rank),
        scratch_shapes=[pltpu.VMEM((2, page_size, pages.shape[4]), pages.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    out = pl.pallas_call(
        functools.partial(_mla_kernel, block_q=block_q, page_size=page_size,
                          rank=rank, rope=rope),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Tp, H, rank), q_lat.dtype),
        interpret=interpret,
        name="mla_ragged_paged_attention",
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=MLA_VMEM_LIMIT_BYTES),
    )(page_tables.astype(jnp.int32), row_starts.astype(jnp.int32),
      q_begins.astype(jnp.int32), q_lens.astype(jnp.int32), block_rows,
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
