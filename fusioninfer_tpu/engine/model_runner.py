"""KV-cache-aware forwards: the three programs the engine dispatches.

Each is jitted with fully static shapes (XLA compiles each signature
once and caches it) and runs ONE layer body under :func:`_scan_layers`:

* :func:`prefill` — B whole prompts padded to one bucket: the causal
  flash forward, scattering fresh K/V (or latent rows) into each
  sequence's cache pages; returns logits at each last real token.
* :func:`decode_burst` — the continuous-batching hot loop: ``n_steps``
  decode + sample steps over B sequences × one token with on-device
  token feedback.  Its step is :func:`_decode_step_impl`;
  :func:`decode_step` is the thin jit of that step which tests compare
  a burst against.
* :func:`fused_step` — one weight pass over a flat ragged token axis:
  decode rows, speculative windows, budgeted prefill chunks and
  cache-hit suffixes are rows of it.

Paged attention has two branches in each: the Pallas ragged family of
:mod:`fusioninfer_tpu.ops.paged_attention` (or the latent kernel of
:mod:`fusioninfer_tpu.ops.mla_attention`), which reads pages in place,
through :func:`_ragged_attn`; and the gather-based portable baseline
written out here.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from fusioninfer_tpu.engine.kv_cache import CacheConfig
from fusioninfer_tpu.ops import masks
from fusioninfer_tpu.models.config import ModelConfig
from fusioninfer_tpu.models.quantization import (
    embed_lookup,
    kv_quantize,
    maybe_dequantize_tree,
)
from fusioninfer_tpu.models.transformer import (
    attn_scope,
    gqa_block,
    layer_forward,
    lm_head,
    mla_absorb_queries,
    mla_attn_out,
    mla_block,
    mla_latent,
    mla_queries,
    rms_norm,
    scan_layers as _scan_layers,
)


def _layer_unpack(inputs, has_lora: bool):
    it = iter(inputs)
    layer = next(it)
    layer_lora = next(it) if has_lora else None
    return layer, layer_lora, next(it)


@jax.named_scope("kv_write")
def _scatter_kv(cache: dict, l, k, v, write_page, write_slot,
                head_axis: int, pool: str = "") -> dict:
    """Write fresh K/V (``[..., KV, Hd]`` with the head axis at
    ``head_axis``) into layer ``l`` of the stacked head-major pools
    ``[L, KV, n_pages, ps, Hd]`` IN PLACE, quantizing on the way when
    the cache is int8 (per-token scales land in the
    ``[L, KV, n_pages, 1, ps]`` scale arrays).  ``pool``: the layer
    kind's pool (``cache["k" + pool]``; "_win" = the window kind's).

    The index expression is load-bearing: a scalar basic ``l`` followed
    by an ADJACENT block of advanced indices (kv-head rows, page map,
    slot map) lowers to an in-place scatter on the donated pools.  The
    previous per-layer ``.at[:, page, slot]`` form — a basic slice
    BEFORE the advanced block — moves the advanced dims to the front,
    which XLA implements as a transpose of the ENTIRE operand: measured
    89 ms per 101 MB pool on CPU, and on the chip a full-cache copy per
    layer per step (decode time scaled with pool size, not context)."""
    quantized = "k_scale" in cache
    if quantized:
        k, k_s = kv_quantize(k)
        v, v_s = kv_quantize(v)
    kn, vn = "k" + pool, "v" + pool
    KV = cache[kn].shape[1]
    kvr = jnp.arange(KV).reshape((KV,) + (1,) * write_page.ndim)
    wp = write_page[None]
    ws = write_slot[None]
    out = dict(cache)
    out[kn] = cache[kn].at[l, kvr, wp, ws].set(
        jnp.moveaxis(k, head_axis, 0))
    out[vn] = cache[vn].at[l, kvr, wp, ws].set(
        jnp.moveaxis(v, head_axis, 0))
    if quantized:
        # scatter via the squeezed [L, KV, n_pages, ps] view (a bitcast
        # reshape) so the advanced block stays adjacent here too
        out["k_scale"] = cache["k_scale"][:, :, :, 0].at[
            l, kvr, wp, ws].set(
            jnp.moveaxis(k_s, head_axis, 0))[:, :, :, None, :]
        out["v_scale"] = cache["v_scale"][:, :, :, 0].at[
            l, kvr, wp, ws].set(
            jnp.moveaxis(v_s, head_axis, 0))[:, :, :, None, :]
    return out


@jax.named_scope("kv_write")
def _scatter_latent(cache: dict, l, latent, write_page, write_slot) -> dict:
    """Write fresh latent rows (``[..., rank + rope]``, index maps
    ``write_page`` / ``write_slot`` of the leading shape) into layer
    ``l`` of the pool ``[L, 1, n_pages, ps, W]`` IN PLACE, zero-padded
    to the stored width (:func:`_scatter_kv`'s index form: a scalar
    ``l`` then an adjacent block of advanced indices)."""
    pool = cache["kv"]
    pad = pool.shape[-1] - latent.shape[-1]
    rows = jnp.pad(latent.astype(pool.dtype),
                   ((0, 0),) * (latent.ndim - 1) + ((0, pad),))
    return {**cache, "kv": pool.at[l, 0, write_page, write_slot].set(rows)}


def _add_moe_stats(cache: dict, stats) -> dict:
    """Add one expert layer's counters to the pool tree's running sums
    (``cache["moe_stats"]``, uint32 and wrapping; the engine reads
    differences)."""
    if stats is None:
        return cache
    return {**cache, "moe_stats": cache["moe_stats"] + stats}


def _add_dsa_stats(cfg, cache: dict, positions, live) -> dict:
    """Add one forward's sparse-attention counts to the pool tree's
    running sums (``cache["dsa_stats"]``: the positions the indexer
    scored and those the attention chose, every layer, each as (low,
    high) uint32 words with the carry taken): a live token at position
    ``p`` scores ``p + 1`` and chooses ``min(p + 1, index_topk)``."""
    ctx = jnp.where(live, positions + 1, 0).astype(jnp.uint32)
    add = jnp.stack([jnp.sum(ctx),
                     jnp.sum(jnp.minimum(ctx, jnp.uint32(cfg.index_topk)))]
                    ) * jnp.uint32(cfg.n_cache_layers)
    st = cache["dsa_stats"]
    lo = st[:, 0] + add
    hi = st[:, 1] + (lo < add).astype(jnp.uint32)
    return {**cache, "dsa_stats": jnp.stack([lo, hi], axis=1)}


@jax.named_scope("kv_write")
def _scatter_index_keys(cache: dict, l, k_idx, write_page, write_slot) -> dict:
    """Write fresh indexer keys ``[T, Di]`` into layer ``l`` of
    ``cache["k_idx"]`` ``[L, n_pages, ps, W]`` IN PLACE, zero-padded to the
    stored width, at the pages and slots its K/V go to
    (:func:`_scatter_latent`'s index form)."""
    pool = cache["k_idx"]
    rows = jnp.pad(k_idx.astype(pool.dtype),
                   ((0, 0), (0, pool.shape[-1] - k_idx.shape[-1])))
    return {**cache, "k_idx": pool.at[l, write_page, write_slot].set(rows)}


def _sparse_paged_attend(cfg, cache, q, k, v, idx, cache_l, write, tables,
                         items, *, use_kernel):
    """What a sparse-attention layer does with its fresh rows in every
    paged program (:func:`transformer.gqa_block`'s ``attend`` with the
    indexer's projections ``idx``): write each token's K/V and indexer
    key into layer ``cache_l`` IN PLACE, score the indexer over each
    token's row (``attn/indexer``), choose the exact top ``index_topk``
    (``attn/select``) and attend over those alone (``attn/sparse``) →
    (cache, [T, 1, H * Hd]).  ``q`` [T, 1, H, Hd], ``k`` / ``v`` [T, 1,
    KV, Hd]; ``tables`` [R, mp] the rows' pages; ``items``:
    :func:`ops.sparse_attention.sparse_items` of the same rows."""
    from fusioninfer_tpu.ops import dispatch
    from fusioninfer_tpu.ops import sparse_attention as sa

    q_i, w, k_i = (a[:, 0] for a in idx)
    cache = _scatter_kv(cache, cache_l, k[:, 0], v[:, 0], *write,
                        head_axis=1)
    cache = _scatter_index_keys(cache, cache_l, k_i, *write)
    H, Hd = cfg.n_heads, cfg.head_dim
    KV, G = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
    N, bq = items.tok.shape
    C = tables.shape[1] * cache["k"].shape[3]
    interpret = {"interpret": dispatch.kernel_interpret()} if use_kernel else {}
    with jax.named_scope("attn"):
        with jax.named_scope("indexer"):
            score = (sa.indexer_paged_scores if use_kernel
                     else sa.reference_indexer_paged_scores)
            scores = score(jnp.moveaxis(sa.to_items(q_i, items), 2, 1),
                           sa.to_items(w, items), cache["k_idx"], tables,
                           items, layer=cache_l, **interpret)
        with jax.named_scope("select"):
            if use_kernel:
                thr_s, thr_c = sa.sparse_select(scores, items, cfg.index_topk,
                                                **interpret)
            else:
                thr_s, thr_c = sa.sparse_threshold(scores.reshape(N * bq, C),
                                                   cfg.index_topk)
        with jax.named_scope("sparse"):
            qa = sa.to_items(q[:, 0], items).reshape(N, bq, KV, G, Hd)
            qa = qa.transpose(0, 2, 3, 1, 4).reshape(N, KV, G * bq, Hd)
            attend = (sa.sparse_paged_attention if use_kernel
                      else sa.reference_sparse_paged_attention)
            out = attend(qa, cache["k"], cache["v"], scores,
                         thr_s.reshape(N, bq), thr_c.reshape(N, bq), tables,
                         items, layer=cache_l, **interpret)
            out = out.reshape(N, KV, G, bq, Hd).transpose(0, 3, 1, 2, 4)
            attn = sa.from_items(out.reshape(N, bq, H * Hd), items)
    return cache, attn[:, None, :].astype(q.dtype)


def _sparse_body(cfg, body_inputs, x, positions, cache, live, write, tables,
                 items, lora, adapter_ids, *, use_kernel):
    """One sparse-attention block of flat tokens ``x`` [T, 1, D] (the
    three paged programs share it) → the scan carry ``(x, cache)``."""
    inputs, kind, cache_l = body_inputs
    layer, layer_lora, _ = _layer_unpack(inputs, lora is not None)
    layer = maybe_dequantize_tree(layer, cfg.jax_dtype)

    def attend(q, k, v, cache, idx):
        return _sparse_paged_attend(cfg, cache, q, k, v, idx, cache_l, write,
                                    tables, items, use_kernel=use_kernel)

    x, cache, stats = gqa_block(cfg, layer, x, positions[:, None], kind,
                                attend, cache, live[:, None], layer_lora,
                                adapter_ids)
    return x, _add_moe_stats(cache, stats)


def _cache_layer_of(cfg, l, i: int):
    """The pool's layer of attention ``i`` of stack layer ``l``: ``l``
    itself where a layer has one attention (nothing is traced for it)."""
    return l if cfg.sublayers == 1 else cfg.sublayers * l + i


def _mla_paged_block(cfg, layer, x, positions, cache, l, live, write_page,
                     write_slot, rows, *, use_kernel, interpret, walks):
    """One block (:func:`transformer.mla_block`) of flat tokens ``x``
    [T, 1, D] whose attentions run over latent pages
    (:func:`_mla_attn_block`, each into its own cache layer) → the scan
    carry ``(x, cache)`` with the expert layer's counters added."""

    def attend(i, sub, x, cache):
        return _mla_attn_block(
            cfg, sub, x, positions, cache, _cache_layer_of(cfg, l, i),
            write_page, write_slot, *rows, use_kernel=use_kernel,
            interpret=interpret, walks=walks)

    x, cache, stats = mla_block(cfg, layer, x, attend, cache, live)
    return x, _add_moe_stats(cache, stats)


def _mla_attn_block(cfg, layer, x, positions, cache, l, write_page,
                    write_slot, page_tables, row_starts, q_begins, q_lens,
                    *, use_kernel, interpret, walks=None):
    """Latent attention of flat tokens ``x`` [T, 1, D] over their rows'
    pages, the one body of decode and chunk rows alike: project, write
    each token's latent row into cache layer ``l``, attend in the
    absorbed form straight over the latent pages → (cache, attention
    output [T, 1, D], residual NOT added).  ``walks``:
    :func:`_ragged_walks` of the same rows."""
    from fusioninfer_tpu.ops.mla_attention import (
        mla_ragged_paged_attention,
        reference_mla_ragged_paged_attention,
    )

    pos2 = positions[:, None]
    h = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
    q_nope, q_rope = mla_queries(cfg, layer, h, pos2)
    latent = mla_latent(cfg, layer, h, pos2)
    cache = _scatter_latent(cache, l, latent[:, 0], write_page, write_slot)
    q_lat, q_rope = mla_absorb_queries(cfg, layer, q_nope[:, 0], q_rope[:, 0])
    with jax.named_scope("attn"):
        args = (q_lat, q_rope, cache["kv"], page_tables, row_starts,
                q_begins, q_lens)
        if use_kernel:
            o_lat = mla_ragged_paged_attention(
                *args, layer=l, rank=cfg.kv_lora_rank, interpret=interpret,
                walks=walks)
        else:
            o_lat = reference_mla_ragged_paged_attention(
                *args, layer=l, rank=cfg.kv_lora_rank)
    return cache, mla_attn_out(cfg, layer, o_lat)[:, None, :]


def _cache_layer(cache: dict, l, pool: str = ""):
    """Materialize ONE layer's pools (portable/gather attention branch
    only — the Pallas kernels read the stacked pools in place via their
    ``layer`` operand and never pay this slice)."""
    k_l = lax.dynamic_index_in_dim(cache["k" + pool], l, 0, keepdims=False)
    v_l = lax.dynamic_index_in_dim(cache["v" + pool], l, 0, keepdims=False)
    if "k_scale" in cache:
        ks_l = lax.dynamic_index_in_dim(cache["k_scale"], l, 0,
                                        keepdims=False)
        vs_l = lax.dynamic_index_in_dim(cache["v_scale"], l, 0,
                                        keepdims=False)
        return k_l, v_l, ks_l, vs_l
    return k_l, v_l, None, None


def _dequant_gather(ctx, scale_l, pages, flat_shape):
    """Portable-path read-side dequant: gathered int8 context ``ctx``
    (``[KV, *flat_shape, Hd]``) × its gathered scales → f32."""
    sc = scale_l[:, pages, 0].reshape(*flat_shape)
    return ctx.astype(jnp.float32) * sc[..., None]


def _pool_tables(cfg, cache_cfg, page_tables) -> dict:
    """pool -> (its page tables [R, mp], its trash page, its layers'
    window) for every pool of the model's cache: the one pool of a model
    of one layer kind, or, over a cache kept by layer kind
    (``page_tables`` [R, 2, mp]: a row's list in each pool), the full
    kind's and the window kind's."""
    kinds = {k.pool: k.window for k in cfg.layer_kinds}
    if not cfg.cache_by_kind:
        return {"": (page_tables, cache_cfg.trash_page, kinds[""])}
    return {"": (page_tables[:, 0], cache_cfg.trash_page, kinds[""]),
            "_win": (page_tables[:, 1], cache_cfg.window_trash_page,
                     kinds["_win"])}


def splits_of(kv_splits, pool: str) -> int:
    """A pool's KV-split choice: ``kv_splits`` is one static int, or a
    (full kind, window kind) pair over a cache kept by layer kind."""
    return (kv_splits if isinstance(kv_splits, int)
            else kv_splits[1 if pool else 0])


def _ragged_walks(cfg, cache, mesh, use_kernel, n_tokens, page_tables,
                  row_starts, q_begins, q_lens, kv_splits, pool: str = "",
                  window=None):
    """The paged kernel's walk lists for one forward's rows over ONE
    pool (the ragged family's, or the latent kernel's over a latent
    cache), built BEFORE the layer scan: every layer of a kind scores
    the same rows, and what a scan body computes XLA leaves inside its
    loop.  None where no paged kernel runs (portable branch) and under
    a serving mesh, where each shard's kernel builds its own."""
    if not use_kernel or mesh is not None:
        return None
    if cfg.is_mla:
        from fusioninfer_tpu.ops.mla_attention import mla_walk_lists

        return mla_walk_lists(n_tokens, cache["kv"], row_starts, q_begins,
                              q_lens)
    from fusioninfer_tpu.ops.paged_attention import ragged_walk_lists

    q = jax.ShapeDtypeStruct((n_tokens, cfg.n_heads, cfg.head_dim),
                             cfg.jax_dtype)
    return ragged_walk_lists(
        q, cache["k" + pool], cache["v" + pool], page_tables, row_starts,
        q_begins, q_lens, cache.get("k_scale"), window=window,
        kv_splits=splits_of(kv_splits, pool))


@jax.named_scope("attn")
def _ragged_attn(mesh, q, cache, page_tables, row_starts, q_begins, q_lens,
                 k_scales, v_scales, *, layer, kind, coalesce,
                 kv_splits, interpret, walks=None):
    """The ONE ragged-kernel dispatch every model-path forward routes
    through: tp shard_map when a serving mesh is given, the flash-decode
    KV-split grid when the engine's static heuristic engaged it
    (``kv_splits > 0``, :func:`ops.paged_attention.pick_kv_splits`),
    else the single-walk grid — so no forward can reacquire a private
    kernel-selection policy.  ``walks``: :func:`_ragged_walks` of the
    same rows.  ``kind``: the layer's static kind: its pool is read
    under its window, traced under its scope, and the window kind's
    calls carry a name of their own in the device trace."""
    from fusioninfer_tpu.ops import (
        ragged_paged_attention,
        ragged_paged_attention_kvsplit,
    )

    k_pages, v_pages = cache["k" + kind.pool], cache["v" + kind.pool]
    named = {"name": "ragged_paged_attention_window"} if kind.pool else {}
    with jax.named_scope(attn_scope(kind)):
        if mesh is not None:
            from fusioninfer_tpu.ops.sharded import ragged_paged_attention_tp

            return ragged_paged_attention_tp(
                mesh, q, k_pages, v_pages, page_tables, row_starts,
                q_begins, q_lens, k_scales, v_scales, layer=layer,
                interpret=interpret, window=kind.window, coalesce=coalesce,
                kv_splits=kv_splits)
        if kv_splits > 0:
            return ragged_paged_attention_kvsplit(
                q, k_pages, v_pages, page_tables, row_starts,
                q_begins, q_lens, k_scales, v_scales, layer=layer,
                kv_splits=kv_splits, interpret=interpret,
                window=kind.window, walks=walks, **named)
        return ragged_paged_attention(
            q, k_pages, v_pages, page_tables, row_starts, q_begins,
            q_lens, k_scales, v_scales, layer=layer, interpret=interpret,
            window=kind.window, coalesce=coalesce, walks=walks, **named)


def _paged_attend(mesh, cache, q, k, v, kind, cache_l, write, rows,
                  walks, portable, *, use_kernel, coalesce, kv_splits):
    """What a GQA layer does with its fresh rows in every paged program
    (:func:`transformer.gqa_block`'s ``attend``): write each token's K/V
    (``k`` / ``v`` [T, KV, Hd]) into layer ``cache_l`` of the layer
    kind's pool IN PLACE, then score ``q`` [T, 1, H, Hd] over the kind's
    pages, with its window → (cache, [T, 1, H * Hd]).  ``write``:
    (write_page, write_slot) of the kind's pool; ``rows``: the ragged
    descriptors over that pool; ``portable(cache, q, kind, cache_l)``:
    the caller's gather-based scorer where no kernel runs."""
    from fusioninfer_tpu.ops import dispatch

    cache = _scatter_kv(cache, cache_l, k, v, *write, head_axis=1,
                        pool=kind.pool)
    if not use_kernel:
        with jax.named_scope("attn"), jax.named_scope(attn_scope(kind)):
            return cache, portable(cache, q, kind, cache_l)
    attn = _ragged_attn(
        mesh, q[:, 0], cache, *rows, cache.get("k_scale"),
        cache.get("v_scale"), layer=cache_l, kind=kind, coalesce=coalesce,
        kv_splits=splits_of(kv_splits, kind.pool),
        interpret=dispatch.kernel_interpret(), walks=walks)
    return cache, attn[:, None, :]


@partial(jax.jit, static_argnums=(0, 1), static_argnames=("mesh",), donate_argnums=(3,))
def prefill(
    cfg: ModelConfig,
    cache_cfg: CacheConfig,
    params,
    cache: dict,
    tokens: jax.Array,  # [B, S] — B sequences padded to one bucket
    true_lens: jax.Array,  # [B] int32
    page_rows: jax.Array,  # [B, max_pages_per_seq]
    mesh=None,  # tp-only serving mesh: shard_map'd kernels per TP shard
    lora=None,  # stacked AdapterSet tree ([L, N, ...] per projection)
    adapter_ids: jax.Array = None,  # [B] int32; 0 = base model
):
    """Prefill B sequences in one forward; returns (cache, last-token
    logits [B, V]).

    Batching prompts raises MXU utilization and turns an N-request burst
    into ⌈N/group⌉ compiled calls instead of N (the engine groups
    admissible same-bucket requests — vLLM batches prefills the same
    way).  Causality is per row: flash attention's batch dim isolates
    sequences, and each row's padded positions write to the trash page.
    """
    B, S = tokens.shape
    ps = cache_cfg.page_size
    if cfg.is_sparse:
        return _sparse_prefill(cfg, cache_cfg, params, cache, tokens,
                               true_lens, page_rows, lora, adapter_ids)
    x = embed_lookup(params["embed"], tokens, cfg.jax_dtype)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    token_idx = jnp.arange(S)[None, :]  # [1, S]
    # Padded positions (>= true_len) write to the trash page (each
    # pool's own, over a cache kept by layer kind).
    page_of_token = {
        pool: jnp.where(
            token_idx < true_lens[:, None],
            jnp.take_along_axis(rows, token_idx // ps, axis=1),
            trash,
        )  # [B, S]
        for pool, (rows, trash, _) in _pool_tables(
            cfg, cache_cfg, page_rows).items()}
    slot_of_token = jnp.broadcast_to(token_idx % ps, (B, S))
    live = token_idx < true_lens[:, None]  # [B, S]

    def body(carry, inputs, kind, cache_l):
        x, cache = carry
        layer, layer_lora, l = _layer_unpack(inputs, lora is not None)
        out, kv, stats = layer_forward(
            cfg, layer, x, positions, mesh=mesh, lora=layer_lora,
            adapter_ids=adapter_ids, live=live, kind=kind)
        if cfg.is_mla:  # a fresh prompt attends in the expanded form and
            # caches what decode will read: each attention's latent rows
            # [B, S, .] into its own cache layer
            for i, latent in enumerate(kv):
                cache = _scatter_latent(cache, _cache_layer_of(cfg, l, i),
                                        latent, page_of_token[""],
                                        slot_of_token)
        else:
            # stacked head-major cache [L, KV, n_pages, ps, Hd]; k is
            # [B, S, KV, Hd] → in-place scatter at the layer's place in
            # its kind's pool, [B, S] maps
            cache = _scatter_kv(cache, cache_l, *kv,
                                page_of_token[kind.pool], slot_of_token,
                                head_axis=2, pool=kind.pool)
        return out, _add_moe_stats(cache, stats)

    x, cache = _scan_layers(cfg, params, lora, body, (x, cache))
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    last = x[jnp.arange(B), jnp.maximum(true_lens - 1, 0)]  # [B, D]
    return cache, lm_head(cfg, params, last)


def _sparse_prefill(cfg, cache_cfg, params, cache, tokens, true_lens,
                    page_rows, lora, adapter_ids):
    """:func:`prefill` of a sparse-attention model: the B prompts as rows
    of ONE flat token axis (row ``b`` = tokens ``[b S, b S + len_b)`` at
    positions from 0), written into their pages and attended over them
    layer by layer, as the ragged step does."""
    from fusioninfer_tpu.ops import dispatch
    from fusioninfer_tpu.ops.sparse_attention import (
        SPARSE_BLOCK_Q,
        sparse_items,
    )

    B, S = tokens.shape
    ps = cache_cfg.page_size
    T = B * S
    use_kernel = dispatch.resolve_attn(cfg.attn_impl) == "flash"
    off = jnp.broadcast_to(jnp.arange(S), (B, S))
    live = (off < true_lens[:, None]).reshape(T)
    positions = off.reshape(T)
    write_page = jnp.where(
        live, jnp.take_along_axis(page_rows, off // ps, axis=1).reshape(T),
        cache_cfg.trash_page)
    write = (write_page, positions % ps)
    items = sparse_items(jnp.arange(B, dtype=jnp.int32) * S, true_lens,
                         jnp.zeros((B,), jnp.int32), T, SPARSE_BLOCK_Q)
    ids = (None if adapter_ids is None
           else jnp.repeat(adapter_ids, S, total_repeat_length=T))
    x = embed_lookup(params["embed"], tokens.reshape(T), cfg.jax_dtype)
    cache = _add_dsa_stats(cfg, cache, positions, live)

    def body(carry, inputs, kind, cache_l):
        return _sparse_body(cfg, (inputs, kind, cache_l), carry[0],
                            positions, carry[1], live, write, page_rows,
                            items, lora, ids, use_kernel=use_kernel)

    x, cache = _scan_layers(cfg, params, lora, body, (x[:, None, :], cache))
    x = rms_norm(x[:, 0], params["final_norm"], cfg.rms_eps).reshape(B, S, -1)
    last = x[jnp.arange(B), jnp.maximum(true_lens - 1, 0)]  # [B, D]
    return cache, lm_head(cfg, params, last)


def _decode_step_impl(
    cfg: ModelConfig,
    cache_cfg: CacheConfig,
    params,
    cache: dict,
    tokens: jax.Array,  # [B] current input token per sequence
    positions: jax.Array,  # [B] index the token lands at (== tokens so far)
    page_tables: jax.Array,  # [B, max_pages_per_seq]
    active: jax.Array,  # [B] bool
    mesh=None,  # tp-only serving mesh: shard_map'd kernels per TP shard
    lora=None,  # stacked AdapterSet tree ([L, N, ...] per projection)
    adapter_ids: jax.Array = None,  # [B] int32; 0 = base model
    coalesce: bool = None,  # decode-kernel grid; the ENGINE resolves the
    # FUSIONINFER_DECODE_COALESCE env var eagerly per call so a
    # mid-process flip retraces instead of reusing the latched variant
    kv_splits: int = 0,  # flash-decode KV-split grid (0 = single walk)
):
    """One decode step for the whole running batch → (cache, logits [B, V])."""
    from fusioninfer_tpu.ops import dispatch

    B = tokens.shape[0]
    ps = cache_cfg.page_size
    mp = page_tables.shape[-1]
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    quantized = cache_cfg.quantized
    use_kernel = dispatch.resolve_attn(cfg.attn_impl) == "flash"

    x = embed_lookup(params["embed"], tokens, cfg.jax_dtype)[:, None, :]  # [B, 1, D]
    pos = positions[:, None]  # [B, 1]
    if cfg.is_sparse:
        # B rows of one token each, as the ragged step lays them out
        from fusioninfer_tpu.ops.sparse_attention import (
            SPARSE_BLOCK_Q_DECODE,
            sparse_items,
        )

        items = sparse_items(jnp.arange(B, dtype=jnp.int32),
                             active.astype(jnp.int32), positions, B,
                             SPARSE_BLOCK_Q_DECODE)
        write = (jnp.where(active, page_tables[jnp.arange(B), positions // ps],
                           cache_cfg.trash_page), positions % ps)
        cache = _add_dsa_stats(cfg, cache, positions, active)

        def sparse(carry, inputs, kind, cache_l):
            return _sparse_body(cfg, (inputs, kind, cache_l), carry[0],
                                positions, carry[1], active, write,
                                page_tables, items, lora, adapter_ids,
                                use_kernel=use_kernel)

        x, cache = _scan_layers(cfg, params, lora, sparse, (x, cache))
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        return cache, lm_head(cfg, params, x[:, 0])

    # per pool of the cache (one, or the full and the window kind's):
    # where this step's token lands, the ONE ragged kernel's degenerate
    # descriptors (B rows of one token each, q_len = active), its walk
    # lists, and the attention mask over the gathered [mp * ps] context
    # (reference path)
    write, rows, walks, attend = {}, {}, {}, {}
    for pool, (tables, trash, window) in _pool_tables(
            cfg, cache_cfg, page_tables).items():
        write_page = jnp.where(
            active, tables[jnp.arange(B), positions // ps], trash
        )
        write[pool] = (write_page, positions % ps)
        rows[pool] = (tables, positions, jnp.arange(B, dtype=jnp.int32),
                      active.astype(jnp.int32))
        walks[pool] = _ragged_walks(cfg, cache, mesh, use_kernel, B,
                                    *rows[pool], kv_splits, pool, window)
        ctx_idx = jnp.arange(mp * ps)[None, :]  # [1, T]
        attend[pool] = masks.attend(
            positions[:, None], ctx_idx,
            window)[:, None, None, :]  # [B, 1, 1, T] (new token included)

    def portable(cache, q, kind, cache_l):
        # gather pages [KV, B, mp, ps, Hd] -> [KV, B, T, Hd]
        tables = rows[kind.pool][0]
        k_cache_l, v_cache_l, ks_l, vs_l = _cache_layer(cache, cache_l,
                                                        kind.pool)
        k_ctx = k_cache_l[:, tables].reshape(KV, B, mp * ps, Hd)
        v_ctx = v_cache_l[:, tables].reshape(KV, B, mp * ps, Hd)
        if quantized:
            k_ctx = _dequant_gather(k_ctx, ks_l, tables, (KV, B, mp * ps))
            v_ctx = _dequant_gather(v_ctx, vs_l, tables, (KV, B, mp * ps))

        group = H // KV
        qg = q.reshape(B, 1, KV, group, Hd)
        scores = jnp.einsum("bskgd,kbtd->bkgst", qg, k_ctx).astype(jnp.float32) / jnp.sqrt(Hd)
        scores = jnp.where(
            attend[kind.pool][:, :, None, :, :] * jnp.ones_like(scores, bool),
            scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(v_ctx.dtype)
        return jnp.einsum("bkgst,kbtd->bskgd", probs, v_ctx).reshape(
            B, 1, H * Hd).astype(x.dtype)

    def body(carry, inputs, kind, cache_l):
        x, cache = carry
        layer, layer_lora, l = _layer_unpack(inputs, lora is not None)
        layer = maybe_dequantize_tree(layer, cfg.jax_dtype)
        if cfg.is_mla:
            # B rows of one token each through the latent kernel: the
            # same body (and bits) the fused step scores decode rows with
            return _mla_paged_block(
                cfg, layer, x, positions, cache, l, active[:, None],
                *write[""], rows[""], use_kernel=use_kernel,
                interpret=dispatch.kernel_interpret(), walks=walks[""])

        def attend_paged(q, k, v, cache):
            # this step's K/V into each sequence's page slot, then the
            # same kernel (and bits) the fused mixed-batch path scores
            # decode rows with
            return _paged_attend(
                mesh, cache, q, k[:, 0], v[:, 0], kind, cache_l,
                write[kind.pool], rows[kind.pool], walks[kind.pool],
                portable, use_kernel=use_kernel, coalesce=coalesce,
                kv_splits=kv_splits)

        x, cache, stats = gqa_block(
            cfg, layer, x, pos, kind, attend_paged, cache, active[:, None],
            layer_lora, adapter_ids)
        return x, _add_moe_stats(cache, stats)

    x, cache = _scan_layers(cfg, params, lora, body, (x, cache))
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = lm_head(cfg, params, x[:, 0])
    return cache, logits


decode_step = partial(
    jax.jit, static_argnums=(0, 1),
    static_argnames=("mesh", "coalesce", "kv_splits"),
    donate_argnums=(3,))(_decode_step_impl)


# ctl_i / ctl_f column layout for decode_burst's packed control arrays.
# Every per-row scalar rides ONE int32 and ONE float32 upload instead of
# ~14 separate transfers.
CTL_I_COLS = ("tokens", "positions", "top_k", "min_tokens", "gen_count",
              "seed_bits", "adapter_id", "active")
CTL_F_COLS = ("temperature", "top_p", "min_p", "presence", "frequency",
              "repetition")


@partial(jax.jit, static_argnums=(0, 1),
         static_argnames=("mesh", "n_steps", "sample_mode", "coalesce",
                          "kv_splits"),
         donate_argnums=(3, 6, 7))
def decode_burst(
    cfg: ModelConfig,
    cache_cfg: CacheConfig,
    params,
    cache: dict,
    ctl_i: jax.Array,  # [B, 8] int32 — CTL_I_COLS (seeds bitcast u32→i32)
    ctl_f: jax.Array,  # [B, 6] float32 — CTL_F_COLS
    token_counts: jax.Array,  # [B, V] int32 — penalty counts (prompt+out)
    output_counts: jax.Array,  # [B, V] int32 — penalty counts (out only)
    suppress: jax.Array,  # [B, V] bool — min_tokens stop-id suppression
    page_tables: jax.Array,  # [B, max_pages_per_seq]
    n_steps: int = 8,
    sample_mode: str = "filtered",  # static hint, see sampler.sample
    mesh=None,
    lora=None,
    coalesce: bool = None,  # decode-kernel grid, resolved by the caller
    kv_splits: int = 0,  # flash-decode KV-split grid (0 = single walk)
):
    """``n_steps`` fused decode+sample steps with on-device token
    feedback → ``(cache, sampled [n_steps, B], token_counts,
    output_counts, next_ctl_i)``.

    Per-token stepping pays a host↔device round trip — ~14 per-step
    array uploads plus the blocking fetch — for every token; whether
    that or the device step dominates on a directly attached chip is
    ROADMAP S2's question.  This is the multi-step scheduling answer,
    twice over: one jitted ``lax.scan`` runs the full decode→penalties→min-tokens→
    sample→count-bump chain ``n_steps`` times, feeding each row's
    sampled token back as the next input on device (ONE round trip per
    ``n_steps`` tokens), and every per-row control scalar is packed
    into two arrays (``ctl_i``/``ctl_f``, columns above) so the call
    uploads 3 arrays instead of ~14.  Key derivation, penalty ordering
    and filtering are the exact single-step math
    (:func:`fusioninfer_tpu.engine.sampler.sample` et al. inline into
    the scan body), so burst output is bit-identical to ``n_steps``
    sequential ``decode_step`` calls.

    Rows that finish mid-burst (stop token / max_tokens, detected host
    side after the fetch) keep decoding garbage until the burst ends;
    the engine discards those tokens.  Their KV writes land either in
    pages the row exclusively owns (freed at finish) or — once a row's
    position would exceed its page table's reach — the row is force-
    deactivated in-scan (``pos_ok`` below) so the write is redirected
    to the trash page rather than clamp-corrupting a real page.

    Eligibility is the engine's call: speculative, guided, logprobs and
    logit_bias rows need host work per token and fall back to the
    single-step path (`engine.Engine._burst_span`).
    """
    from fusioninfer_tpu.engine.sampler import (
        apply_penalties,
        make_row_keys,
        sample,
    )

    tokens = ctl_i[:, 0]
    positions = ctl_i[:, 1]
    top_ks = ctl_i[:, 2]
    min_toks = ctl_i[:, 3]
    gen_counts = ctl_i[:, 4]
    seeds = lax.bitcast_convert_type(ctl_i[:, 5], jnp.uint32)
    adapter_ids = ctl_i[:, 6] if lora is not None else None
    active = ctl_i[:, 7] > 0
    temps = ctl_f[:, 0]
    top_ps = ctl_f[:, 1]
    min_ps = ctl_f[:, 2]
    presence = ctl_f[:, 3]
    frequency = ctl_f[:, 4]
    repetition = ctl_f[:, 5]

    max_tokens_covered = page_tables.shape[-1] * cache_cfg.page_size

    def one(carry, _):
        cache, toks, pos, tcounts, ocounts, gcounts = carry
        # a row whose next write would fall past its page table cannot
        # run this step: gather-index clamping would silently write into
        # its own LAST real page (which may be prefix-cache-shared)
        act = active & (pos < max_tokens_covered)
        cache, logits = _decode_step_impl(
            cfg, cache_cfg, params, cache, toks, pos, page_tables, act,
            mesh=mesh, lora=lora, adapter_ids=adapter_ids,
            coalesce=coalesce, kv_splits=kv_splits)
        with jax.named_scope("sample"):
            logits = apply_penalties(logits, tcounts, ocounts,
                                     presence, frequency, repetition)
            logits = jnp.where((gcounts < min_toks)[:, None] & suppress,
                               -jnp.inf, logits)
            keys = make_row_keys(seeds, gcounts)
            sampled = sample(logits, keys, temps, top_ks, top_ps, min_ps,
                             mode=sample_mode)
            inc = act.astype(tcounts.dtype)
            rows = jnp.arange(sampled.shape[0])
            tcounts = tcounts.at[rows, sampled].add(inc)
            ocounts = ocounts.at[rows, sampled].add(inc)
        step = act.astype(pos.dtype)
        next_tok = jnp.where(act, sampled, toks)
        return (cache, next_tok, pos + step, tcounts, ocounts,
                gcounts + step), sampled

    (cache, toks_f, pos_f, token_counts, output_counts, gcounts_f), \
        sampled_all = lax.scan(
            one, (cache, tokens, positions, token_counts, output_counts,
                  gen_counts),
            None, length=n_steps)
    # device-side control carry for burst PIPELINING: the successor
    # burst's inputs (advanced tokens/positions/gen_counts, other
    # columns copied) without any host round trip — the engine can
    # dispatch burst N+1 from this BEFORE blocking on burst N's fetch
    next_ctl_i = jnp.stack(
        [toks_f, pos_f, ctl_i[:, 2], ctl_i[:, 3], gcounts_f,
         ctl_i[:, 5], ctl_i[:, 6], ctl_i[:, 7]], axis=1)
    return cache, sampled_all, token_counts, output_counts, next_ctl_i


@partial(jax.jit, static_argnums=(0, 1),
         static_argnames=("mesh", "coalesce", "kv_splits", "decode_hidden"),
         donate_argnums=(3,))
def fused_step(
    cfg: ModelConfig,
    cache_cfg: CacheConfig,
    params,
    cache: dict,
    tokens: jax.Array,  # [T] int32 — flat ragged-concat token axis
    row_starts: jax.Array,  # [R] int32: global position of row's token 0
    q_begins: jax.Array,  # [R] int32: flat offset of each row's segment
    q_lens: jax.Array,  # [R] int32: row token count (0 = inert row)
    page_tables: jax.Array,  # [R, max_pages_per_seq]
    sel: jax.Array,  # [B, W] int32: decode slots' FLAT window indices
    chunk_sel: jax.Array,  # [NC] int32: chunk rows' FLAT last-token indices
    mesh=None,  # tp-only serving mesh: shard_map'd kernels per TP shard
    lora=None,  # stacked AdapterSet tree ([L, N, ...] per projection)
    adapter_ids: jax.Array = None,  # [R] int32 per ROW; 0 = base model
    coalesce: bool = None,  # ragged-grid variant, resolved by the engine
    kv_splits: int = 0,  # flash-decode KV-split grid (0 = single walk);
    # static per engine (pick_kv_splits over the cache config)
    decode_hidden: bool = False,  # fused-sampling path: return the decode
    # group's HIDDEN states [B, W, D] instead of its logits, so the
    # engine's lm_head→top-k never materializes [B·W, V]
    carry_tokens: jax.Array = None,  # [B] int32 — the previous step's
    # draws, still on the device (the mixed program's operands only)
    carry: jax.Array = None,  # [B] bool — decode slots whose input token
    # is ``carry_tokens[slot]`` instead of ``tokens[q_begins[slot]]``
):
    """ONE weight pass over a flat ragged-concat token axis →
    (cache, logits [B, W, V], chunk_logits [NC, V]).

    The unified engine step: decode rows (q_len=1), speculative verify
    windows (q_len=1+drafts) and budgeted prefill chunks (q_len=chunk)
    concatenate along ONE token dimension — ``T = Σ q_lens`` plus the
    power-of-two signature pad — and ride a single embed → layer-scan →
    lm_head forward.  With ``carry`` the decode slots it marks take their
    input token from ``carry_tokens`` (a dispatched-ahead mixed step's
    decode rows read the step before's draws without a host round trip:
    the engine's ``_chain_mixed``).  Decode is weight-bandwidth-bound by
    arithmetic
    (ROADMAP S1 has the chip measurement to make), so chunked prefill
    riding the same pass should be nearly free; unlike the retired ``[rows, C]`` rectangle,
    dense (embed/QKV/MLP) work grows with the REAL token count — a
    decode row costs one token whatever the chunk bucket is (the Ragged
    Paged Attention layout, PAPERS.md).

    Attention is :func:`fusioninfer_tpu.ops.ragged_paged_attention` —
    the same kernel decode-only and chunk-only dispatches use, with
    per-token output bits independent of what else shares the batch —
    so there is no scorer switch anywhere on the model path: split and
    fused engine streams are bit-identical, kernel and portable alike.
    The portable branch gathers each token's own pages with the exact
    einsum structure of ``decode_step``'s (flat tokens ride the batch
    axis).

    ``sel``/``chunk_sel`` keep lm_head narrow AND shape-stable: only
    the flat positions the engine will read project — decode slots
    their sampled-token logits (and spec windows), chunk rows their
    last real token for activation — never a [T, V] tensor.  The two
    groups project through SEPARATE lm_head calls because XLA's bf16
    matmul bits vary with the row count: the decode group is always
    ``[B·W, D]`` (constant per engine) and the chunk group ``[NC, D]``
    (the pow2-padded chunk count, equal between a split chunk advance
    and the fused step that absorbs it), so a stream's logits bits
    never depend on which dispatch computed them.
    """
    from fusioninfer_tpu.ops import dispatch
    from fusioninfer_tpu.ops.paged_attention import ragged_token_rows

    T = tokens.shape[0]
    ps = cache_cfg.page_size
    mp = page_tables.shape[-1]
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    quantized = cache_cfg.quantized
    use_kernel = dispatch.resolve_attn(cfg.attn_impl) == "flash"

    if carry is not None:
        # a decode slot's one token sits at its segment's start; slots
        # without a carry scatter out of range and are dropped (a dead
        # slot's zero-length segment shares its start with a live one's)
        at = jnp.where(carry, q_begins[: carry.shape[0]], T)
        tokens = tokens.at[at].set(carry_tokens.astype(tokens.dtype),
                                   mode="drop")
    row_of, off, live = ragged_token_rows(q_begins, q_lens, T)
    positions = jnp.where(live, row_starts[row_of] + off, 0)
    pools = _pool_tables(cfg, cache_cfg, page_tables)
    tables_tok, write = {}, {}
    for pool, (tables, trash, _) in pools.items():
        tables_tok[pool] = tables[row_of]  # [T, mp] — each token's row's pages
        write_page = jnp.where(
            live, tables_tok[pool][jnp.arange(T), positions // ps], trash,
        )
        write[pool] = (write_page, positions % ps)
    adapter_tok = adapter_ids[row_of] if adapter_ids is not None else None

    x = embed_lookup(params["embed"], tokens, cfg.jax_dtype)[:, None, :]
    pos2 = positions[:, None]  # [T, 1]
    if cfg.is_sparse:
        from fusioninfer_tpu.ops.sparse_attention import (
            SPARSE_BLOCK_Q,
            sparse_items,
        )

        items = sparse_items(q_begins, q_lens, row_starts, T, SPARSE_BLOCK_Q)
        cache = _add_dsa_stats(cfg, cache, positions, live)

        def sparse(carry, inputs, kind, cache_l):
            return _sparse_body(cfg, (inputs, kind, cache_l), carry[0],
                                positions, carry[1], live, write[""],
                                page_tables, items, lora, adapter_tok,
                                use_kernel=use_kernel)

        x, cache = _scan_layers(cfg, params, lora, sparse, (x, cache))
        return _fused_heads(cfg, params, cache, x, sel, chunk_sel,
                            decode_hidden)

    rows = {pool: (tables, row_starts, q_begins, q_lens)
            for pool, (tables, _, _) in pools.items()}
    walks = {pool: _ragged_walks(cfg, cache, mesh, use_kernel, T,
                                 *rows[pool], kv_splits, pool, window)
             for pool, (_, _, window) in pools.items()}

    # portable-path mask over each token's own gathered [mp * ps] context
    attend = {}
    for pool, (_, _, window) in pools.items():
        ctx_idx = jnp.arange(mp * ps)[None, :]  # [1, T_ctx]
        attend[pool] = (masks.attend(positions[:, None], ctx_idx, window)
                        & live[:, None])[:, None, None, :]  # [T, 1, 1, T_ctx]

    def portable(cache, q, kind, cache_l):
        # portable flat gather: decode_step's einsum with the flat
        # tokens on the batch axis — per-token bits independent of
        # the rest of the batch, so split/fused stay bit-identical.
        # int8 pages fold their scales AFTER the dots (the kernel's
        # scale-after-dot identity): multiplying the scale into the
        # contraction operand lets XLA move it inside or outside
        # the Σ_d per shape — a T-dependent algebraic rewrite that
        # flipped sampled streams between split and fused packs
        tok = tables_tok[kind.pool]
        k_cache_l, v_cache_l, ks_l, vs_l = _cache_layer(cache, cache_l,
                                                        kind.pool)
        k_ctx = k_cache_l[:, tok].reshape(KV, T, mp * ps, Hd)
        v_ctx = v_cache_l[:, tok].reshape(KV, T, mp * ps, Hd)
        if quantized:
            k_ctx = k_ctx.astype(jnp.float32)
            v_ctx = v_ctx.astype(jnp.float32)
            # per-(head, token, position) scale planes [KV, T, S] →
            # broadcast over the score axes (b=token, k, g, s=1, t)
            k_sc = ks_l[:, tok, 0].reshape(
                KV, T, mp * ps).transpose(1, 0, 2)[:, :, None, None, :]
            v_sc = vs_l[:, tok, 0].reshape(
                KV, T, mp * ps).transpose(1, 0, 2)[:, :, None, None, :]

        group = H // KV
        qg = q.reshape(T, 1, KV, group, Hd)
        scores = jnp.einsum("bskgd,kbtd->bkgst", qg, k_ctx).astype(
            jnp.float32) / jnp.sqrt(Hd)
        if quantized:
            scores = scores * k_sc
        scores = jnp.where(
            attend[kind.pool][:, :, None, :, :] * jnp.ones_like(scores, bool),
            scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(v_ctx.dtype)
        if quantized:
            probs = probs * v_sc
        return jnp.einsum("bkgst,kbtd->bskgd", probs, v_ctx).reshape(
            T, 1, H * Hd).astype(x.dtype)

    def body(carry, inputs, kind, cache_l):
        x, cache = carry
        layer, layer_lora, l = _layer_unpack(inputs, lora is not None)
        layer = maybe_dequantize_tree(layer, cfg.jax_dtype)
        if cfg.is_mla:
            return _mla_paged_block(
                cfg, layer, x, positions, cache, l, live[:, None],
                *write[""], rows[""],
                use_kernel=use_kernel, interpret=dispatch.kernel_interpret(),
                walks=walks[""])

        def attend_paged(q, k, v, cache):
            # stacked head-major cache [L, KV, n_pages, ps, Hd]; k[:, 0]
            # is [T, KV, Hd] → in-place scatter at the layer's place in
            # its kind's pool, per-token maps
            return _paged_attend(
                mesh, cache, q, k[:, 0], v[:, 0], kind, cache_l,
                write[kind.pool], rows[kind.pool], walks[kind.pool],
                portable, use_kernel=use_kernel, coalesce=coalesce,
                kv_splits=kv_splits)

        x, cache, stats = gqa_block(
            cfg, layer, x, pos2, kind, attend_paged, cache, live[:, None],
            layer_lora, adapter_tok)
        return x, _add_moe_stats(cache, stats)

    x, cache = _scan_layers(cfg, params, lora, body, (x, cache))
    return _fused_heads(cfg, params, cache, x, sel, chunk_sel, decode_hidden)


def _fused_heads(cfg, params, cache, x, sel, chunk_sel, decode_hidden):
    """:func:`fused_step`'s tail: the final norm, then the decode group's
    and the chunk rows' heads through separate ``lm_head`` calls."""
    T = x.shape[0]
    x = rms_norm(x, params["final_norm"], cfg.rms_eps)
    h = x[:, 0]  # [T, D]
    idx = jnp.clip(sel.astype(jnp.int32), 0, T - 1)  # [B, W]
    cidx = jnp.clip(chunk_sel.astype(jnp.int32), 0, T - 1)  # [NC]
    chunk_logits = lm_head(cfg, params, h[cidx])  # [NC, V]
    if decode_hidden:
        # fused-sampling path: hand the decode group's hidden states to
        # the engine's blocked lm_head→top-k (ops/lm_head_topk.py) —
        # the SAME [B·W, D] gather the logits path projects, so the
        # candidates it produces are bit-identical to top-k over the
        # unfused logits below
        picked = h[idx.reshape(idx.size)]  # [B·W, D]
        return cache, picked.reshape(*idx.shape, h.shape[-1]), chunk_logits
    # FLAT [B·W, D] through lm_head — the same [N, D] @ [D, V] shape
    # decode_step projects, so a decode row's logits bits match the
    # classic/burst path's exactly
    logits = lm_head(cfg, params, h[idx.reshape(idx.size)])  # [B·W, V]
    logits = logits.reshape(*idx.shape, logits.shape[-1])  # [B, W, V]
    return cache, logits, chunk_logits


def prefill_buckets(max_len: int, smallest: int = 32) -> list[int]:
    """Power-of-two padding buckets: each prompt compiles against the
    smallest bucket that holds it, bounding compile count to log2(max)."""
    out = []
    b = smallest
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return out


def pick_bucket(buckets: list[int], n: int) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt of {n} tokens exceeds max bucket {buckets[-1]}")
