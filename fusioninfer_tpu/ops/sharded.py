"""shard_map wrappers: the Pallas attention kernels under tensor parallelism.

Megatron-style TP shards attention by head: each device owns ``H/tp``
query heads and ``KV/tp`` KV heads.  With ``tp | KV`` (the engine already
requires it for the KV cache) every GQA group lives wholly on one shard,
so attention needs **zero** cross-device communication — each shard runs
the single-device kernel on its local heads and the row-parallel output
projection's psum (inserted by XLA from the shardings) is the only
collective.  These wrappers express exactly that: kernel inside
``shard_map``, head axes split over ``tp``, everything else replicated.

The serving mesh must be tp-only (dp=sp=ep=1) — the engine falls back to
the jnp reference path otherwise.

Every in/out spec here is DERIVED from the canonical logical-axis table
(:mod:`fusioninfer_tpu.parallel.axes`): the head axes name ``heads`` /
``kv`` (→ ``tp`` under the Megatron rules) and everything else —
descriptor rows, page tables, flat token axes — is replicated by
construction on the tp-only mesh this module serves, so those axes are
spelled ``None`` / ``rows`` / ``tokens`` (all replicated).  No raw
``PartitionSpec`` literals live here (fusionlint ``sharding-discipline``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh

from fusioninfer_tpu.ops.flash_attention import flash_attention
from fusioninfer_tpu.ops.paged_attention import (
    _as_stacked,
    ragged_paged_attention,
    ragged_paged_attention_kvsplit,
)
from fusioninfer_tpu.parallel import sharding as _sharding
from fusioninfer_tpu.parallel.axes import default_rules

_RULES = default_rules()
# [(L,) KV, n_pages, ps, Hd] stacked pools / [(L,) KV, n_pages, 1, ps]
# int8 per-token scale planes: KV heads over tp, like the cache itself
_KV_SPEC = _sharding.kv_cache_spec(_RULES)
_SCALE_SPEC = _sharding.kv_scale_spec(_RULES)
# replicated descriptor shapes (each shard sees every row/token id)
_ROW_SPEC = _RULES.spec("rows")  # [R] row starts / begins / lengths
_TABLE_SPEC = _RULES.spec("rows", "pages")  # [R, mp] page tables


def tp_compatible(mesh: Mesh, n_heads: int, n_kv_heads: int) -> bool:
    """True when the kernels can run per-shard without communication."""
    if "tp" not in mesh.axis_names:
        return False
    tp = mesh.shape["tp"]
    others = [mesh.shape[a] for a in mesh.axis_names if a != "tp"]
    return (
        tp > 1
        and all(s == 1 for s in others)
        and n_kv_heads % tp == 0
        and n_heads % tp == 0
    )


def flash_attention_tp(
    mesh: Mesh,
    q: jax.Array,  # [B, S, H, Hd] — H sharded over tp
    k: jax.Array,  # [B, S, KV, Hd] — KV sharded over tp
    v: jax.Array,
    *,
    causal: bool = True,
    interpret: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Per-shard flash attention → [B, S, H·Hd] sharded on the feature axis."""
    head_spec = _RULES.spec(None, None, "heads", "head_dim")
    fn = shard_map(
        partial(flash_attention, causal=causal, interpret=interpret,
                window=window),
        mesh=mesh,
        in_specs=(head_spec, head_spec, head_spec),
        out_specs=_RULES.spec(None, None, "heads"),
        check_vma=False,
    )
    return fn(q, k, v)


def ragged_paged_attention_tp(
    mesh: Mesh,
    q: jax.Array,  # [T, H, Hd] flat ragged tokens — H sharded over tp
    k_pages: jax.Array,  # [(L,) KV, n_pages, ps, Hd] — KV sharded over tp
    v_pages: jax.Array,
    page_tables: jax.Array,  # [R, mp] replicated
    row_starts: jax.Array,  # [R] replicated
    q_begins: jax.Array,  # [R] replicated
    q_lens: jax.Array,  # [R] replicated
    k_scale: jax.Array | None = None,  # [(L,) KV, n_pages, 1, ps] — int8
    v_scale: jax.Array | None = None,
    *,
    interpret: bool = False,
    window: int | None = None,
    coalesce: bool | None = None,  # resolved by the engine per call
    kv_splits: int = 0,  # flash-decode KV-split grid; 0 = single walk
    layer: jax.Array | int | None = None,
) -> jax.Array:
    """Per-shard ragged paged attention → [T, H·Hd] sharded on features.
    The row descriptors are replicated (they index tokens and pages, not
    heads); each shard runs the one ragged kernel on its local heads —
    the KV-split grid included, whose split axis is page-parallel and
    therefore orthogonal to the head sharding."""
    k_pages, v_pages, k_scale, v_scale, layer = _as_stacked(
        k_pages, v_pages, k_scale, v_scale, layer)
    in_specs = [
        _RULES.spec("tokens", "heads", "head_dim"),
        _KV_SPEC,
        _KV_SPEC,
        _TABLE_SPEC,
        _ROW_SPEC,
        _ROW_SPEC,
        _ROW_SPEC,
        _ROW_SPEC,
    ]
    args = [q, k_pages, v_pages, page_tables, row_starts, q_begins,
            q_lens, layer]
    if k_scale is not None:
        in_specs += [_SCALE_SPEC, _SCALE_SPEC]
        args += [k_scale, v_scale]

    def run(q, kp, vp, pt, rs, qb, ql, l, *scales):
        ks, vs = scales if scales else (None, None)
        if kv_splits > 0:
            return ragged_paged_attention_kvsplit(
                q, kp, vp, pt, rs, qb, ql, ks, vs, kv_splits=kv_splits,
                interpret=interpret, window=window, layer=l)
        return ragged_paged_attention(q, kp, vp, pt, rs, qb, ql, ks, vs,
                                      interpret=interpret, window=window,
                                      coalesce=coalesce, layer=l)

    fn = shard_map(
        run,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=_RULES.spec("tokens", "heads"),
        check_vma=False,
    )
    return fn(*args)


def lm_head_topk_tp(
    mesh: Mesh,
    h: jax.Array,  # [N, D] hidden states — replicated
    head,  # vocab-sharded head operand: lm_head [D, V] (vocab over tp)
    #        or the tied [V, D] embed table (vocab rows over tp); either
    #        may be the quantized {"_q8", "_scale"} dict
    token_counts: jax.Array,  # [N, V] — vocab axis sharded over tp
    output_counts: jax.Array,
    presence: jax.Array,  # [N] replicated
    frequency: jax.Array,
    repetition: jax.Array,
    early: jax.Array,  # [N] bool replicated
    suppress: jax.Array,  # [N, V] — vocab axis sharded over tp
    *,
    tied: bool,
    k: int | None = None,
    block_v: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Vocab-parallel fused lm_head→top-k → replicated ``(vals [N, k],
    idx [N, k])``.  Each shard runs :func:`ops.lm_head_topk.lm_head_topk`
    over its local vocab columns, rebases its candidate ids to global,
    and the shards merge with a collective top-k: the all_gather
    concatenates candidate lists in shard order — lower vocab indices
    first, preserving the lower-index tie contract — so the merged set
    is bit-identical to the single-device candidates (selection under a
    strict total order is merge-tree independent)."""
    from fusioninfer_tpu.ops.lm_head_topk import (
        LM_HEAD_BLOCK_V,
        LM_HEAD_TOPK,
        lm_head_topk,
    )

    k = LM_HEAD_TOPK if k is None else k
    block_v = LM_HEAD_BLOCK_V if block_v is None else block_v
    row = _RULES.spec("rows")
    hidden_spec = _RULES.spec("rows", "embed")  # replicated (embed unsharded)
    vocab_cols = _RULES.spec("rows", "vocab")  # [N, V] vocab over tp
    w_axes = ("vocab", "embed") if tied else ("embed", "vocab")
    s_axes = ("vocab", None) if tied else (None, "vocab")
    if isinstance(head, dict):
        head_spec = {"_q8": _RULES.spec(*w_axes), "_scale": _RULES.spec(*s_axes)}
    else:
        head_spec = _RULES.spec(*w_axes)
    tp = mesh.shape["tp"]

    def run(h, head, tc, oc, pres, freq, rep, early, sup):
        vals, idx = lm_head_topk(h, head, tc, oc, pres, freq, rep, early,
                                 sup, tied=tied, k=k, block_v=block_v)
        idx = idx + jax.lax.axis_index("tp") * tc.shape[1]
        allv = jax.lax.all_gather(vals, "tp")  # [tp, N, k] shard order
        alli = jax.lax.all_gather(idx, "tp")
        n = vals.shape[0]
        mv = jnp.moveaxis(allv, 0, 1).reshape(n, tp * vals.shape[1])
        mi = jnp.moveaxis(alli, 0, 1).reshape(n, tp * vals.shape[1])
        sv, si = jax.lax.top_k(mv, min(k, mv.shape[1]))
        return sv, jnp.take_along_axis(mi, si, axis=1)

    fn = shard_map(
        run,
        mesh=mesh,
        in_specs=(hidden_spec, head_spec, vocab_cols, vocab_cols, row,
                  row, row, row, vocab_cols),
        out_specs=(_RULES.spec("rows", None), _RULES.spec("rows", None)),
        check_vma=False,
    )
    return fn(h, head, token_counts, output_counts, presence, frequency,
              repetition, early, suppress)
