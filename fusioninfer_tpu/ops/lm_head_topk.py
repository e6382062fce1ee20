"""Fused lm_head → running top-k: sampling without the [rows, V] tensor.

The serving decode step projects each row's hidden state through the
lm_head and immediately reduces the result to one sampled token — yet
the unfused path materializes the full ``[rows, V]`` logits tensor in
HBM between the matmul and the sampler (~150k f32 columns per row for
the Qwen3 family, written and re-read every step).  :func:`lm_head_topk`
streams the head matrix in vocab blocks instead: each block's
``[rows, block_v]`` logits get the exact penalty / min-tokens algebra
applied in place and fold into a running top-k candidate set, so the
widest tensor alive is one block.  Greedy and top-k sampled rows then
draw from the candidates (:func:`engine.sampler.sample_topk`); rows
needing the full distribution — logprobs, guided masks, logit_bias,
min_p — take the unfused path explicitly.

The running top-k is exact, not approximate: ``lax.top_k`` breaks value
ties toward the lower index, so the running merge (carry candidates
first, block candidates after, both idx-ascending within equal values)
selects exactly the k best under the strict total order (value desc,
vocab index asc), the same set and order ``lax.top_k`` returns over the
blocks' penalized logits laid side by side.  Against the unfused path's
ONE ``[D, V]`` matmul each block element is the same contraction, but a
backend may sum it in another order at another width: jax 0.9's CPU
backend differs by one float32 ulp of the summed terms at 128- and
256-column blocks (33 of 3 885 elements) and by none at 250 or 4096, the
serving width.  Both paths then share ONE candidate sampler
(``tests/test_flash_decode.py::TestLmHeadTopk`` pins all three).

The TP variant (:func:`fusioninfer_tpu.ops.sharded.lm_head_topk_tp`)
runs this per vocab shard and merges candidates with a collective
top-k: shard-local indices rebase to global, an all_gather concatenates
shard candidate lists in shard order (lower vocab first, preserving the
tie contract), and one more ``top_k`` reduces — no shard ever holds
more than its local vocab columns.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from fusioninfer_tpu.models.quantization import dequantize, is_quantized

# candidate-set width: the cap on `top_k` a request may ask for and
# still ride the fused path (engine eligibility).  64 covers every
# OpenAI-style serving default with room; the candidate tensors are
# [rows, 64] — noise next to one vocab block.
LM_HEAD_TOPK = 64

# vocab block width: ~[rows, 4096] f32 per block live at once.  Must be
# >= LM_HEAD_TOPK so the first block can seed the full candidate set.
LM_HEAD_BLOCK_V = 4096


def head_vocab_size(head, tied: bool) -> int:
    """Vocab width of a (possibly quantized) lm_head operand."""
    w = head["_q8"] if is_quantized(head) else head
    return w.shape[0] if tied else w.shape[-1]


def _head_block(head, tied: bool, lo: int, hi: int, dtype) -> jax.Array:
    """Columns [lo, hi) of the [D, V] head matrix, slice-then-dequantize
    so a quantized head never materializes its full dequantized form —
    elementwise dequant commutes with slicing, so block values are
    bit-identical to slicing the full dequantized matrix."""
    if tied:
        # [V, D] embedding table rows, transposed on use (tied weights)
        blk = (jax.tree.map(lambda a: a[lo:hi], head)
               if is_quantized(head) else head[lo:hi])
        if is_quantized(blk):
            blk = dequantize(blk, dtype)
        return blk.T
    blk = (jax.tree.map(lambda a: a[..., lo:hi], head)
           if is_quantized(head) else head[:, lo:hi])
    if is_quantized(blk):
        blk = dequantize(blk, dtype)
    return blk


@functools.partial(jax.jit, static_argnames=("tied", "k", "block_v"))
def lm_head_topk(
    h: jax.Array,  # [N, D] — selected hidden states (model dtype)
    head,  # lm_head weight [D, V], or the [V, D] embed table when tied;
    #        either may be the quantized {"_q8", "_scale"} dict
    token_counts: jax.Array,  # [N, V] int32 — penalty counts (prompt+out)
    output_counts: jax.Array,  # [N, V] int32 — penalty counts (out only)
    presence: jax.Array,  # [N] f32
    frequency: jax.Array,  # [N] f32
    repetition: jax.Array,  # [N] f32, 1.0 = off
    early: jax.Array,  # [N] bool — min_tokens still unmet
    suppress: jax.Array,  # [N, V] bool — stop-id suppression rows
    *,
    tied: bool,
    k: int = LM_HEAD_TOPK,
    block_v: int = LM_HEAD_BLOCK_V,
) -> tuple[jax.Array, jax.Array]:
    """Top-k penalized logits per row → ``(vals [N, k], idx [N, k])``,
    value-descending with ties vocab-index-ascending, never holding
    more than one ``[N, block_v]`` logits block.

    The per-block algebra is the unfused chain verbatim —
    ``sampler.apply_penalties`` then ``engine._suppress_early_rows`` —
    restricted to the block's columns (both are elementwise over vocab,
    so restriction is exact).  ``vals`` are penalized UNSCALED logits:
    temperature belongs to :func:`engine.sampler.sample_topk`, exactly
    where the unfused ``sample`` applies it.
    """
    V = head_vocab_size(head, tied)
    k = min(k, V)
    rep = repetition[:, None]
    vals = idx = None
    for i in range(-(-V // block_v)):
        lo, hi = i * block_v, min(V, (i + 1) * block_v)
        wb = _head_block(head, tied, lo, hi, h.dtype)
        lb = (h @ wb).astype(jnp.float32)  # [N, hi-lo]
        tc = token_counts[:, lo:hi]
        oc = output_counts[:, lo:hi]
        seen = tc > 0
        lb = jnp.where(seen, jnp.where(lb > 0, lb / rep, lb * rep), lb)
        lb = lb - presence[:, None] * (oc > 0)
        lb = lb - frequency[:, None] * oc
        lb = jnp.where(early[:, None] & suppress[:, lo:hi], -jnp.inf, lb)
        bv, bi = jax.lax.top_k(lb, min(k, hi - lo))
        bi = bi + lo
        if vals is None:
            # seed from the first block (never from a -inf carry: with
            # fewer than k finite logits the -inf ties must still
            # resolve to the LOWEST vocab indices, like full top_k)
            vals, idx = bv, bi
        else:
            # the candidate set grows toward k while block widths are
            # below it (block_v < k only in tests/tiny vocabs)
            mv = jnp.concatenate([vals, bv], axis=1)
            sv, si = jax.lax.top_k(mv, min(k, mv.shape[1]))
            vals = sv
            idx = jnp.take_along_axis(
                jnp.concatenate([idx, bi], axis=1), si, axis=1)
    return vals, idx
