"""Ring attention: sequence-parallel exact attention over the ``sp`` axis.

Long-context capability the reference leaves entirely to the engine
(SURVEY §5 "Long-context / sequence parallelism: not an operator
concern") — here it is first-class: the sequence axis is sharded over the
mesh, each device holds a Q/K/V chunk, and K/V chunks rotate around the
ring via ``lax.ppermute`` while a blockwise online softmax accumulates
exact attention. Peak memory per device is O(S/sp · S/sp) for scores
instead of O(S²), and the ppermute rides ICI neighbour links.

Causality is handled per (q-chunk, k-chunk) pair with global positions,
so the result is bit-comparable (up to fp reassociation) with dense
causal attention on one device — asserted in tests/test_ring.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh

from fusioninfer_tpu.parallel.axes import default_rules

NEG_INF = -1e30


def _chunk_attend(q, k, v, q_pos, k_pos, causal):
    """Scores for one (q-chunk, k-chunk) pair with running-softmax stats.

    q: [B, Sq, H, Hd]; k/v: [B, Sk, KV, Hd] → (m, l, o) partials where
    m/l: [B, KV, G, Sq], o: [B, Sq, H, Hd]-shaped accumulator pieces.
    """
    B, Sq, H, Hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, Hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(Hd).astype(jnp.float32)
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]  # [Sq, Sk]
        scores = jnp.where(mask[None, None, None, :, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)  # [B, KV, G, Sq]
    # Guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1.
    safe_m = jnp.maximum(m, NEG_INF / 2)
    p = jnp.exp(scores - safe_m[..., None])  # [B, KV, G, Sq, Sk]
    l = jnp.sum(p, axis=-1)  # [B, KV, G, Sq]
    o = jnp.einsum("bkgst,btkd->bkgsd", p, v.astype(jnp.float32))
    return m, l, o


def _merge(acc, new):
    """Combine two blockwise-softmax partials (the flash-attention merge)."""
    m_a, l_a, o_a = acc
    m_n, l_n, o_n = new
    m = jnp.maximum(m_a, m_n)
    safe_m = jnp.maximum(m, NEG_INF / 2)
    a = jnp.exp(m_a - safe_m)
    b = jnp.exp(m_n - safe_m)
    return m, l_a * a + l_n * b, o_a * a[..., None] + o_n * b[..., None]


def ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
) -> jax.Array:
    """Per-shard body: runs INSIDE shard_map over ``axis_name``.

    q: [B, S_local, H, Hd], k/v: [B, S_local, KV, Hd] — the local sequence
    chunk of each device. Returns local attention output [B, S_local, H·Hd].
    """
    B, S, H, Hd = q.shape
    KV = k.shape[2]
    G = H // KV
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)

    q_pos = me * S + jnp.arange(S)

    m0 = jnp.full((B, KV, G, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, S), jnp.float32)
    o0 = jnp.zeros((B, KV, G, S, Hd), jnp.float32)

    perm = [(j, (j + 1) % n) for j in range(n)]

    # Chunk 0 is the local K/V — attend before any communication, then
    # rotate at the top of each remaining step: n chunks, n-1 exchanges.
    acc0 = _merge((m0, l0, o0), _chunk_attend(q, k, v, q_pos, q_pos, causal))

    def body(i, carry):
        acc, kv_blk = carry
        kv_blk = jax.tree.map(lambda x: lax.ppermute(x, axis_name, perm), kv_blk)
        k_blk, v_blk = kv_blk
        # Block i arrived from device (me - i); its chunk owns positions
        # [(me - i) % n * S, ...).
        src = (me - i) % n
        k_pos = src * S + jnp.arange(S)
        new = _chunk_attend(q, k_blk, v_blk, q_pos, k_pos, causal)
        acc = _merge(acc, new)
        return acc, kv_blk

    (m, l, o), _ = lax.fori_loop(1, n, body, (acc0, (k, v)))
    l = jnp.maximum(l, 1e-20)
    out = o / l[..., None]  # [B, KV, G, S, Hd]
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, S, H * Hd)
    return out.astype(q.dtype)


def make_ring_attention(
    mesh: Mesh,
    axis_name: str = "sp",
    causal: bool = True,
):
    """shard_map-wrapped ring attention over the mesh's sequence axis.

    Takes globally-shaped q [B, S, H, Hd], k/v [B, S, KV, Hd] whose S axis
    is sharded over ``axis_name`` (batch over dp); returns [B, S, H·Hd]
    sharded the same way.  Specs derive from the logical-axis table with
    the ``length`` axis remapped onto ``axis_name`` — the head axes stay
    replicated here (each device owns EVERY head for its sequence chunk;
    the ring rotates K/V chunks, not heads).
    """
    rules = default_rules().with_overrides(length=axis_name)
    qkv_spec = rules.spec("batch", "length", None, None)
    out_spec = rules.spec("batch", "length", None)
    fn = shard_map(
        partial(ring_attention_local, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec),
        out_specs=out_spec,
        check_vma=False,
    )
    return jax.jit(fn)


def dense_reference(q, k, v, causal: bool = True) -> jax.Array:
    """Single-device exact attention with identical GQA semantics — the
    correctness oracle for the ring path."""
    B, S, H, Hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, Hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) / jnp.sqrt(Hd)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H * Hd).astype(q.dtype)
