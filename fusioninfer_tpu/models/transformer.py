"""Functional decoder-only transformer.

Pure-pytree params + pure functions (no module framework): everything is
trivially jittable, shardable with ``NamedSharding``, and scannable.
Layer weights are stacked on a leading ``n_layers`` axis and consumed with
``lax.scan`` — one compiled layer body regardless of depth, the
XLA-friendly shape for 80-layer models.

Attention variants consumed here live in :mod:`fusioninfer_tpu.ops`;
the KV-cache-aware serving paths (paged prefill/decode) live in
:mod:`fusioninfer_tpu.engine.model_runner`.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from fusioninfer_tpu.models.config import ModelConfig
from fusioninfer_tpu.models.quantization import embed_lookup, maybe_dequantize_tree

Params = dict[str, Any]


# -- building blocks ---------------------------------------------------------


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    orig_dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * lax.rsqrt(var + eps)
    return (x * weight.astype(jnp.float32)).astype(orig_dtype)


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding, NeoX half-rotation layout.

    x: [..., seq, heads, head_dim]; positions: [..., seq]
    """
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)  # [head_dim/2]
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # [..., seq, hd/2]
    cos = jnp.cos(angles)[..., None, :]  # [..., seq, 1, hd/2]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array) -> jax.Array:
    gate = jax.nn.silu(x @ w_gate)
    return (gate * (x @ w_up)) @ w_down


# dense MoE computes every expert on every token: exact, but its FLOPs
# scale with E — past this expert count the capacity-dispatch path wins
DENSE_MOE_MAX_EXPERTS = 16


def moe_ffn(x: jax.Array, router_w: jax.Array, w_gate: jax.Array, w_up: jax.Array,
            w_down: jax.Array, n_active: int) -> jax.Array:
    """Token-choice top-k mixture of experts, dense-compute formulation.

    Every expert runs on every token and results are combined with the
    (renormalized) top-k router weights.  Exact and static-shaped — the
    right choice at small expert counts (≤ ``DENSE_MOE_MAX_EXPERTS``,
    e.g. the tiny test presets); large-E models like qwen3-30b-a3b
    route through :func:`moe_ffn_sparse`, whose FLOPs track the ACTIVE
    experts.  The expert axis is shardable over the mesh's ``ep`` axis
    either way.

    x: [tokens, d_model]; router_w: [d_model, E];
    w_gate/w_up: [E, d_model, d_ff]; w_down: [E, d_ff, d_model]
    """
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))  # [T, E]
    top_vals, _ = lax.top_k(logits, n_active)
    threshold = top_vals[..., -1:]
    mask = logits >= threshold
    weights = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)  # [T, E]
    # einsum over experts: dense but static-shaped
    gate = jax.nn.silu(jnp.einsum("td,edf->tef", x, w_gate))
    up = jnp.einsum("td,edf->tef", x, w_up)
    per_expert = jnp.einsum("tef,efd->ted", gate * up, w_down)  # [T, E, D]
    return jnp.einsum("ted,te->td", per_expert, weights.astype(x.dtype))


def moe_capacity(n_tokens: int, n_active: int, n_experts: int,
                 capacity_factor: float = 2.0) -> int:
    """Static per-expert token capacity (Switch/GShard): expected load
    ``T·k/E`` times a slack factor, floored at 4 so tiny decode batches
    never drop."""
    import math

    return max(4, int(math.ceil(n_tokens * n_active / n_experts * capacity_factor)))


def moe_ffn_sparse(x: jax.Array, router_w: jax.Array, w_gate: jax.Array,
                   w_up: jax.Array, w_down: jax.Array, n_active: int,
                   capacity_factor: float = 2.0) -> jax.Array:
    """Capacity-based sparse MoE (the Switch/GShard dispatch, XLA-style).

    FLOPs scale with the ACTIVE experts, not E: each token's top-k
    assignments scatter into a static ``[E, C, D]`` dispatch buffer
    (``C`` = :func:`moe_capacity`), every expert runs one batched matmul
    over its buffer, and results gather back weighted by the renormalized
    router scores.  All shapes are static — capacity overflow *drops*
    that (token, expert) assignment, the standard trade the slack factor
    makes rare.  The leading expert axis of both the buffer and the
    weights shards over ``ep``.

    x: [tokens, d_model] → [tokens, d_model]
    """
    T, D = x.shape
    E = router_w.shape[-1]
    k = n_active
    C = moe_capacity(T, k, E, capacity_factor)

    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))  # [T, E]
    top_vals, top_idx = lax.top_k(logits, k)  # [T, k]
    weights = jax.nn.softmax(top_vals, axis=-1)  # renormalized over chosen

    flat_e = top_idx.reshape(-1)  # [T*k] expert id per assignment
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # [T*k, E]
    # slot of each assignment within its expert's buffer: how many prior
    # assignments chose the same expert
    prior = jnp.cumsum(onehot, axis=0) - onehot
    slot = jnp.take_along_axis(prior, flat_e[:, None], axis=1)[:, 0]  # [T*k]
    keep = slot < C
    slot = jnp.where(keep, slot, 0)  # clamped; masked contributions add zero

    x_rep = jnp.repeat(x, k, axis=0)  # [T*k, D]
    contrib = x_rep * keep[:, None].astype(x.dtype)
    dispatch = jnp.zeros((E, C, D), x.dtype).at[flat_e, slot].add(contrib)

    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", dispatch, w_gate))
    up = jnp.einsum("ecd,edf->ecf", dispatch, w_up)
    out_e = jnp.einsum("ecf,efd->ecd", gate * up, w_down)  # [E, C, D]

    gathered = out_e[flat_e, slot]  # [T*k, D]
    w_flat = (weights.reshape(-1) * keep).astype(x.dtype)
    return (gathered * w_flat[:, None]).reshape(T, k, D).sum(axis=1)


# -- parameter init ----------------------------------------------------------


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random-init parameters, layer weights stacked on axis 0."""
    cfg.validate()
    dtype = cfg.jax_dtype
    L, D, H, KV, Hd, F = (
        cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff,
    )
    keys = jax.random.split(key, 12)

    def dense(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(dtype)

    layers: Params = {
        "attn_norm": jnp.ones((L, D), dtype),
        "wq": dense(keys[0], (L, D, H * Hd), D),
        "wk": dense(keys[1], (L, D, KV * Hd), D),
        "wv": dense(keys[2], (L, D, KV * Hd), D),
        "wo": dense(keys[3], (L, H * Hd, D), H * Hd),
        "mlp_norm": jnp.ones((L, D), dtype),
    }
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, Hd), dtype)
        layers["k_norm"] = jnp.ones((L, Hd), dtype)
    if cfg.is_moe:
        E, EF = cfg.n_experts, cfg.expert_d_ff
        layers["router"] = dense(keys[4], (L, D, E), D).astype(jnp.float32)
        layers["w_gate"] = dense(keys[5], (L, E, D, EF), D)
        layers["w_up"] = dense(keys[6], (L, E, D, EF), D)
        layers["w_down"] = dense(keys[7], (L, E, EF, D), EF)
    else:
        layers["w_gate"] = dense(keys[5], (L, D, F), D)
        layers["w_up"] = dense(keys[6], (L, D, F), D)
        layers["w_down"] = dense(keys[7], (L, F, D), F)

    params: Params = {
        "embed": dense(keys[8], (cfg.vocab_size, D), D),
        "layers": layers,
        "final_norm": jnp.ones((D,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(keys[9], (D, cfg.vocab_size), D)
    return params


# -- forward -----------------------------------------------------------------


def _attention(q, k, v, mask):
    """Plain batched attention: q [B,S,H,Hd], k/v [B,T,KV,Hd], mask [B,1,S,T]."""
    B, S, H, Hd = q.shape
    KV = k.shape[2]
    group = H // KV
    q = q.reshape(B, S, KV, group, Hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) / jnp.sqrt(Hd)
    scores = jnp.where(mask[:, :, None, :, :] if mask.ndim == 4 else mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, H * Hd)


@jax.named_scope("attn_qkv")
def qkv_proj(
    cfg: ModelConfig, layer: Params, x: jax.Array, positions: jax.Array,
    lora: Params = None, adapter_ids: jax.Array = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pre-norm + QKV projection + (optional) QK-norm + RoPE — shared by
    every execution path (full forward, paged prefill/suffix, decode) so
    model features can never drift between them.

    x: [B, S, D] → q [B, S, H, Hd], k/v [B, S, KV, Hd].
    ``lora``: this layer's stacked adapter slice (``[N, d_in, r]`` per
    projection) + per-row ``adapter_ids`` — batched multi-LoRA deltas on
    the same normalized input the base matmuls consume.
    """
    B, S, _ = x.shape
    H, KV, Hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    # invariant: callers (layer_forward / the model_runner scan bodies)
    # maybe_dequantize_tree the layer once at block entry
    h = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
    q, k, v = h @ layer["wq"], h @ layer["wk"], h @ layer["wv"]
    if lora is not None:
        from fusioninfer_tpu.models.lora import lora_delta

        q = q + lora_delta(lora, "wq", h, adapter_ids)
        k = k + lora_delta(lora, "wk", h, adapter_ids)
        v = v + lora_delta(lora, "wv", h, adapter_ids)
    q = q.reshape(B, S, H, Hd)
    k = k.reshape(B, S, KV, Hd)
    v = v.reshape(B, S, KV, Hd)
    if cfg.qk_norm:
        q = rms_norm(q, layer["q_norm"], cfg.rms_eps)
        k = rms_norm(k, layer["k_norm"], cfg.rms_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


@jax.named_scope("mlp")
def mlp_block(cfg: ModelConfig, layer: Params, x: jax.Array) -> jax.Array:
    """Pre-norm + FFN (dense SwiGLU or MoE), shared by every path.

    x: [B, S, D] → [B, S, D] (residual NOT added).  Callers dequantize
    the layer tree once at block entry (see qkv_proj invariant)."""
    B, S, D = x.shape
    h = rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
    if cfg.is_moe:
        ffn = moe_ffn if cfg.n_experts <= DENSE_MOE_MAX_EXPERTS else moe_ffn_sparse
        return ffn(
            h.reshape(B * S, D), layer["router"], layer["w_gate"], layer["w_up"],
            layer["w_down"], cfg.n_experts_active,
        ).reshape(B, S, D)
    return swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"])


@jax.named_scope("attn_out")
def attn_out_proj(layer: Params, attn: jax.Array, lora: Params = None,
                  adapter_ids: Optional[jax.Array] = None) -> jax.Array:
    """Attention output projection (+ its LoRA delta), shared by every
    path; the residual is NOT added."""
    out = attn @ layer["wo"]
    if lora is not None:
        from fusioninfer_tpu.models.lora import lora_delta

        out = out + lora_delta(lora, "wo", attn, adapter_ids)
    return out


def layer_forward(
    cfg: ModelConfig,
    layer: Params,
    x: jax.Array,
    positions: jax.Array,
    mask: Optional[jax.Array] = None,
    kv: Optional[tuple[jax.Array, jax.Array]] = None,
    mesh=None,
    lora: Params = None,
    adapter_ids: Optional[jax.Array] = None,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One transformer block. Returns (output, (k, v)) for cache management.

    x: [B, S, D]; positions: [B, S]; mask broadcastable to [B, 1, S, T].
    ``kv=None`` means fresh causal self-attention — the mask is derived
    internally (``mask`` must be None; the flash-kernel path is causal by
    construction and cannot honor an arbitrary caller mask).  When ``kv``
    is given, attends over provided (k, v) history that already includes
    this block's fresh keys, under the required ``mask``.  ``mesh``: a
    tp-only serving mesh — runs the flash kernel per tensor-parallel
    shard via shard_map.
    """
    B, S, D = x.shape

    layer = maybe_dequantize_tree(layer, cfg.jax_dtype)
    q, k, v = qkv_proj(cfg, layer, x, positions, lora, adapter_ids)

    with jax.named_scope("attn"):
        if kv is None:
            if mask is not None:
                raise ValueError(
                    "layer_forward(kv=None) is causal self-attention; it derives "
                    "its own mask — pass kv=(k, v) history to use a custom mask"
                )
            from fusioninfer_tpu.ops import dispatch, flash_attention

            if dispatch.resolve_attn(cfg.attn_impl) == "flash" and dispatch.flash_seq_ok(S):
                # fresh K/V over the full (causal) sequence: Pallas flash path
                if mesh is not None:
                    from fusioninfer_tpu.ops.sharded import flash_attention_tp

                    attn = flash_attention_tp(
                        mesh, q, k, v, causal=True,
                        interpret=dispatch.kernel_interpret(),
                        window=cfg.sliding_window,
                    )
                else:
                    attn = flash_attention(
                        q, k, v, causal=True, interpret=dispatch.kernel_interpret(),
                        window=cfg.sliding_window,
                    )
            else:
                attn = _attention(q, k, v,
                                  causal_mask(S, window=cfg.sliding_window))
        else:
            if mask is None:
                raise ValueError("layer_forward with kv history requires a mask")
            attn_k, attn_v = kv
            attn = _attention(q, attn_k, attn_v, mask)
    x = x + attn_out_proj(layer, attn, lora, adapter_ids)
    return x + mlp_block(cfg, layer, x), (k, v)


def causal_mask(S: int, dtype=jnp.bool_, window: int | None = None) -> jax.Array:
    """Causal [1, 1, S, S] mask; ``window`` bands it Mistral-style (each
    query sees the previous ``window`` positions, itself included)."""
    from fusioninfer_tpu.ops.masks import attend

    m = attend(jnp.arange(S)[:, None], jnp.arange(S)[None, :], window)
    return m.astype(dtype)[None, None, :, :]


def lm_head_operands(cfg: ModelConfig, params: Params):
    """``(head, tied)``: the raw (possibly quantized) lm_head operand —
    the ``[D, V]`` projection, or the ``[V, D]`` embedding table when
    weights are tied (transposed on use).  The ONE head-resolution rule,
    shared by :func:`lm_head` and the blocked fused-sampling projection
    (:mod:`fusioninfer_tpu.ops.lm_head_topk`) so the two paths can never
    read different weights."""
    head = params.get("lm_head")
    if head is not None:
        return head, False
    return params["embed"], True


@jax.named_scope("lm_head")
def lm_head(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    """Project hidden states to fp32 logits; tied embeddings fall back to
    the transposed embedding table."""
    from fusioninfer_tpu.models.quantization import dequantize, is_quantized

    head, tied = lm_head_operands(cfg, params)
    if is_quantized(head):
        head = dequantize(head, cfg.jax_dtype)
    if tied:
        head = head.T
    return (x @ head).astype(jnp.float32)


def hidden_states(cfg: ModelConfig, params: Params,
                  tokens: jax.Array) -> jax.Array:
    """Full-sequence causal trunk → final hidden states [B, S, D] —
    the ONE definition of the no-cache forward pass, shared by
    :func:`forward` (logits) and :func:`embed_sequences` (pooling) so
    /v1/embeddings can never drift from generation semantics."""
    B, S = tokens.shape
    x = embed_lookup(params["embed"], tokens, cfg.jax_dtype)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))

    def body(x, layer):
        out, _ = layer_forward(cfg, layer, x, positions)
        return out, None

    x, _ = lax.scan(body, x, params["layers"])
    return rms_norm(x, params["final_norm"], cfg.rms_eps)


@partial(jax.jit, static_argnums=0)
def forward(cfg: ModelConfig, params: Params, tokens: jax.Array) -> jax.Array:
    """Full-sequence causal forward → logits [B, S, V].

    The training / compile-check path: no KV cache, scan over stacked
    layer weights.
    """
    return lm_head(cfg, params, hidden_states(cfg, params, tokens))


@partial(jax.jit, static_argnums=0)
def embed_sequences(cfg: ModelConfig, params: Params, tokens: jax.Array,
                    true_lens: jax.Array) -> jax.Array:
    """Sequence embeddings for /v1/embeddings → L2-normalized [B, D].

    Last-REAL-token pooling of the final hidden states (the decoder-only
    convention: the last position has attended the whole sequence), fp32
    normalized so cosine similarity is a dot product."""
    B = tokens.shape[0]
    x = hidden_states(cfg, params, tokens)
    last = x[jnp.arange(B), jnp.maximum(true_lens - 1, 0)].astype(jnp.float32)
    norm = jnp.linalg.norm(last, axis=-1, keepdims=True)
    return last / jnp.maximum(norm, 1e-12)


def loss_fn(cfg: ModelConfig, params: Params, tokens: jax.Array) -> jax.Array:
    """Next-token cross-entropy over the sequence (training step target)."""
    logits = forward(cfg, params, tokens)  # [B, S, V]
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1).squeeze(-1)
    return jnp.mean(nll)
