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

import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from fusioninfer_tpu.models.config import LayerKind, ModelConfig
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


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature term: 0.1 * mscale * ln(factor) + 1."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_correction_range(beta_fast: float, beta_slow: float, dim: int,
                          theta: float, original_max: int) -> tuple[int, int]:
    """The rotary pairs between which YaRN blends interpolated and
    original frequencies (``find_correction_range``)."""

    def correction_dim(n_rot: float) -> float:
        return (dim * math.log(original_max / (n_rot * 2 * math.pi))
                / (2 * math.log(theta)))

    return (max(math.floor(correction_dim(beta_fast)), 0),
            min(math.ceil(correction_dim(beta_slow)), dim - 1))


def yarn_frequencies(dim: int, theta: float, yarn: tuple) -> jax.Array:
    """Inverse frequencies [dim/2] of a YaRN-scaled rotary embedding:
    ``theta^(-2j/dim)`` blended with the same / factor by the linear ramp
    between the correction range's ends."""
    factor, original_max, beta_fast, beta_slow = yarn[:4]
    low, high = yarn_correction_range(beta_fast, beta_slow, dim, theta,
                                      original_max)
    extra = rope_frequencies(dim, theta)
    span = (high - low) if high != low else 0.001
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / span, 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def apply_rope_pairs(x: jax.Array, positions: jax.Array,
                     freqs: jax.Array, scale: float = 1.0) -> jax.Array:
    """Rotary embedding over interleaved pairs ``(2i, 2i+1)``, the
    rotated values written half by half (evens, then odds) as
    DeepSeek-V2's modeling code leaves them; ``scale`` multiplies cos
    and sin.  x: [..., seq, heads, dim]; positions: [..., seq]."""
    angles = positions[..., :, None].astype(jnp.float32) * freqs
    cos = (jnp.cos(angles) * scale)[..., None, :]
    sin = (jnp.sin(angles) * scale)[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- multi-head latent attention (DeepSeek-V2) -------------------------------


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """``(nope + rope)^-1/2`` times YaRN's ``mscale(factor, all_dim)^2``."""
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    if cfg.rope_yarn is not None:
        scale *= yarn_mscale(cfg.rope_yarn[0], cfg.rope_yarn[5]) ** 2
    return scale


def _mla_rope(cfg: ModelConfig, x: jax.Array, positions: jax.Array) -> jax.Array:
    if cfg.rope_yarn is None:
        return apply_rope_pairs(
            x, positions, rope_frequencies(cfg.qk_rope_dim, cfg.rope_theta))
    factor, mscale, all_dim = (cfg.rope_yarn[0], cfg.rope_yarn[4],
                               cfg.rope_yarn[5])
    return apply_rope_pairs(
        x, positions,
        yarn_frequencies(cfg.qk_rope_dim, cfg.rope_theta, cfg.rope_yarn),
        yarn_mscale(factor, mscale) / yarn_mscale(factor, all_dim))


def _scaled_gain(gain: jax.Array, scale: float) -> jax.Array:
    """A norm's gain times a Python ``scale``, in float32, so the scaled
    result is rounded once; the gain itself where the scale is 1 (no
    operation is traced for it)."""
    return gain if scale == 1.0 else gain.astype(jnp.float32) * scale


@jax.named_scope("mla_q")
def mla_queries(cfg: ModelConfig, layer: Params, h: jax.Array,
                positions: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Normed input ``h`` [B, S, D] → per-head queries: the no-position
    part [B, S, H, nope] and the rotated rope part [B, S, H, rope].
    ``cfg.mla_q_scale`` multiplies the compressed query (and so, the
    up-projection being linear, every query)."""
    B, S, _ = h.shape
    c_q = rms_norm(h @ layer["wq_a"],
                   _scaled_gain(layer["q_a_norm"], cfg.mla_q_scale),
                   cfg.rms_eps)
    q = (c_q @ layer["wq_b"]).reshape(
        B, S, cfg.n_heads, cfg.qk_nope_dim + cfg.qk_rope_dim)
    return (q[..., :cfg.qk_nope_dim],
            _mla_rope(cfg, q[..., cfg.qk_nope_dim:], positions))


@jax.named_scope("mla_kv")
def mla_latent(cfg: ModelConfig, layer: Params, h: jax.Array,
               positions: jax.Array) -> jax.Array:
    """Normed input ``h`` [B, S, D] → the row a position caches
    [B, S, kv_lora_rank + rope]: the compressed KV after its norm (times
    ``cfg.mla_kv_scale``: the SCALED row is cached, so the up-projections
    and the absorbed form stay as they are), then the one rope key every
    head shares, after rotation."""
    r = cfg.kv_lora_rank
    ckv = h @ layer["wkv_a"]
    c = rms_norm(ckv[..., :r],
                 _scaled_gain(layer["kv_a_norm"], cfg.mla_kv_scale),
                 cfg.rms_eps)
    k_rope = _mla_rope(cfg, ckv[..., None, r:], positions)[..., 0, :]
    return jnp.concatenate([c, k_rope], axis=-1)


def _wkv_b_heads(cfg: ModelConfig, layer: Params) -> jax.Array:
    return layer["wkv_b"].reshape(
        cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_dim + cfg.v_head_dim)


@jax.named_scope("mla_q")
def mla_absorb_queries(cfg: ModelConfig, layer: Params, q_nope: jax.Array,
                       q_rope: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The absorbed form's queries against a latent row, softmax scale
    folded in before the one rounding: ``q_nope W_UK^T`` [..., H, rank]
    and the rope part [..., H, rope]."""
    scale = mla_softmax_scale(cfg)
    w_uk = _wkv_b_heads(cfg, layer)[..., :cfg.qk_nope_dim]  # [r, H, nope]
    q_lat = jnp.einsum("...hn,rhn->...hr", q_nope, w_uk,
                       preferred_element_type=jnp.float32)
    return ((q_lat * scale).astype(q_nope.dtype),
            (q_rope.astype(jnp.float32) * scale).astype(q_rope.dtype))


@jax.named_scope("mla_out")
def mla_attn_out(cfg: ModelConfig, layer: Params, o_lat: jax.Array) -> jax.Array:
    """Attention-weighted latent rows [..., H, rank] → per-head values
    through ``W_UV`` → the output projection [..., D] (residual NOT
    added)."""
    w_uv = _wkv_b_heads(cfg, layer)[..., cfg.qk_nope_dim:]  # [r, H, v]
    o = jnp.einsum("...hr,rhv->...hv", o_lat, w_uv)
    return o.reshape(*o.shape[:-2], cfg.attn_out_dim) @ layer["wo"]


def mla_expand_kv(cfg: ModelConfig, layer: Params,
                  latent: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Latent rows [B, S, rank + rope] → the published (expanded) keys
    [B, S, H, nope + rope] and values [B, S, H, v]."""
    B, S, _ = latent.shape
    r, H = cfg.kv_lora_rank, cfg.n_heads
    kv = (latent[..., :r] @ layer["wkv_b"]).reshape(
        B, S, H, cfg.qk_nope_dim + cfg.v_head_dim)
    k_rope = jnp.broadcast_to(latent[..., None, r:],
                              (B, S, H, cfg.qk_rope_dim))
    k = jnp.concatenate([kv[..., :cfg.qk_nope_dim], k_rope], axis=-1)
    return k, kv[..., cfg.qk_nope_dim:]


def _mla_fresh_attention(cfg: ModelConfig, q: jax.Array, k: jax.Array,
                         v: jax.Array) -> jax.Array:
    """Causal attention of a whole fresh sequence in the expanded form →
    [B, S, H * v].  The flash kernel wants one head width, so keys and
    queries are zero-padded to it (the products are unchanged) and the
    values' padding is cut from the output."""
    from fusioninfer_tpu.ops import dispatch, flash_attention

    B, S, H, Dk = q.shape
    Dv = v.shape[-1]
    scale = mla_softmax_scale(cfg)
    if dispatch.resolve_attn(cfg.attn_impl) == "flash" and dispatch.flash_seq_ok(S):
        width = -(-Dk // 128) * 128

        def pad(x):
            return jnp.pad(x, ((0, 0),) * 3 + ((0, width - x.shape[-1]),))

        out = flash_attention(pad(q), pad(k), pad(v), causal=True,
                              sm_scale=scale,
                              interpret=dispatch.kernel_interpret())
        return out.reshape(B, S, H, width)[..., :Dv].reshape(B, S, H * Dv)
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    scores = jnp.where(causal_mask(S), scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", probs, v).reshape(B, S, H * Dv)


# -- mixture of experts ------------------------------------------------------


def moe_ffn(x: jax.Array, router_w: jax.Array, w_gate: jax.Array, w_up: jax.Array,
            w_down: jax.Array, n_active: int) -> jax.Array:
    """Token-choice top-k mixture of experts, dense-compute formulation:
    every expert runs on every token and results are combined with the
    top-k router weights renormalised over the chosen.  Exact and
    static-shaped; its FLOPs scale with E, so it serves as the tests'
    oracle for :func:`moe_layer` and nothing serves through it.

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


@jax.named_scope("moe_route")
def moe_route(cfg: ModelConfig, h: jax.Array, router_w: jax.Array,
              bias: Optional[jax.Array] = None
              ) -> tuple[jax.Array, jax.Array]:
    """Each token's experts and their weights, over the router's whole
    width whatever share is held here (identity experts, the last
    ``n_zero_experts`` outputs, among them) → (ids [T, k] int32, weights
    [T, k] float32).  ``bias`` [E] (a score-correction bias) is added for
    the choice only: the weights are the uncorrected scores.

    ``norm_topk``: top-k of the logits, weights a softmax over the
    chosen.  Otherwise the scores are a softmax over all experts, kept
    as they are and scaled by ``routed_scaling``.  With ``n_group`` > 1
    the choice is group-limited greedy: a group scores as its best
    expert, the best ``topk_group`` groups stay and the rest are masked
    out before the top-k."""
    logits = h.astype(jnp.float32) @ router_w.astype(jnp.float32)  # [T, E]
    scores = logits if cfg.norm_topk else jax.nn.softmax(logits, axis=-1)
    choose = scores
    if cfg.n_group > 1:
        T, E = scores.shape
        per_group = E // cfg.n_group
        group_best = scores.reshape(T, cfg.n_group, per_group).max(axis=-1)
        _, kept = lax.top_k(group_best, cfg.topk_group)  # [T, topk_group]
        keep = jnp.zeros((T, cfg.n_group), bool).at[
            jnp.arange(T)[:, None], kept].set(True)
        choose = jnp.where(jnp.repeat(keep, per_group, axis=1), scores,
                           -jnp.inf)
    if bias is not None:
        choose = choose + bias
    top_vals, top_idx = lax.top_k(choose, cfg.n_experts_active)
    if bias is not None:
        top_vals = jnp.take_along_axis(scores, top_idx, axis=-1)
    weights = (jax.nn.softmax(top_vals, axis=-1) if cfg.norm_topk
               else top_vals * cfg.routed_scaling)
    return top_idx.astype(jnp.int32), weights


# megablox tiles (rows, contraction, columns) of the grouped product on
# the chip, by the product's (K, N); rows are padded to whole tiles.
# DeepSeek-V2's two (5120 x 1536 and back): of the four tried at the
# served shapes (decode pass: 384 rows, 36 experts touched; chunk pass:
# 3072 rows) the fastest on both matrices (PERF.md section 6, PR 29).
# LongCat-Flash's two (6144 x 2048 and back): whole tiles in K and N; of
# seven tried a matrix at a decode pass (64 tokens, 11 experts touched)
# and a chunk pass (832 tokens) the fastest on the first (476 / 708 us
# against 532 / 793 for DeepSeek's tuple) and within 1.5 % of the best on
# the second (PERF.md section 6, PR 34).
# SmallThinker's two (2560 x 768 and back): one whole weight tile an
# expert and 256 rows; of six tried at a decode pass (32 tokens x 6, 62
# experts touched) and a chunk pass (1024 tokens x 6) the fastest on the
# chunk pass by 11-12 % (580 / 587 us against 657 / 659 for 128 rows)
# and within 1.5 % of the best on the decode pass (381 / 371 against
# 375 / 368) (PERF.md section 6, PR 36).
GMM_TILING = {
    (5120, 1536): (128, 2560, 768), (1536, 5120): (128, 2560, 768),
    (6144, 2048): (128, 2048, 1024), (2048, 6144): (128, 2048, 1024),
    (2560, 768): (256, 2560, 768), (768, 2560): (256, 768, 2560),
}
GMM_TILE_ELEMENTS = 2560 * 768  # a weight tile of any other product


def gmm_tiling(k: int, n: int) -> tuple[int, int, int]:
    """The grouped product's tiles for a ``[K, N]`` matrix: the probed
    tuple where the shape has one, else the largest whole 128-multiples
    dividing K (at most 2560) and N within one weight tile's budget, so
    that no tile is ragged."""
    if (k, n) in GMM_TILING:
        return GMM_TILING[(k, n)]

    def whole(dim: int, most: int) -> int:
        fits = [t for t in range(128, min(dim, most) + 1, 128) if dim % t == 0]
        return fits[-1] if fits else min(dim, most)

    tk = whole(k, 2560)
    return 128, tk, whole(n, max(128, GMM_TILE_ELEMENTS // tk))


def grouped_matmul_impl() -> str:
    """``"megablox_gmm"`` (the Pallas grouped matmul) on a TPU,
    ``"ragged_dot"`` (XLA's, the oracle) elsewhere: resolved at trace
    time like the attention kernels."""
    from fusioninfer_tpu.ops import dispatch

    return "megablox_gmm" if dispatch.is_tpu_backend() else "ragged_dot"


def grouped_matmul(xs: jax.Array, w, group_sizes: jax.Array,
                   out_dtype) -> jax.Array:
    """Rows of ``xs`` [A, K], sorted by group, each through its group's
    matrix of ``w`` [G, K, N] → [A, N]; rows past the groups' total are
    zeros.  On the chip a Pallas grouped matmul that visits only the
    tiles of groups that have rows (measured against ``lax.ragged_dot``
    there: equal on a decode pass, twice as fast on a chunk pass).

    ``w`` may be ``(stack [L, G, K, N], l)``: layer ``l`` of a stack,
    read IN PLACE.  A kernel's operand is a buffer of its own, so a
    layer sliced out of its stack by the layer scan is first copied,
    gigabytes a pass for a stack of experts; the kernel is handed the
    whole stack as ``L * G`` groups instead, every group outside layer
    ``l`` empty (empty groups are not visited)."""
    stack_layer = None
    if isinstance(w, tuple):
        stack, stack_layer = w
        w = stack.reshape(-1, *stack.shape[2:])
    if grouped_matmul_impl() == "ragged_dot":
        if stack_layer is not None:
            w = lax.dynamic_index_in_dim(stack, stack_layer, 0, keepdims=False)
        return lax.ragged_dot(xs, w, group_sizes,
                              preferred_element_type=jnp.dtype(out_dtype))
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    if stack_layer is not None:
        group_sizes = lax.dynamic_update_slice(
            jnp.zeros((w.shape[0],), group_sizes.dtype), group_sizes,
            (stack_layer * group_sizes.shape[0],))
    A = xs.shape[0]
    tiling = gmm_tiling(*w.shape[1:])
    pad = -A % tiling[0]
    out = gmm(jnp.pad(xs, ((0, pad), (0, 0))) if pad else xs, w, group_sizes,
              preferred_element_type=jnp.dtype(out_dtype),
              tiling=tiling)[:A]
    # the kernel leaves rows past the last group unwritten
    rows = lax.broadcasted_iota(jnp.int32, (A, 1), 0)
    return jnp.where(rows < jnp.sum(group_sizes), out, 0)


EXPERT_MATRICES = ("w_gate", "w_up", "w_down")


# counters a pass through an expert layer adds (engine: fusioninfer:moe_*)
MOE_STATS = ("assignments", "assignments_local", "expert_touches",
             "layer_passes", "assignments_zero")


EXPERT_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def moe_layer(cfg: ModelConfig, layer: Params, h: jax.Array,
              live: Optional[jax.Array] = None,
              routing: Optional[tuple[jax.Array, jax.Array]] = None,
              ) -> tuple[jax.Array, jax.Array]:
    """The ONE expert layer: route over the router's whole width
    (``routing``: :func:`moe_route`'s result where the caller routed
    from another input than ``h``, ``cfg.router_input``),
    compute the part of the result that the experts held here give, with
    no capacity and no assignment dropped, plus what every process
    computes alike for its OWN tokens: the shared experts and the
    identity experts' term → (y [T, D], stats uint32 in
    :data:`MOE_STATS` order).

    An assignment is of three kinds: to an expert held here, to one held
    elsewhere, or to an identity expert (id >= ``n_experts``), which
    returns its input: those weights sum into one ``[T]`` factor on
    ``h`` (scope ``moe_zero``), with no weights read and no exchange.

    Assignments are sorted by held expert (the others, identity ones
    included, and those of tokens not ``live``, sort past the last group
    and weigh nothing), their tokens' rows gathered, and each expert's
    rows go through its own matrices in one grouped product; the
    weighted results return to token order and sum in float32.  What the experts held elsewhere
    would add is left out: on one process of an expert-parallel group
    that is its share before the exchange."""
    T, D = h.shape
    w_gate = layer["w_gate"]  # [held, D, F], or (stack [L, held, D, F], l)
    k = cfg.n_experts_active
    held = (w_gate[0].shape[1] if isinstance(w_gate, tuple)
            else w_gate.shape[0])
    top_idx, top_w = routing if routing is not None else moe_route(
        cfg, h, layer["router"], layer.get("router_bias"))
    with jax.named_scope("moe_experts"):
        local = top_idx - cfg.expert_offset
        mine = (local >= 0) & (local < held)
        if live is not None:
            mine = mine & live[:, None]
        group = jnp.where(mine, local, held).reshape(T * k)
        order = jnp.argsort(group, stable=True)
        sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
        xs = h[order // k]  # [A, D] rows in expert order
        act = EXPERT_ACTS[cfg.expert_act](
            grouped_matmul(xs, w_gate, sizes, h.dtype))
        act = act * grouped_matmul(xs, layer["w_up"], sizes, h.dtype)
        ys = grouped_matmul(act, layer["w_down"], sizes, jnp.float32)
        weight = jnp.where(mine, top_w, 0.0).reshape(T * k)[order]
        ys = ys * weight[:, None]
        back = jnp.zeros((T * k,), jnp.int32).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32))
        y = ys[back].reshape(T, k, D).sum(axis=1)
    if "ws_gate" in layer:
        with jax.named_scope("moe_shared"):
            y = y + swiglu(h, layer["ws_gate"], layer["ws_up"],
                           layer["ws_down"]).astype(jnp.float32)
    n_zero = jnp.zeros((), jnp.int32)
    if cfg.n_zero_experts:
        with jax.named_scope("moe_zero"):
            zero = top_idx >= cfg.n_experts
            if live is not None:
                zero = zero & live[:, None]
            factor = jnp.sum(jnp.where(zero, top_w, 0.0), axis=1)  # [T]
            y = y + factor[:, None] * h.astype(jnp.float32)
            n_zero = jnp.sum(zero)
    n_live = T if live is None else jnp.sum(live)
    stats = jnp.stack([n_live * k, jnp.sum(sizes), jnp.sum(sizes > 0),
                       jnp.ones((), jnp.int32), n_zero]).astype(jnp.uint32)
    return y.astype(h.dtype), stats


# -- parameter init ----------------------------------------------------------


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key: jax.Array, denom: jax.Array, shape: tuple, dtype) -> jax.Array:
    """One seeded matrix, N(0, 1/fan_in) rounded to ``dtype``: normal →
    scale → cast under one jit, so no float32 copy outlives the call.
    ``denom`` = sqrt(fan_in) is an ARGUMENT: a true division, the same
    bits as the op-by-op form (a constant would let XLA multiply by its
    reciprocal, which rounds about one element in 1e5 otherwise)."""
    return (jax.random.normal(key, shape, jnp.float32) / denom).astype(dtype)


@partial(jax.jit, static_argnames=("shape",), donate_argnums=(0,))
def _draw_into(buf: jax.Array, i, key: jax.Array, denom: jax.Array,
               shape: tuple) -> jax.Array:
    """Layer ``i`` of a stacked matrix drawn in place: the stack is born
    whole and filled a layer at a time, so the float32 draw in flight is
    one layer's, not the stack's."""
    return buf.at[i].set(_draw(key, denom, shape, buf.dtype))


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Random-init parameters, layer weights stacked on axis 0.

    One homogeneous stack (``params["layers"]``) is drawn a whole matrix
    at a time under ``split(key, 12)[slot]``.  A model with latent
    attention (and with it, leading dense layers) or with a layer
    pattern or an indexer is drawn a layer at a time by
    :func:`_init_stacks`."""
    cfg.validate()
    if cfg.is_mla or cfg.layer_pattern is not None or cfg.is_sparse:
        return _init_stacks(cfg, key)
    dtype = cfg.jax_dtype
    L, D, H, KV, Hd, F = (
        cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff,
    )
    keys = jax.random.split(key, 12)

    def dense(k, shape, fan_in):
        return _draw(k, jnp.sqrt(fan_in), shape, dtype)

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
        E, EF = cfg.experts_held, cfg.expert_d_ff
        layers["router"] = dense(keys[4], (L, D, cfg.n_experts), D).astype(jnp.float32)
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


# the key of every matrix a two-stack model draws: fold_in(fold_in(key,
# slot), layer index within its stack); embed and lm_head fold the slot only
STACK_SLOTS = {
    "embed": 1, "lm_head": 2, "wo": 13,
    "wq_a": 14, "wq_b": 15, "wkv_a": 16, "wkv_b": 17,
    "w_gate": 20, "w_up": 21, "w_down": 22, "router": 23,
    "ws_gate": 24, "ws_up": 25, "ws_down": 26,
    "wd_gate": 27, "wd_up": 28, "wd_down": 29,
    "wq": 30, "wk": 31, "wv": 32,
    "wiq": 40, "wik": 41, "ww": 42,
}
DENSE_STACK_SLOT_OFFSET = 100  # the leading dense layers' matrices
# what a shortcut-connected double layer holds twice, on a sub-layer
# axis after the layer axis: norms, attention, and the dense FFNs
# (``wd_*``: ``w_*`` are the expert stack's there)
SUBLAYER_MATRICES = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo",
                     "wd_gate", "wd_up", "wd_down")
SUBLAYER_PARAMS = ("attn_norm", "mlp_norm", "q_a_norm", "kv_a_norm",
                   *SUBLAYER_MATRICES)


def stack_matrix_shapes(cfg: ModelConfig, experts: bool) -> dict:
    """name -> (one layer's shape, fan_in) of the seeded matrices of a
    layer of the dense stack or of the expert stack.  In a
    shortcut-connected double layer the attention matrices and the two
    dense FFNs (``wd_*``) lead with a sub-layer axis of 2."""
    D, H, F = cfg.d_model, cfg.n_heads, cfg.d_ff
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    if cfg.is_mla:
        out = {
            "wq_a": ((D, cfg.q_lora_rank), D),
            "wq_b": ((cfg.q_lora_rank, H * qk), cfg.q_lora_rank),
            "wkv_a": ((D, cfg.latent_dim), D),
            "wkv_b": ((cfg.kv_lora_rank,
                       H * (cfg.qk_nope_dim + cfg.v_head_dim)),
                      cfg.kv_lora_rank),
        }
    else:
        kv = cfg.n_kv_heads * cfg.head_dim
        out = {"wq": ((D, H * cfg.head_dim), D), "wk": ((D, kv), D),
               "wv": ((D, kv), D)}
    out["wo"] = ((cfg.attn_out_dim, D), cfg.attn_out_dim)
    if cfg.is_sparse:  # the indexer's queries, its one key, head weights
        HI, Di = cfg.index_n_heads, cfg.index_head_dim
        out.update(wiq=((D, HI * Di), D), wik=((D, Di), D), ww=((D, HI), D))
    if cfg.sublayers > 1:
        out.update(wd_gate=((D, F), D), wd_up=((D, F), D), wd_down=((F, D), F))
        out = {name: ((cfg.sublayers, *shape), fan_in)
               for name, (shape, fan_in) in out.items()}
    if not experts:
        out.update(w_gate=((D, F), D), w_up=((D, F), D), w_down=((F, D), F))
        return out
    E, EF = cfg.experts_held, cfg.expert_d_ff
    out.update(router=((D, cfg.router_width), D),
               w_gate=((E, D, EF), D), w_up=((E, D, EF), D),
               w_down=((E, EF, D), EF))
    if cfg.n_shared_experts:
        SF = cfg.n_shared_experts * EF
        out.update(ws_gate=((D, SF), D), ws_up=((D, SF), D),
                   ws_down=((SF, D), SF))
    return out


def _init_stacks(cfg: ModelConfig, key: jax.Array) -> Params:
    """Seeded weights of a latent-attention or layer-pattern model, in up
    to two stacks:
    ``params["dense_layers"]`` (the leading ``first_k_dense`` layers)
    and ``params["layers"]`` (the expert layers after them).  Every
    matrix is drawn a LAYER at a time into its stack (:func:`_draw_into`):
    a stack of held experts is gigabytes, and its float32 draw beside
    the weights already resident would not fit the chip."""
    dtype = cfg.jax_dtype
    D = cfg.d_model

    def stack(n: int, experts: bool, slot_offset: int) -> Params:
        lead = (n,) if cfg.sublayers == 1 else (n, cfg.sublayers)
        layers: Params = {
            "attn_norm": jnp.ones((*lead, D), dtype),
            "mlp_norm": jnp.ones((*lead, D), dtype),
        }
        if cfg.is_mla:
            layers["q_a_norm"] = jnp.ones((*lead, cfg.q_lora_rank), dtype)
            layers["kv_a_norm"] = jnp.ones((*lead, cfg.kv_lora_rank), dtype)
        elif cfg.qk_norm:
            layers["q_norm"] = jnp.ones((n, cfg.head_dim), dtype)
            layers["k_norm"] = jnp.ones((n, cfg.head_dim), dtype)
        if cfg.is_sparse:  # the LayerNorm on the indexer key
            layers["ik_norm"] = jnp.ones((n, cfg.index_head_dim), dtype)
            layers["ik_bias"] = jnp.zeros((n, cfg.index_head_dim), dtype)
        if experts and cfg.router_score_bias:
            # a buffer, zeros until a checkpoint brings a trained one
            layers["router_bias"] = jnp.zeros((n, cfg.router_width),
                                              jnp.float32)
        for name, (shape, fan_in) in stack_matrix_shapes(cfg, experts).items():
            k_m = jax.random.fold_in(key, STACK_SLOTS[name] + slot_offset)
            buf = jnp.zeros((n, *shape), dtype)
            for i in range(n):
                buf = _draw_into(buf, i, jax.random.fold_in(k_m, i),
                                 jnp.sqrt(fan_in), shape)
            # the router is read in float32 (top-k is sensitive to the
            # logits' rounding); its values stay the rounded draw's
            layers[name] = buf.astype(jnp.float32) if name == "router" else buf
        return layers

    nd = cfg.n_dense_layers
    params: Params = {
        "embed": _draw(jax.random.fold_in(key, STACK_SLOTS["embed"]),
                       jnp.sqrt(D), (cfg.vocab_size, D), dtype),
        "layers": stack(cfg.n_layers - nd, cfg.is_moe, 0),
        "final_norm": jnp.ones((D,), dtype),
    }
    if nd and cfg.is_moe:
        params["dense_layers"] = stack(nd, False, DENSE_STACK_SLOT_OFFSET)
    if not cfg.tie_embeddings:
        params["lm_head"] = _draw(
            jax.random.fold_in(key, STACK_SLOTS["lm_head"]), jnp.sqrt(D),
            (D, cfg.vocab_size), dtype)
    return params


def layer_stacks(cfg: ModelConfig, params: Params) -> list[tuple[Params, int]]:
    """The model's layer stacks in order, each with the index of its
    first layer: the leading dense layers (where the model has them),
    then ``params["layers"]``."""
    if "dense_layers" in params:
        return [(params["dense_layers"], 0),
                (params["layers"], cfg.n_dense_layers)]
    return [(params["layers"], 0)]


# -- forward -----------------------------------------------------------------


def scan_layers(cfg: ModelConfig, params: Params, lora, body, carry):
    """``lax.scan`` of the ONE layer ``body`` over each of the model's
    layer stacks in turn (the leading dense layers, then the rest), the
    layer index running on, BY PERIOD: one scan step runs the period's
    layers in turn, ``body(carry, (layer, [lora,] l), kind, cache_l)``
    with the layer's static :class:`LayerKind` and its cache layer in
    its kind's pool.  A model without a pattern is a period of one: the
    scan it always was, ``cache_l`` the layer index itself.

    Per-layer scan operands: weights (+ lora) + the layer index.  The KV
    cache is deliberately NOT xs: it rides the scan CARRY as one donated
    stacked pool per array, updated in place by ``_scatter_kv`` —
    threading it through xs→ys made XLA write a fresh cache-sized ys
    every step (a full pool copy per decode step; measured step time
    scaled with pool size, round 5)."""
    from fusioninfer_tpu.models.quantization import is_quantized

    kinds, P = cfg.layer_kinds, cfg.period
    for stack, first in layer_stacks(cfg, params):
        n = jax.tree.leaves(stack)[0].shape[0]
        # a stack of experts is not sliced by the scan: the grouped
        # product reads layer l of it in place (grouped_matmul)
        whole = {k: stack[k] for k in EXPERT_MATRICES
                 if "router" in stack and not is_quantized(stack[k])}
        # nor are a double layer's twice-held matrices [L, 2, ...]: the
        # scan's slice [2, ...] of one is a buffer of its own, written
        # every layer (1.1 GB of dense-FFN weights a layer at
        # LongCat-Flash's widths), where a dot reads ONE matrix of the
        # stack in place (mla_block indexes it by 2 l + i)
        if cfg.sublayers > 1:
            whole.update({k: stack[k] for k in SUBLAYER_MATRICES})
        # nor, for the same reason, the matrices a period holds several
        # times a step: layer P s + j's are read in place
        indexed = ({k: v for k, v in stack.items()
                    if k not in whole and v.ndim > 2} if P > 1 else {})
        rest = {k: v for k, v in stack.items()
                if k not in whole and k not in indexed}
        if P > 1:  # [n/P, P, ...]: a step's norms, a row a layer
            rest = {k: v.reshape(n // P, P, *v.shape[1:])
                    for k, v in rest.items()}
        xs = [rest]
        if lora is not None:
            xs.append(lora)
        xs.append(first + jnp.arange(n // P) * P if P > 1
                  else first + jnp.arange(n))

        def stack_body(carry, inputs, whole=whole, indexed=indexed,
                       first=first):
            if P == 1:
                layer = {**inputs[0], **{k: (w, inputs[-1] - first)
                                         for k, w in whole.items()}}
                return body(carry, (layer, *inputs[1:]), kinds[0],
                            inputs[-1]), None
            for j, kind in enumerate(kinds):
                l = inputs[-1] + j
                layer = {
                    **{k: v[j] for k, v in inputs[0].items()},
                    **{k: lax.dynamic_index_in_dim(w, l - first, 0,
                                                   keepdims=False)
                       for k, w in indexed.items()},
                    **{k: (w, l - first) for k, w in whole.items()}}
                carry = body(carry, (layer, *inputs[1:-1], l), kind,
                             (inputs[-1] - first) // P * kind.per_period
                             + kind.rank)
            return carry, None

        carry, _ = lax.scan(stack_body, carry, tuple(xs))
    return carry



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


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    orig_dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(orig_dtype)


def _index_rope(cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    """Rotary (half-rotation layout) on the leading half of the indexer
    dims, the rest as projected."""
    r = cfg.index_head_dim // 2
    return jnp.concatenate(
        [apply_rope(x[..., :r], positions, cfg.rope_theta), x[..., r:]],
        axis=-1)


def indexer_proj(cfg: ModelConfig, layer: Params, x: jax.Array,
                 positions: jax.Array):
    """The sparse-attention indexer's projections of the pre-normed
    input, traced under ``attn/indexer``: x [B, S, D] → (queries [B, S,
    HI, Di], head weights [B, S, HI] float32 with ``HI^-1/2`` folded in,
    the position's one key [B, S, Di] after its LayerNorm)."""
    B, S, _ = x.shape
    HI, Di = cfg.index_n_heads, cfg.index_head_dim
    with jax.named_scope("attn"), jax.named_scope("indexer"):
        h = rms_norm(x, layer["attn_norm"], cfg.rms_eps)
        q = _index_rope(cfg, (h @ layer["wiq"]).reshape(B, S, HI, Di),
                        positions)
        k = layer_norm(h @ layer["wik"], layer["ik_norm"], layer["ik_bias"],
                       cfg.rms_eps)
        k = _index_rope(cfg, k[..., None, :], positions)[..., 0, :]
        w = jnp.einsum("bsd,dh->bsh", h, layer["ww"],
                       preferred_element_type=jnp.float32) * HI ** -0.5
    return q, w, k


def sparse_fresh_attention(cfg: ModelConfig, q, k, v, idx) -> jax.Array:
    """Causal sparse attention of whole fresh sequences, written out: the
    indexer over every earlier position, the exact top ``index_topk``,
    the softmax over those alone → [B, S, H * Hd]."""
    from fusioninfer_tpu.ops.sparse_attention import (
        selection_mask,
        sparse_threshold,
    )

    q_i, w, k_i = idx
    B, S = q.shape[:2]
    causal = causal_mask(S)[0, 0]  # [S, S]
    with jax.named_scope("indexer"):
        s = jnp.einsum("bthd,bsd->bths", q_i, k_i,
                       preferred_element_type=jnp.float32)
        s = jnp.maximum(s * cfg.index_head_dim ** -0.5, 0.0)
        s = jnp.einsum("bths,bth->bts", s, w)
        s = jnp.where(causal, jnp.where(s == 0, 0.0, s), -jnp.inf)
    with jax.named_scope("select"):
        flat = s.reshape(B * S, S)
        keep = selection_mask(flat, *sparse_threshold(flat, cfg.index_topk))
    with jax.named_scope("sparse"):
        return _attention(q, k, v, (keep.reshape(B, S, S) & causal)[:, None])


@jax.named_scope("attn_qkv")
def qkv_proj(
    cfg: ModelConfig, layer: Params, x: jax.Array, positions: jax.Array,
    lora: Params = None, adapter_ids: jax.Array = None, rope: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pre-norm + QKV projection + (optional) QK-norm + RoPE — shared by
    every execution path (full forward, paged prefill/suffix, decode) so
    model features can never drift between them.  ``rope`` False (a
    NoPE layer's static kind): q and k are left as projected.

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
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def mlp_block(cfg: ModelConfig, layer: Params, x: jax.Array,
              live: Optional[jax.Array] = None, routing=None):
    """Pre-norm + FFN, shared by every path → ``(y, stats)``: a dense
    SwiGLU (stats None), or the expert layer where the layer's tree
    holds a router (:func:`moe_layer`; stats its counters).

    x: [B, S, D] → [B, S, D] (residual NOT added).  ``live`` [B, S]
    marks real tokens: padding chooses no expert; ``routing``: the
    block's own :func:`moe_route` where the router reads the layer's
    input (:func:`gqa_block`).  Callers dequantize the layer tree once
    at block entry (see qkv_proj invariant)."""
    B, S, D = x.shape
    h = rms_norm(x, layer["mlp_norm"], cfg.rms_eps)
    if "router" in layer:
        y, stats = moe_layer(cfg, layer, h.reshape(B * S, D),
                             None if live is None else live.reshape(B * S),
                             routing)
        return y.reshape(B, S, D), stats
    with jax.named_scope("mlp"):
        return swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"]), None


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


def attn_scope(kind: LayerKind) -> str:
    """The named scope of a layer kind's attention, inside ``attn``."""
    return "attn_full" if kind.window is None else "attn_window"


def gqa_block(cfg: ModelConfig, layer: Params, x: jax.Array,
              positions: jax.Array, kind: LayerKind, attend, carry,
              live: Optional[jax.Array] = None, lora: Params = None,
              adapter_ids: Optional[jax.Array] = None):
    """ONE block of a GQA model → ``(x, carry, stats)``: the body the
    no-cache forward and the three serving programs share, traced with
    the layer's STATIC ``kind`` (rotary or not here; the window where
    the caller attends).  They differ only in how the layer attends, so
    that is a callback: ``attend(q, k, v, carry) -> (carry, attention
    output [B, S, H * Hd])``, with the indexer's projections
    (:func:`indexer_proj`) as a fifth argument where ``cfg.is_sparse``
    (fresh causal attention in
    :func:`layer_forward`; write, then score over the kind's pages in
    ``model_runner``), ``carry`` being the caller's own (the pool).

    Where ``cfg.router_input`` is "layer_input" the experts are chosen
    HERE, from the raw ``x`` before attention, and the expert layer is
    handed the choice.  x: [B, S, D]; ``live`` [B, S] marks real
    tokens for the experts; ``stats``: the expert layer's counters."""
    routing = None
    if cfg.router_input == "layer_input" and "router" in layer:
        routing = moe_route(cfg, x.reshape(-1, x.shape[-1]), layer["router"],
                            layer.get("router_bias"))
    q, k, v = qkv_proj(cfg, layer, x, positions, lora, adapter_ids,
                       rope=kind.rope)
    if cfg.is_sparse:  # the indexer's projections go to ``attend`` too
        carry, attn = attend(q, k, v, carry,
                             indexer_proj(cfg, layer, x, positions))
    else:
        carry, attn = attend(q, k, v, carry)
    x = x + attn_out_proj(layer, attn, lora, adapter_ids)
    y, stats = mlp_block(cfg, layer, x, live, routing)
    return x + y, carry, stats


def mla_block(cfg: ModelConfig, layer: Params, x: jax.Array, attend,
              carry, live: Optional[jax.Array] = None):
    """ONE block of a latent-attention model → ``(x, carry, stats)``:
    the body the no-cache forward and the three serving programs share.
    They differ only in how a sub-layer attends, so that is a callback:
    ``attend(i, sub, x, carry) -> (carry, attention output, residual NOT
    added)`` for sub-layer ``i`` with its weights ``sub`` (a fresh causal
    sequence in the expanded form in :func:`layer_forward`; the absorbed
    form over latent pages in ``model_runner``), ``carry`` being the
    caller's own (the rows to cache; the pool).

    ``cfg.block`` "single": attention, then the FFN (:func:`mlp_block`).
    "shortcut_double": ``a0 = x + MLA_0(N(x))``; ``m = N(a0)``; the
    expert layer's ``s = MoE(m)`` is held back while ``b0 = a0 +
    FFN_0(m)``, ``a1 = b0 + MLA_1(N(b0))``, ``b1 = a1 + FFN_1(N(a1))``
    run; ``out = b1 + s``.  x: [B, S, D]; ``live`` [B, S] marks real
    tokens for the experts; ``stats``: the expert layer's counters."""
    if cfg.block == "single":
        carry, attn = attend(0, layer, x, carry)
        x = x + attn
        y, stats = mlp_block(cfg, layer, x, live)
        return x + y, carry, stats
    B, S, D = x.shape

    def of_sublayer(v, i):
        """``v``: this layer's two ``[2, ...]``, or ``(stack [L, 2, ...],
        l)``: matrix ``2 l + i`` of the whole stack, read in place."""
        if not isinstance(v, tuple):
            return v[i]
        stack, l = v
        return lax.dynamic_index_in_dim(
            stack.reshape(-1, *stack.shape[2:]), cfg.sublayers * l + i, 0,
            keepdims=False)

    for i in range(cfg.sublayers):
        sub = {k: (of_sublayer(v, i) if k in SUBLAYER_PARAMS else v)
               for k, v in layer.items()}
        carry, attn = attend(i, sub, x, carry)
        x = x + attn
        h = rms_norm(x, sub["mlp_norm"], cfg.rms_eps)
        if i == 0:  # the shortcut: read here, added after the last FFN
            shortcut, stats = moe_layer(
                cfg, layer, h.reshape(B * S, D),
                None if live is None else live.reshape(B * S))
        with jax.named_scope("mlp"):
            x = x + swiglu(h, sub["wd_gate"], sub["wd_up"], sub["wd_down"])
    return x + shortcut.reshape(B, S, D), carry, stats


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
    live: Optional[jax.Array] = None,
    kind: Optional[LayerKind] = None,
):
    """One transformer block → ``(output, kv, stats)``: ``kv`` is what a
    position caches, (k, v) or with latent attention a tuple of the
    latent rows [B, S, rank + rope] of each of the block's attentions;
    ``stats`` the expert layer's counters (None for a dense FFN).
    ``live`` [B, S] marks real tokens for the experts.  ``kind``: the
    layer's static kind (:attr:`ModelConfig.layer_kinds`; default: the
    period's first, which is every layer of a model without a pattern).

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
    if kind is None:
        kind = cfg.layer_kinds[0]

    layer = maybe_dequantize_tree(layer, cfg.jax_dtype)
    if cfg.is_mla:
        if kv is not None or mesh is not None or lora is not None:
            raise NotImplementedError(
                "latent attention runs fresh causal sequences on one device "
                "without adapters; cached context goes through model_runner")

        def attend(i, sub, x, latents):
            h = rms_norm(x, sub["attn_norm"], cfg.rms_eps)
            q_nope, q_rope = mla_queries(cfg, sub, h, positions)
            latent = mla_latent(cfg, sub, h, positions)
            with jax.named_scope("attn"):
                k, v = mla_expand_kv(cfg, sub, latent)
                attn = _mla_fresh_attention(
                    cfg, jnp.concatenate([q_nope, q_rope], axis=-1), k, v)
            with jax.named_scope("mla_out"):
                return (*latents, latent), attn @ sub["wo"]

        return mla_block(cfg, layer, x, attend, (), live)
    if kv is None and mask is not None:
        raise ValueError(
            "layer_forward(kv=None) is causal self-attention; it derives "
            "its own mask — pass kv=(k, v) history to use a custom mask"
        )
    if kv is not None and mask is None:
        raise ValueError("layer_forward with kv history requires a mask")

    if cfg.is_sparse and kv is not None:
        raise NotImplementedError(
            "sparse attention reads its history from the paged cache "
            "(model_runner); the no-cache forward runs fresh sequences")

    def attend(q, k, v, fresh, idx=None):
        with jax.named_scope("attn"), jax.named_scope(attn_scope(kind)):
            if idx is not None:  # kv: the position's K, V and indexer key
                return (k, v, idx[2]), sparse_fresh_attention(
                    cfg, q, k, v, idx)
            if kv is not None:
                return (k, v), _attention(q, *kv, mask)
            from fusioninfer_tpu.ops import dispatch, flash_attention

            if dispatch.resolve_attn(cfg.attn_impl) == "flash" and dispatch.flash_seq_ok(S):
                # fresh K/V over the full (causal) sequence: Pallas flash path
                if mesh is not None:
                    from fusioninfer_tpu.ops.sharded import flash_attention_tp

                    attn = flash_attention_tp(
                        mesh, q, k, v, causal=True,
                        interpret=dispatch.kernel_interpret(),
                        window=kind.window,
                    )
                else:
                    attn = flash_attention(
                        q, k, v, causal=True, interpret=dispatch.kernel_interpret(),
                        window=kind.window,
                    )
            else:
                attn = _attention(q, k, v, causal_mask(S, window=kind.window))
            return (k, v), attn

    return gqa_block(cfg, layer, x, positions, kind, attend, None, live,
                     lora, adapter_ids)


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

    def body(x, inputs, kind, _cache_l):
        return layer_forward(cfg, inputs[0], x, positions, kind=kind)[0]

    x = scan_layers(cfg, params, None, body, x)
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
