"""``model_type`` "deepseek_v2": the architecture's plain forward and its
work counts, found by that name (``reference.py``'s docstring states what
a file in this directory gives; ``work.py`` reads the counts).

Architecture (DeepSeek-V2, huggingface.co/deepseek-ai/DeepSeek-V2,
modeling_deepseek.py; arXiv:2405.04434), pre-norm decoder, RMSNorm with
gain 1, residuals as usual; ``h = RMSNorm(x)``:

- Multi-head latent attention, in the published (expanded) form, no
  absorption and no cache.  ``c_q = RMSNorm(h W_DQ)``; ``q = c_q W_UQ`` ->
  heads x (nope | rope); ``[c_kv | k_r] = h W_DKV``; ``c_kv =
  RMSNorm(c_kv)``; ``q_r``, ``k_r`` rotated (one ``k_r`` shared by all
  heads); ``[k_n | v] = c_kv W_UKV`` per head; ``k = [k_n | k_r]``; causal
  softmax of ``q . k x s``, ``s = (nope + rope)^-1/2 x m^2``, ``m = 0.1 x
  mscale_all_dim x ln(factor) + 1``; ``o = concat_i(p v_i) W_O``.
- Rotary embedding: YaRN frequencies (``theta^(-2j/d)`` blended with the
  same / factor by the linear ramp between ``find_correction_range(
  beta_fast, beta_slow, d, theta, original_max)``), cos and sin scaled by
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``; rotation
  over interleaved pairs (2i, 2i+1), the rotated values written half by
  half, as modeling_deepseek.py does.
- Layers below ``first_k_dense_replace``: SwiGLU of ``intermediate_size``.
  The others: ``sc = softmax(h W_r)`` over the router's published width in
  float32; a group scores as the best of its experts; the best
  ``topk_group`` of ``n_group`` groups stay, the rest are masked; the top
  ``num_experts_per_tok`` of what is left; weight ``routed_scaling_factor
  x sc_e`` (``norm_topk_prob`` false: not renormalised); ``y = sum_{e
  chosen and held} w_e SwiGLU_e(h) + SwiGLU_shared(h)``.

The share (PERF.md section 4): the configuration's file gives, under the
published keys, what THIS chip holds: ``n_routed_experts`` experts
starting at ``deployment.expert_offset`` of the ``published`` count (the
router keeps the published width), ``vocab_size`` rows of the vocabulary,
``num_hidden_layers`` layers.  What the absent experts would add is left
out, and that partial result goes on to the next layer.

Departures: none in the mathematics.  Weights are random, not trained:
every matrix is N(0, 1/fan_in) from ``jax.random.normal`` in float32,
divided by sqrt(fan_in) (a true division) and rounded to the
configuration's dtype, norm gains 1.  The layers are two stacks (the
leading dense layers; the expert layers after them) and every matrix of a
stack is drawn a layer at a time under ``fold_in(fold_in(key(seed), slot
[+ 100 for the dense stack]), layer index within the stack)``; the
embedding and the head whole, under ``fold_in(key(seed), slot)``.  That is
the recipe the served model is documented to use for ``--seed``; it is
restated here.

One device holds everything (the configuration is one chip's share).  It
imports nothing of the program, and no jax until a forward is built: the
counts are plain Python over the configuration's dict.
"""

from __future__ import annotations

import math
from functools import partial

from reference import Q_BLOCK, int8_round, rms_norm

SLOTS = {"embed": 1, "lm_head": 2, "wo": 13, "wq_a": 14, "wq_b": 15,
         "wkv_a": 16, "wkv_b": 17, "w_gate": 20, "w_up": 21, "w_down": 22,
         "router": 23, "ws_gate": 24, "ws_up": 25, "ws_down": 26}
DENSE_STACK = 100
HEAD_BLOCK = 16     # heads per attention block (bounds the score tensor)


def sizes(cfg: dict) -> dict:
    held = cfg["n_routed_experts"]
    routed = cfg.get("published", {}).get("n_routed_experts", held)
    return {
        "L": cfg["num_hidden_layers"], "D": cfg["hidden_size"],
        "H": cfg["num_attention_heads"], "F": cfg["intermediate_size"],
        "ql": cfg["q_lora_rank"], "r": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "EF": cfg["moe_intermediate_size"],
        "held": held, "routed": routed,
        "offset": cfg.get("deployment", {}).get("expert_offset", 0),
        "shared": cfg["n_shared_experts"], "k": cfg["num_experts_per_tok"],
        "n_group": cfg["n_group"], "topk_group": cfg["topk_group"],
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "nd": min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"]),
        "V": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
        "theta": float(cfg["rope_theta"]), "yarn": cfg.get("rope_scaling"),
        "tied": bool(cfg["tie_word_embeddings"]),
        "dtype": cfg["torch_dtype"],
    }


# ---- the counts ----------------------------------------------------------

def _attention_params(z: dict) -> int:
    return (z["D"] * z["ql"] + z["ql"] * z["H"] * (z["nope"] + z["rope"])
            + z["D"] * (z["r"] + z["rope"])
            + z["r"] * z["H"] * (z["nope"] + z["v"]) + z["H"] * z["v"] * z["D"])


def matmul_params(cfg: dict) -> int:
    """Parameters a token is multiplied through ON THIS CHIP: attention,
    the dense layers' SwiGLU, per expert layer the router, the shared
    experts and the EXPECTED number of held routed experts a token
    chooses (experts per token x held / published), and the output head
    over the held vocabulary."""
    z = sizes(cfg)
    expert = 3 * z["D"] * z["EF"]
    moe = (z["D"] * z["routed"] + z["shared"] * expert
           + z["k"] * z["held"] * expert // z["routed"])
    return (z["L"] * _attention_params(z) + z["nd"] * 3 * z["D"] * z["F"]
            + (z["L"] - z["nd"]) * moe + z["D"] * z["V"])


def _attention_flops_per_position(z: dict) -> float:
    """The published (expanded) attention: QK^T over nope + rope and PV
    over the value width, every head, one layer."""
    return 2.0 * z["H"] * (z["nope"] + z["rope"] + z["v"])


def token_flops(cfg: dict, context: int, with_head: bool = True) -> float:
    """Forward FLOPs of one token that attends to ``context`` positions."""
    z = sizes(cfg)
    dense = 2.0 * (matmul_params(cfg) - (0 if with_head else z["D"] * z["V"]))
    return dense + z["L"] * _attention_flops_per_position(z) * context


def prompt_flops(cfg: dict, prompt_len: int) -> float:
    """Prefill of a whole prompt: every token through the layers, the
    head once, causal attention over sum(1..n) positions."""
    z = sizes(cfg)
    dense = (2.0 * (matmul_params(cfg) - z["D"] * z["V"]) * prompt_len
             + 2.0 * z["D"] * z["V"])
    return dense + (z["L"] * _attention_flops_per_position(z)
                    * prompt_len * (prompt_len + 1) / 2.0)


def kv_bytes_per_position(cfg: dict, kv_dtype_bytes: int = 2) -> int:
    """Bytes one cached position holds, all layers: one latent row (the
    compressed KV and the shared rope key) a layer."""
    z = sizes(cfg)
    return z["L"] * (z["r"] + z["rope"]) * kv_dtype_bytes


def decode_kv_bytes(cfg: dict, contexts: list[int]) -> float:
    return float(kv_bytes_per_position(cfg)) * float(sum(contexts))


def decode_attn_flops(cfg: dict, contexts: list[int]) -> float:
    """FLOPs of attending one token at each of ``contexts`` straight over
    latent rows (the absorbed form): per head a score over rank + rope
    and values over rank, the least arithmetic that reads a latent row
    once (re-expanding keys and values would cost 2 x rank x heads x
    (nope + v) more a position)."""
    z = sizes(cfg)
    per_position = 2.0 * z["H"] * (z["r"] + z["rope"] + z["r"])
    return z["L"] * per_position * float(sum(contexts))


# ---- the weights ---------------------------------------------------------

def stack_shapes(z: dict, experts: bool) -> dict:
    D, H = z["D"], z["H"]
    out = {"wq_a": ((D, z["ql"]), D),
           "wq_b": ((z["ql"], H * (z["nope"] + z["rope"])), z["ql"]),
           "wkv_a": ((D, z["r"] + z["rope"]), D),
           "wkv_b": ((z["r"], H * (z["nope"] + z["v"])), z["r"]),
           "wo": ((H * z["v"], D), H * z["v"])}
    if not experts:
        F = z["F"]
        out.update(w_gate=((D, F), D), w_up=((D, F), D), w_down=((F, D), F))
        return out
    E, EF, SF = z["held"], z["EF"], z["shared"] * z["EF"]
    out.update(router=((D, z["routed"]), D), w_gate=((E, D, EF), D),
               w_up=((E, D, EF), D), w_down=((E, EF, D), EF))
    if z["shared"]:
        out.update(ws_gate=((D, SF), D), ws_up=((D, SF), D),
                   ws_down=((SF, D), SF))
    return out


def make_weights(z: dict, seed: int, device):
    """(dense stack, expert stack, embed, head) on ``device``: each
    stacked matrix is filled a layer at a time, in place, so the float32
    draw in flight is one layer's."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(z["dtype"])
    root = jax.random.key(seed)

    @partial(jax.jit, static_argnames=("shape",))
    def draw(key, denom, shape):
        return (jax.random.normal(key, shape, jnp.float32) / denom).astype(dtype)

    @partial(jax.jit, static_argnames=("shape",), donate_argnums=(0,))
    def draw_into(buf, i, key, denom, shape):
        return buf.at[i].set(
            (jax.random.normal(key, shape, jnp.float32) / denom).astype(dtype))

    def stack(n, experts, offset):
        out = {}
        for name, (shape, fan_in) in stack_shapes(z, experts).items():
            k_m = jax.random.fold_in(root, SLOTS[name] + offset)
            buf = jnp.zeros((n, *shape), dtype)
            for i in range(n):
                buf = draw_into(buf, i, jax.random.fold_in(k_m, i),
                                jnp.sqrt(fan_in), shape)
            out[name] = buf
        return out

    with jax.default_device(device):
        dense = stack(z["nd"], False, DENSE_STACK) if z["nd"] else None
        moe = stack(z["L"] - z["nd"], True, 0)
        embed = draw(jax.random.fold_in(root, SLOTS["embed"]),
                     jnp.sqrt(z["D"]), (z["V"], z["D"]))
        head = None
        if not z["tied"]:
            head = draw(jax.random.fold_in(root, SLOTS["lm_head"]),
                        jnp.sqrt(z["D"]), (z["D"], z["V"]))
    return dense, moe, embed, head


# ---- the forward ---------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_frequencies(z: dict):
    """(inverse frequencies [rope/2], the factor on cos and sin)."""
    import jax.numpy as jnp

    d, theta, y = z["rope"], z["theta"], z["yarn"]
    extra = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if not y:
        return extra, 1.0

    def correction_dim(n_rot):
        return (d * math.log(y["original_max_position_embeddings"]
                             / (n_rot * 2 * math.pi)) / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), d - 1)
    span = (high - low) if high != low else 0.001
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / span,
                    0.0, 1.0)
    inv = extra / y["factor"] * ramp + extra * (1.0 - ramp)
    return inv, (yarn_mscale(y["factor"], y["mscale"])
                 / yarn_mscale(y["factor"], y["mscale_all_dim"]))


def softmax_scale(z: dict) -> float:
    s = (z["nope"] + z["rope"]) ** -0.5
    if z["yarn"]:
        s *= yarn_mscale(z["yarn"]["factor"], z["yarn"]["mscale_all_dim"]) ** 2
    return s


def rope(z: dict, x, positions):
    """x [S, heads, rope]: pairs (2i, 2i+1) rotated, written half by half."""
    import jax.numpy as jnp

    inv, factor = rope_frequencies(z)
    ang = positions[:, None].astype(jnp.float32) * inv
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(z: dict, w_of, h):
    """Multi-head latent attention of one sequence, expanded form, in
    blocks of heads (and of queries inside them) so that neither the
    per-head keys and values nor the scores are ever whole."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    S = h.shape[0]
    H, nope, rp, v, r = z["H"], z["nope"], z["rope"], z["v"], z["r"]
    pos = jnp.arange(S)
    c_q = rms_norm(h @ w_of("wq_a"), z["eps"])
    ckv = h @ w_of("wkv_a")
    c_kv = rms_norm(ckv[:, :r], z["eps"])
    k_r = rope(z, ckv[:, None, r:], pos)  # [S, 1, rope]: one for all heads
    hb = min(HEAD_BLOCK, H)
    w_uq = w_of("wq_b").reshape(-1, H // hb, hb, nope + rp)
    w_ukv = w_of("wkv_b").reshape(r, H // hb, hb, nope + v)
    w_o = w_of("wo").reshape(H // hb, hb * v, -1)
    scale = softmax_scale(z)
    nb = S // Q_BLOCK
    t = jnp.arange(S)

    def head_block(o, ws):
        uq, ukv, wo = ws  # [ql, hb, nope+rope], [r, hb, nope+v], [hb*v, D]
        q = jnp.einsum("sc,chd->shd", c_q, uq)
        q = jnp.concatenate([q[..., :nope], rope(z, q[..., nope:], pos)], -1)
        kv = jnp.einsum("sc,chd->shd", c_kv, ukv)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r, (S, hb, rp))], -1)
        val = kv[..., nope:]

        def q_block(args):
            qi, b = args
            at = b * Q_BLOCK + jnp.arange(Q_BLOCK)
            s = jnp.einsum("qhd,thd->hqt", qi, k) * scale
            s = jnp.where(t[None, None, :] <= at[None, :, None], s, -jnp.inf)
            return jnp.einsum("hqt,thd->qhd", jax.nn.softmax(s, axis=-1), val)

        out = lax.map(q_block, (q.reshape(nb, Q_BLOCK, hb, nope + rp),
                                jnp.arange(nb)))
        return o + out.reshape(S, hb * v) @ wo, None

    o, _ = lax.scan(head_block, jnp.zeros((S, z["D"]), jnp.float32),
                    (jnp.moveaxis(w_uq, 1, 0), jnp.moveaxis(w_ukv, 1, 0), w_o))
    return o


def route(z: dict, h, router):
    """Weights [S, routed] of the experts each token chose (0 elsewhere),
    over the router's whole width."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    logits = h @ router
    scores = logits if z["norm_topk"] else jax.nn.softmax(logits, axis=-1)
    S, E = scores.shape
    choose = scores
    if z["n_group"] > 1:
        per = E // z["n_group"]
        best = scores.reshape(S, z["n_group"], per).max(axis=-1)
        _, kept = lax.top_k(best, z["topk_group"])
        keep = jnp.zeros((S, z["n_group"]), bool).at[
            jnp.arange(S)[:, None], kept].set(True)
        choose = jnp.where(jnp.repeat(keep, per, axis=1), scores, -jnp.inf)
    vals, idx = lax.top_k(choose, z["k"])
    w = (jax.nn.softmax(vals, axis=-1) if z["norm_topk"]
         else vals * z["routed_scale"])
    return jnp.zeros((S, E), jnp.float32).at[jnp.arange(S)[:, None], idx].set(w)


def swiglu(h, gate, up, down):
    import jax

    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def expert_ffn(z: dict, quant: bool, w_of, layer, h):
    """The held experts' part of the layer, every held expert over every
    token and weighted by the router's choice (0 where not chosen), one
    expert at a time; plus the shared experts."""
    import jax.numpy as jnp
    from jax import lax

    weights = route(z, h, w_of("router"))
    mine = lax.dynamic_slice_in_dim(weights, z["offset"], z["held"], axis=1)

    def one(y, ws):
        gate, up, down, w_e = ws
        f = [m.astype(jnp.float32) for m in (gate, up, down)]
        if quant:
            f = [int8_round(m, 0) for m in f]
        return y + w_e[:, None] * swiglu(h, *f), None

    y, _ = lax.scan(one, jnp.zeros_like(h),
                    (layer["w_gate"], layer["w_up"], layer["w_down"], mine.T))
    if z["shared"]:
        y = y + swiglu(h, w_of("ws_gate"), w_of("ws_up"), w_of("ws_down"))
    return y


def stack_forward(z: dict, quant: bool, experts: bool, x, layers):
    """x [S, D] float32 through one stack's layers (one scan)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def body(x, layer):
        def w_of(name):
            w = layer[name].astype(jnp.float32)
            return int8_round(w, 0) if quant else w

        x = x + attention(z, w_of, rms_norm(x, z["eps"]))
        h = rms_norm(x, z["eps"])
        if experts:
            return x + expert_ffn(z, quant, w_of, layer, h), None
        return x + swiglu(h, w_of("w_gate"), w_of("w_up"), w_of("w_down")), None

    with jax.default_matmul_precision("highest"):
        x, _ = lax.scan(body, x, layers)
    return x


def embed_tokens(quant: bool, embed, tokens):
    import jax.numpy as jnp

    rows = embed[tokens].astype(jnp.float32)
    if quant:  # the embedding is read by row: one scale per row
        rows = int8_round(rows, 1)
    return rows


class Forward:
    """The seeded weights on one device and the forward through them."""

    def __init__(self, cfg: dict, seed: int, devices: list):
        self.z = sizes(cfg)
        self.device = devices[0]
        self.dense, self.moe, self.embed, head = make_weights(
            self.z, seed, self.device)
        self.head = self.embed if self.z["tied"] else head
        self._fns: dict = {}

    def _fn(self, what: str, quant: bool):
        import jax

        key = (what, quant)
        if key not in self._fns:
            f = {"embed": partial(embed_tokens, quant),
                 "dense": partial(stack_forward, self.z, quant, False),
                 "moe": partial(stack_forward, self.z, quant, True)}[what]
            self._fns[key] = jax.jit(f)
        return self._fns[key]

    def hidden(self, padded, quant: bool):
        import jax
        import jax.numpy as jnp

        x = self._fn("embed", quant)(
            self.embed, jax.device_put(jnp.asarray(padded), self.device))
        if self.dense is not None:
            x = self._fn("dense", quant)(x, self.dense)
        return self._fn("moe", quant)(x, self.moe)
