"""``model_type`` "longcat_flash": the architecture's plain forward and its
work counts, found by that name (``reference.py``'s docstring states what
a file in this directory gives; ``work.py`` reads the counts).

Architecture (LongCat-Flash-Chat, huggingface.co/meituan-longcat/
LongCat-Flash-Chat, modeling_longcat_flash.py as it is remembered: there
is no network here, so every line is stated and the configuration's
``assumed`` lists what its ``config.json`` does not itself give).
Pre-norm decoder; ``N`` = RMSNorm with gain 1, eps ``rms_norm_eps``.

- One published layer is a **shortcut-connected double layer**, input
  ``x``: ``a0 = x + MLA_0(N(x))``; ``m = N(a0)``; ``s = MoE(m)`` (held
  back); ``b0 = a0 + FFN_0(m)``; ``a1 = b0 + MLA_1(N(b0))``; ``b1 = a1 +
  FFN_1(N(a1))``; ``out = b1 + s``.  ``FFN_i``: SwiGLU of
  ``ffn_hidden_size``.  Four norms, two attentions, two dense FFNs, one
  router and one expert stack a layer; two cached rows a position.
- ``MLA_i(h)``, multi-head latent attention in the published (expanded)
  form, no absorption and no cache: ``c_q = N(h W_DQ)``; ``q = l_q (c_q
  W_UQ)`` -> heads x (nope | rope), ``l_q = (hidden / q_lora_rank)^1/2``
  where ``mla_scale_q_lora``; ``[c | k_r] = h W_DKV``; ``c = l_kv N(c)``,
  ``l_kv = (hidden / kv_lora_rank)^1/2`` where ``mla_scale_kv_lora`` (so
  the keys' nope part AND the values carry it; ``k_r`` does not);
  ``q_r``, ``k_r`` rotated (one ``k_r`` shared by all heads), plain
  frequencies ``theta^(-2j/d)``, interleaved pairs (2i, 2i+1) written half
  by half; ``[k_n | v] = c W_UKV`` per head; causal softmax of ``q . [k_n
  | k_r] x (nope + rope)^-1/2``; ``o = concat_i(p v_i) W_O``.
- ``MoE(m)``: ``z = m W_r`` in float32 over ``n_routed_experts +
  zero_expert_num`` outputs (real experts first, identity experts last);
  ``sc = softmax(z)`` over all of them; the ``moe_topk`` chosen are the
  top of ``sc + b`` (``b`` = ``e_score_correction_bias``, a buffer of
  zeros at initialisation); ``w_e = routed_scaling_factor x sc_e`` (the
  uncorrected score; not renormalised); ``y = sum_{e chosen, real, held}
  w_e SwiGLU_e(m) + (sum_{e chosen, identity} w_e) m``.

The share (PERF.md section 4): the configuration's file gives, under the
published keys, what THIS chip holds: ``n_routed_experts`` experts
starting at ``deployment.expert_offset`` of the ``published`` count (the
router keeps the published width and every identity expert),
``vocab_size`` rows of the vocabulary, ``num_layers`` double layers.  What
the absent experts would add is left out, and that partial result goes on
to the next layer.  The identity term is computed for every token (every
chip computes it alike for its OWN tokens).

Departures: none in the mathematics.  Weights are random, not trained:
every matrix is N(0, 1/fan_in) from ``jax.random.normal`` in float32,
divided by sqrt(fan_in) (a true division) and rounded to the
configuration's dtype, norm gains 1, the score-correction bias zeros.
Every matrix of the stack is drawn a LAYER at a time under
``fold_in(fold_in(key(seed), slot), layer index)``, the attention and
dense-FFN matrices as one draw of ``[2, ...]`` (both sub-layers); the
embedding and the head whole, under ``fold_in(key(seed), slot)``.  That is
the recipe the served model is documented to use for ``--seed``; it is
restated here.

One device holds everything (the configuration is one chip's share): the
12 288-wide FFNs and the experts go to float32 a block of columns, or an
expert, at a time, attention a block of heads and of queries at a time.
It imports nothing of the program, and no jax until a forward is built:
the counts are plain Python over the configuration's dict.
"""

from __future__ import annotations

from functools import partial

from reference import Q_BLOCK, int8_round, rms_norm

SLOTS = {"embed": 1, "lm_head": 2, "wo": 13, "wq_a": 14, "wq_b": 15,
         "wkv_a": 16, "wkv_b": 17, "w_gate": 20, "w_up": 21, "w_down": 22,
         "router": 23, "wd_gate": 27, "wd_up": 28, "wd_down": 29}
SUB = 2             # attentions (and dense FFNs) a layer
HEAD_BLOCK = 16     # heads per attention block (bounds the score tensor)
FFN_BLOCK = 2048    # columns of a dense FFN in float32 at a time


def sizes(cfg: dict) -> dict:
    held = cfg["n_routed_experts"]
    routed = cfg.get("published", {}).get("n_routed_experts", held)
    D = cfg["hidden_size"]
    return {
        "L": cfg["num_layers"], "D": D, "H": cfg["num_attention_heads"],
        "F": cfg["ffn_hidden_size"], "EF": cfg["expert_ffn_hidden_size"],
        "ql": cfg["q_lora_rank"], "r": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"],
        "q_scale": ((D / cfg["q_lora_rank"]) ** 0.5
                    if cfg.get("mla_scale_q_lora") else 1.0),
        "kv_scale": ((D / cfg["kv_lora_rank"]) ** 0.5
                     if cfg.get("mla_scale_kv_lora") else 1.0),
        "held": held, "routed": routed, "zero": cfg["zero_expert_num"],
        "offset": cfg.get("deployment", {}).get("expert_offset", 0),
        "k": cfg["moe_topk"],
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "V": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
        "theta": float(cfg["rope_theta"]),
        "tied": bool(cfg["tie_word_embeddings"]),
        "dtype": cfg["torch_dtype"],
    }


# ---- the counts ----------------------------------------------------------

def _attention_params(z: dict) -> int:
    return (z["D"] * z["ql"] + z["ql"] * z["H"] * (z["nope"] + z["rope"])
            + z["D"] * (z["r"] + z["rope"])
            + z["r"] * z["H"] * (z["nope"] + z["v"]) + z["H"] * z["v"] * z["D"])


def matmul_params(cfg: dict) -> int:
    """Parameters a token is multiplied through ON THIS CHIP: a layer's
    two attentions and two dense FFNs, its router (over its whole width)
    and the EXPECTED number of held routed experts a token chooses
    (experts per token x held / router width: the identity experts take
    their share of the choices and multiply nothing), and the output
    head over the held vocabulary."""
    z = sizes(cfg)
    width = z["routed"] + z["zero"]
    layer = (SUB * (_attention_params(z) + 3 * z["D"] * z["F"])
             + z["D"] * width
             + z["k"] * z["held"] * 3 * z["D"] * z["EF"] // width)
    return z["L"] * layer + z["D"] * z["V"]


def _attention_flops_per_position(z: dict) -> float:
    """The published (expanded) attention: QK^T over nope + rope and PV
    over the value width, every head, one cache layer."""
    return 2.0 * z["H"] * (z["nope"] + z["rope"] + z["v"])


def token_flops(cfg: dict, context: int, with_head: bool = True) -> float:
    """Forward FLOPs of one token that attends to ``context`` positions."""
    z = sizes(cfg)
    dense = 2.0 * (matmul_params(cfg) - (0 if with_head else z["D"] * z["V"]))
    return dense + SUB * z["L"] * _attention_flops_per_position(z) * context


def prompt_flops(cfg: dict, prompt_len: int) -> float:
    """Prefill of a whole prompt: every token through the layers, the
    head once, causal attention over sum(1..n) positions."""
    z = sizes(cfg)
    dense = (2.0 * (matmul_params(cfg) - z["D"] * z["V"]) * prompt_len
             + 2.0 * z["D"] * z["V"])
    return dense + (SUB * z["L"] * _attention_flops_per_position(z)
                    * prompt_len * (prompt_len + 1) / 2.0)


def kv_bytes_per_position(cfg: dict, kv_dtype_bytes: int = 2) -> int:
    """Bytes one cached position holds, all cache layers: one latent row
    (the compressed KV and the shared rope key) an attention, two
    attentions a layer."""
    z = sizes(cfg)
    return SUB * z["L"] * (z["r"] + z["rope"]) * kv_dtype_bytes


def decode_kv_bytes(cfg: dict, contexts: list[int]) -> float:
    return float(kv_bytes_per_position(cfg)) * float(sum(contexts))


def decode_attn_flops(cfg: dict, contexts: list[int]) -> float:
    """FLOPs of attending one token at each of ``contexts`` straight over
    latent rows (the absorbed form): per head a score over rank + rope
    and values over rank, every cache layer."""
    z = sizes(cfg)
    per_position = 2.0 * z["H"] * (z["r"] + z["rope"] + z["r"])
    return SUB * z["L"] * per_position * float(sum(contexts))


# ---- the weights ---------------------------------------------------------

def stack_shapes(z: dict) -> dict:
    """name -> (one layer's shape, fan_in)."""
    D, H, F = z["D"], z["H"], z["F"]
    twice = {"wq_a": ((D, z["ql"]), D),
             "wq_b": ((z["ql"], H * (z["nope"] + z["rope"])), z["ql"]),
             "wkv_a": ((D, z["r"] + z["rope"]), D),
             "wkv_b": ((z["r"], H * (z["nope"] + z["v"])), z["r"]),
             "wo": ((H * z["v"], D), H * z["v"]),
             "wd_gate": ((D, F), D), "wd_up": ((D, F), D),
             "wd_down": ((F, D), F)}
    out = {name: ((SUB, *shape), fan_in)
           for name, (shape, fan_in) in twice.items()}
    E, EF = z["held"], z["EF"]
    out.update(router=((D, z["routed"] + z["zero"]), D),
               w_gate=((E, D, EF), D), w_up=((E, D, EF), D),
               w_down=((E, EF, D), EF))
    return out


def make_weights(z: dict, seed: int, device):
    """(the layers' stack, embed, head) on ``device``: each stacked
    matrix is filled a layer at a time, in place, so the float32 draw in
    flight is one layer's."""
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

    with jax.default_device(device):
        layers = {}
        for name, (shape, fan_in) in stack_shapes(z).items():
            k_m = jax.random.fold_in(root, SLOTS[name])
            buf = jnp.zeros((z["L"], *shape), dtype)
            for i in range(z["L"]):
                buf = draw_into(buf, i, jax.random.fold_in(k_m, i),
                                jnp.sqrt(fan_in), shape)
            layers[name] = buf
        embed = draw(jax.random.fold_in(root, SLOTS["embed"]),
                     jnp.sqrt(z["D"]), (z["V"], z["D"]))
        head = None
        if not z["tied"]:
            head = draw(jax.random.fold_in(root, SLOTS["lm_head"]),
                        jnp.sqrt(z["D"]), (z["D"], z["V"]))
    return layers, embed, head


# ---- the forward ---------------------------------------------------------

def rope(z: dict, x, positions):
    """x [S, heads, rope]: pairs (2i, 2i+1) rotated by plain frequencies,
    written half by half."""
    import jax.numpy as jnp

    d = z["rope"]
    inv = 1.0 / (z["theta"] ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
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
    c_kv = z["kv_scale"] * rms_norm(ckv[:, :r], z["eps"])
    k_r = rope(z, ckv[:, None, r:], pos)  # [S, 1, rope]: one for all heads
    hb = min(HEAD_BLOCK, H)
    w_uq = w_of("wq_b").reshape(-1, H // hb, hb, nope + rp)
    w_ukv = w_of("wkv_b").reshape(r, H // hb, hb, nope + v)
    w_o = w_of("wo").reshape(H // hb, hb * v, -1)
    scale = (nope + rp) ** -0.5
    nb = S // Q_BLOCK
    t = jnp.arange(S)

    def head_block(o, ws):
        uq, ukv, wo = ws  # [ql, hb, nope+rope], [r, hb, nope+v], [hb*v, D]
        q = z["q_scale"] * jnp.einsum("sc,chd->shd", c_q, uq)
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


def route(z: dict, h, router, bias):
    """Weights [S, routed + zero] of the experts each token chose (0
    elsewhere): chosen by the bias-corrected score, weighted by the
    uncorrected one."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    scores = jax.nn.softmax(h @ router, axis=-1)
    _, idx = lax.top_k(scores + bias, z["k"])
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(
        scores[rows, idx] * z["routed_scale"])


def swiglu(h, gate, up, down):
    import jax

    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def dense_ffn(z: dict, quant: bool, h, gate, up, down):
    """SwiGLU of the whole width, ``FFN_BLOCK`` columns in float32 at a
    time (an int8 scale is a whole output channel's: the down matrix's
    spans the blocks, so it is taken first)."""
    import jax.numpy as jnp
    from jax import lax

    D, F = gate.shape
    nb = max(1, F // FFN_BLOCK)
    scale = None
    if quant:
        amax = jnp.max(jnp.abs(down.astype(jnp.float32)), axis=0)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)

    def block(y, ws):
        g, u, d = (w.astype(jnp.float32) for w in ws)
        if quant:
            g, u = int8_round(g, 0), int8_round(u, 0)
            d = jnp.clip(jnp.round(d / scale), -127, 127) * scale
        return y + swiglu(h, g, u, d), None

    y, _ = lax.scan(block, jnp.zeros_like(h), (
        jnp.moveaxis(gate.reshape(D, nb, F // nb), 1, 0),
        jnp.moveaxis(up.reshape(D, nb, F // nb), 1, 0),
        down.reshape(nb, F // nb, D)))
    return y


def expert_layer(z: dict, quant: bool, layer, router, bias, h):
    """The held experts' part of the layer, every held expert over every
    token and weighted by the router's choice (0 where not chosen), one
    expert at a time; plus the identity experts' term."""
    import jax.numpy as jnp
    from jax import lax

    weights = route(z, h, router, bias)
    mine = lax.dynamic_slice_in_dim(weights, z["offset"], z["held"], axis=1)

    def one(y, ws):
        gate, up, down, w_e = ws
        f = [m.astype(jnp.float32) for m in (gate, up, down)]
        if quant:
            f = [int8_round(m, 0) for m in f]
        return y + w_e[:, None] * swiglu(h, *f), None

    y, _ = lax.scan(one, jnp.zeros_like(h),
                    (layer["w_gate"], layer["w_up"], layer["w_down"], mine.T))
    identity = jnp.sum(weights[:, z["routed"]:], axis=1)
    return y + identity[:, None] * h


def layers_forward(z: dict, quant: bool, x, layers, bias):
    """x [S, D] float32 through the double layers (one scan); ``bias``
    [L, routed + zero]: each layer's score-correction bias."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def body(x, inputs):
        layer, b = inputs

        def sub(i):
            def w_of(name):
                w = layer[name][i].astype(jnp.float32)
                return int8_round(w, 0) if quant else w
            return w_of

        router = layer["router"].astype(jnp.float32)
        if quant:
            router = int8_round(router, 0)
        a0 = x + attention(z, sub(0), rms_norm(x, z["eps"]))
        m = rms_norm(a0, z["eps"])
        s = expert_layer(z, quant, layer, router, b, m)  # held back
        b0 = a0 + dense_ffn(z, quant, m, layer["wd_gate"][0],
                            layer["wd_up"][0], layer["wd_down"][0])
        a1 = b0 + attention(z, sub(1), rms_norm(b0, z["eps"]))
        b1 = a1 + dense_ffn(z, quant, rms_norm(a1, z["eps"]),
                            layer["wd_gate"][1], layer["wd_up"][1],
                            layer["wd_down"][1])
        return b1 + s, None

    with jax.default_matmul_precision("highest"):
        x, _ = lax.scan(body, x, (layers, bias))
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
        import jax
        import jax.numpy as jnp

        self.z = sizes(cfg)
        self.device = devices[0]
        self.layers, self.embed, head = make_weights(
            self.z, seed, self.device)
        self.head = self.embed if self.z["tied"] else head
        # e_score_correction_bias: a buffer, zeros at initialisation
        self.router_bias = jax.device_put(jnp.zeros(
            (self.z["L"], self.z["routed"] + self.z["zero"]), jnp.float32),
            self.device)
        self._fns: dict = {}

    def _fn(self, what: str, quant: bool):
        import jax

        key = (what, quant)
        if key not in self._fns:
            f = {"embed": partial(embed_tokens, quant),
                 "layers": partial(layers_forward, self.z, quant)}[what]
            self._fns[key] = jax.jit(f)
        return self._fns[key]

    def hidden(self, padded, quant: bool):
        import jax
        import jax.numpy as jnp

        x = self._fn("embed", quant)(
            self.embed, jax.device_put(jnp.asarray(padded), self.device))
        return self._fn("layers", quant)(x, self.layers, self.router_bias)
