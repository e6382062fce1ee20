"""``model_type`` "smallthinker": the architecture's plain forward and its
work counts, found by that name (``reference.py``'s docstring states what
a file in this directory gives; ``work.py`` reads the counts).

Architecture (SmallThinker-21BA3B-Instruct, huggingface.co/PowerInfer/
SmallThinker-21BA3B-Instruct, arXiv:2507.20984; modeling_smallthinker.py
as it is remembered: there is no network here, so every line is stated
and the configuration's ``assumed`` lists what its ``config.json`` does
not itself give).  Pre-norm decoder; ``N`` = RMSNorm with gain 1, eps
``rms_norm_eps``.  Layer ``l``, input ``x`` [S, hidden]:

- ``win_l = sliding_window_layout[l]``, ``rope_l = rope_layout[l]`` (both
  ``[0, 1, 1, 1]`` repeated: the first layer of a period of four is full
  causal attention with NO positional encoding, the three after it are
  rotary and see a window).
- **Routing, first:** ``z = x W_r`` in float32 over
  ``moe_num_primary_experts`` outputs, from the RAW layer input ``x``
  (not normed: the router sits before attention); the
  ``moe_num_active_primary_experts`` chosen are the top of ``z``; ``w =
  softmax(z_chosen)`` over the chosen alone.
- ``h = N(x)``; ``q = h W_q`` -> heads x head_dim, ``k = h W_k``, ``v = h
  W_v`` -> KV heads x head_dim; where ``rope_l``: q and k rotated,
  half-rotation (NeoX) layout, theta ``rope_theta``; else nothing is
  added.  ``p = softmax(q_i . k_g(i) x head_dim^-1/2)`` over positions
  ``j <= i`` and, where ``win_l``, also ``j > i - sliding_window_size``
  (a query sees the previous ``sliding_window_size`` positions, itself
  included).  ``a = x + concat_i(p v) W_o``.
- ``m = N(a)``; ``out = a + sum_{e chosen} w_e W_down,e (relu(W_gate,e m)
  * (W_up,e m))``, expert width ``moe_ffn_hidden_size``.  No shared
  expert, no capacity, nothing dropped.
- Embedding plain; final norm and the untied head are the judge's
  (``reference.py``).

The cut (PERF.md section 4) is depth alone: ``num_hidden_layers`` whole
periods of the published 52, every expert and the whole vocabulary.

Departures: none in the mathematics.  Weights are random, not trained:
every matrix is N(0, 1/fan_in) from ``jax.random.normal`` in float32,
divided by sqrt(fan_in) (a true division) and rounded to the
configuration's dtype, norm gains 1.  Every matrix of the stack is drawn
a LAYER at a time under ``fold_in(fold_in(key(seed), slot), layer
index)`` (an expert stack ``[experts, ...]`` as one draw); the embedding
and the head whole, under ``fold_in(key(seed), slot)``.  The router is
read in float32, its values the rounded draw's.  That is the recipe the
served model is documented to use for ``--seed``; it is restated here.

One device holds everything: attention goes a KV head's group of query
heads and ``Q_BLOCK`` queries at a time with the band mask written out,
the experts to float32 one at a time, every expert over every token and
weighted by the router's choice (0 where not chosen).  It imports nothing
of the program, and no jax until a forward is built: the counts are
plain Python over the configuration's dict.
"""

from __future__ import annotations

import math
from functools import partial

from reference import Q_BLOCK, int8_round, rms_norm

SLOTS = {"embed": 1, "lm_head": 2, "wo": 13, "w_gate": 20, "w_up": 21,
         "w_down": 22, "router": 23, "wq": 30, "wk": 31, "wv": 32}


def sizes(cfg: dict) -> dict:
    L = cfg["num_hidden_layers"]
    return {
        "L": L, "D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
        "KV": cfg["num_key_value_heads"], "Hd": cfg["head_dim"],
        "E": cfg["moe_num_primary_experts"],
        "k": cfg["moe_num_active_primary_experts"],
        "EF": cfg["moe_ffn_hidden_size"],
        "W": cfg["sliding_window_size"],
        "windowed": [bool(w) for w in cfg["sliding_window_layout"][:L]],
        "rotary": [bool(r) for r in cfg["rope_layout"][:L]],
        "V": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
        "theta": float(cfg["rope_theta"]),
        "tied": bool(cfg["tie_word_embeddings"]),
        "dtype": cfg["torch_dtype"],
    }


# ---- the counts ----------------------------------------------------------

def matmul_params(cfg: dict) -> int:
    """Parameters a token is multiplied through: a layer's attention
    matrices, its router and the experts the token chooses, and the
    output head (the embedding lookup is a gather)."""
    z = sizes(cfg)
    attention = z["D"] * (z["H"] + 2 * z["KV"]) * z["Hd"] + z["H"] * z["Hd"] * z["D"]
    layer = attention + z["D"] * z["E"] + z["k"] * 3 * z["D"] * z["EF"]
    return z["L"] * layer + z["D"] * z["V"]


def _attended(z: dict, context: int) -> int:
    """Positions one token at ``context`` attends to, summed over the
    layers: the whole context in a full layer, the window's reach in a
    windowed one."""
    return sum(min(context, z["W"]) if w else context for w in z["windowed"])


def token_flops(cfg: dict, context: int, with_head: bool = True) -> float:
    """Forward FLOPs of one token that attends to ``context`` positions:
    2 per multiply-add through the matrices, plus QK^T and PV over what
    each layer's kind lets it see."""
    z = sizes(cfg)
    dense = 2.0 * (matmul_params(cfg) - (0 if with_head else z["D"] * z["V"]))
    return dense + 4.0 * z["H"] * z["Hd"] * _attended(z, context)


def prompt_flops(cfg: dict, prompt_len: int) -> float:
    """Prefill of a whole prompt: every token through the layers, the
    head once, attention over the causal triangle in a full layer and
    over the BAND's area in a windowed one."""
    z = sizes(cfg)
    n, W = prompt_len, z["W"]
    triangle = n * (n + 1) / 2.0
    band = triangle if n <= W else W * (W + 1) / 2.0 + (n - W) * W
    area = sum(band if w else triangle for w in z["windowed"])
    dense = (2.0 * (matmul_params(cfg) - z["D"] * z["V"]) * n
             + 2.0 * z["D"] * z["V"])
    return dense + 4.0 * z["H"] * z["Hd"] * area


def kv_bytes_per_position(cfg: dict, kv_dtype_bytes: int = 2) -> int:
    """Bytes of keys and values one cached position holds, all layers."""
    z = sizes(cfg)
    return 2 * z["L"] * z["KV"] * z["Hd"] * kv_dtype_bytes


def _layer_position_bytes(z: dict) -> int:
    return 2 * z["KV"] * z["Hd"] * 2


def decode_kv_bytes(cfg: dict, contexts: list[int]) -> float:
    """Bytes of cache that decoding one token at each of ``contexts``
    must read at the least: the whole context in every full layer, the
    window's reach in every windowed one."""
    z = sizes(cfg)
    return float(_layer_position_bytes(z)) * float(
        sum(_attended(z, c) for c in contexts))


def decode_window_kv_bytes(cfg: dict, contexts: list[int]) -> float:
    """The windowed layers' part of :func:`decode_kv_bytes`."""
    z = sizes(cfg)
    n_windowed = sum(z["windowed"])
    return float(_layer_position_bytes(z)) * float(
        sum(n_windowed * min(c, z["W"]) for c in contexts))


# ---- the weights ---------------------------------------------------------

def stack_shapes(z: dict) -> dict:
    """name -> (one layer's shape, fan_in)."""
    D, H, KV, Hd, E, EF = z["D"], z["H"], z["KV"], z["Hd"], z["E"], z["EF"]
    return {"wq": ((D, H * Hd), D), "wk": ((D, KV * Hd), D),
            "wv": ((D, KV * Hd), D), "wo": ((H * Hd, D), H * Hd),
            "router": ((D, E), D),
            "w_gate": ((E, D, EF), D), "w_up": ((E, D, EF), D),
            "w_down": ((E, EF, D), EF)}


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

def rope(x, positions, theta):
    """x [S, heads, Hd], rotate-half layout."""
    import jax.numpy as jnp

    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window):
    """Softmax attention of one sequence, q [S, H, Hd], k/v [S, KV, Hd],
    a KV head's group of query heads and ``Q_BLOCK`` queries at a time.
    The mask is written out: position ``j`` is seen from ``i`` where ``j
    <= i`` and, with a ``window``, ``j > i - window``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    S, H, Hd = q.shape
    KV = k.shape[1]
    G = H // KV
    nb = S // Q_BLOCK
    t = jnp.arange(S)

    def kv_head(args):
        qh, kh, vh = args  # [S, G, Hd], [S, Hd], [S, Hd]

        def q_block(blk):
            qi, b = blk
            at = b * Q_BLOCK + jnp.arange(Q_BLOCK)
            seen = t[None, :] <= at[:, None]
            if window is not None:
                seen = seen & (t[None, :] > at[:, None] - window)
            s = jnp.einsum("qgd,td->gqt", qi, kh) / math.sqrt(Hd)
            s = jnp.where(seen[None], s, -jnp.inf)
            return jnp.einsum("gqt,td->qgd", jax.nn.softmax(s, axis=-1), vh)

        out = lax.map(q_block, (qh.reshape(nb, Q_BLOCK, G, Hd),
                                jnp.arange(nb)))
        return out.reshape(S, G, Hd)

    out = lax.map(kv_head, (
        jnp.moveaxis(q.reshape(S, KV, G, Hd), 1, 0),
        jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))  # [KV, S, G, Hd]
    return jnp.moveaxis(out, 0, 1).reshape(S, H * Hd)


def route(z: dict, x, router):
    """Weights [S, E] of the experts each token chose (0 elsewhere): the
    top of the logits, a softmax over the chosen alone."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    logits = x @ router
    top, idx = lax.top_k(logits, z["k"])
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, idx].set(
        jax.nn.softmax(top, axis=-1))


def expert_layer(quant: bool, layer, weights, m):
    """Every expert over every token, weighted by the router's choice (0
    where not chosen), one expert in float32 at a time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def one(y, ws):
        gate, up, down, w_e = ws
        f = [w.astype(jnp.float32) for w in (gate, up, down)]
        if quant:
            f = [int8_round(w, 0) for w in f]
        act = jax.nn.relu(m @ f[0]) * (m @ f[1])
        return y + w_e[:, None] * (act @ f[2]), None

    y, _ = lax.scan(one, jnp.zeros_like(m),
                    (layer["w_gate"], layer["w_up"], layer["w_down"],
                     weights.T))
    return y


def layer_forward(z: dict, quant: bool, windowed: bool, rotary: bool, x,
                  layer):
    """One layer of the given kind: x [S, D] float32."""
    import jax.numpy as jnp

    S = x.shape[0]
    pos = jnp.arange(S)
    H, KV, Hd = z["H"], z["KV"], z["Hd"]

    def w_of(name):
        w = layer[name].astype(jnp.float32)
        return int8_round(w, 0) if quant else w

    weights = route(z, x, w_of("router"))  # from the RAW input, first
    h = rms_norm(x, z["eps"])
    q = (h @ w_of("wq")).reshape(S, H, Hd)
    k = (h @ w_of("wk")).reshape(S, KV, Hd)
    v = (h @ w_of("wv")).reshape(S, KV, Hd)
    if rotary:
        q, k = rope(q, pos, z["theta"]), rope(k, pos, z["theta"])
    a = x + attention(q, k, v, z["W"] if windowed else None) @ w_of("wo")
    return a + expert_layer(quant, layer, weights, rms_norm(a, z["eps"]))


def layers_forward(z: dict, quant: bool, x, layers):
    """x [S, D] float32 through the layers, each traced with its own
    kind (the published layouts, layer by layer)."""
    import jax

    with jax.default_matmul_precision("highest"):
        for l in range(z["L"]):
            x = layer_forward(z, quant, z["windowed"][l], z["rotary"][l], x,
                              {name: w[l] for name, w in layers.items()})
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
        self.layers, self.embed, head = make_weights(
            self.z, seed, self.device)
        self.head = self.embed if self.z["tied"] else head
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
        return self._fn("layers", quant)(x, self.layers)
