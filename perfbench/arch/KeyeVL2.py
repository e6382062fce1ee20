"""``model_type`` "KeyeVL2": the architecture's plain forward and its work
counts, found by that name (``reference.py``'s docstring states what a
file in this directory gives; ``work.py`` reads the counts).

Architecture: the language model of Keye-VL-2.0-30B-A3B
(huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B config.json): Qwen3-30B-A3B's
decoder with a DeepSeek-Sparse-Attention indexer in every layer.  The
configuration's ``assumed`` lists what its config.json does not itself
give; every line is stated here.  Pre-norm decoder; ``N`` = RMSNorm with
gain 1, eps ``rms_norm_eps``.  Layer ``l``, input ``x`` [S, hidden]:

- ``h = N(x)``; ``q = RoPE(N_q(h W_q))`` -> heads x head_dim, ``k =
  RoPE(N_k(h W_k))``, ``v = h W_v`` -> KV heads x head_dim; QK-norm per
  head; rotary in the half-rotation (NeoX) layout at ``rope_theta``.  Text
  positions: the three M-RoPE sections (``mrope_section``) carry the same
  position, which is 1-D rotary over the whole head_dim.
- The indexer (``sa_config``; DeepSeek-V3.2-Exp's published indexer,
  without its Hadamard rotation and fp8 cache: bfloat16 served, float32
  here): ``qI_j = RoPE'(h WIQ)_j`` for the ``indexer_num_heads`` heads of
  ``indexer_head_dim``; ``kI = RoPE'(LN(h WIK))``, the one indexer key
  head (LayerNorm with gain 1 and bias 0, eps ``rms_norm_eps``); ``RoPE'``
  rotates the leading half of the indexer dims (half-rotation layout, the
  same theta); ``w = h Ww / sqrt(indexer_num_heads)``; ``I[t, s] = sum_j
  w_j[t] relu(qI_j[t] . kI[s] / sqrt(indexer_head_dim))`` for ``s <= t``.
- ``S_t``: the ``topk`` positions ``s <= t`` of highest ``I[t, s]``, equal
  scores to the lower position; every ``s <= t``
  while ``t < topk``.  ``q_chunk_size`` / ``kv_chunk_size`` are read as
  computation tiling and change nothing here.
- ``a = x + concat_i(softmax_{s in S_t}(q_i . k_g(i),s / sqrt(head_dim))
  v_g(i),s) W_o``.
- ``m = N(a)``; router ``z = m W_r`` in float32 over ``num_experts``
  outputs, the ``num_experts_per_tok`` chosen are the top of ``z``, their
  weights a softmax over the chosen alone (``norm_topk_prob``); ``out = a
  + sum_e w_e W_down,e(silu(W_gate,e m) * (W_up,e m))``, expert width
  ``moe_intermediate_size``.  No shared expert, no capacity, nothing
  dropped.
- Embedding plain; final norm and the untied head are the judge's
  (``reference.py``).  The vision tower is not here: text only.

The cut (PERF.md section 4) is depth alone: ``num_hidden_layers`` of the
published 48, every expert and the whole vocabulary.

Weights are random, not trained: every matrix is N(0, 1/fan_in) from
``jax.random.normal`` in float32, divided by sqrt(fan_in) (a true
division) and rounded to the configuration's dtype, norm gains 1 and the
indexer key's LayerNorm bias 0.  Every matrix of the stack is drawn a
LAYER at a time under ``fold_in(fold_in(key(seed), slot), layer index)``
(an expert stack ``[experts, ...]`` as one draw); the embedding and the
head whole, under ``fold_in(key(seed), slot)``.  The router is read in
float32, its values the rounded draw's.  That is the recipe the served
model is documented to use for ``--seed``; it is restated here.

One device holds everything.  A sequence goes ``Q_BLOCK`` queries at a
time: the indexer's scores over the whole padded sequence (a head at a
time, summed), the selection as the k-th score found by bisection over
its bits and the equal scores taken in position order
(:func:`top_positions`), then each query head's softmax over the chosen
positions with the mask written out.  The experts go over the tokens routed to
them alone: assignments sorted by expert and cut into tiles of
``EXPERT_TILE`` rows, each tile through the experts its rows hold, one
expert in float32 at a time.  It imports nothing of the program, and no
jax until a forward is built: the counts are plain Python over the
configuration's dict.
"""

from __future__ import annotations

from functools import partial

from reference import Q_BLOCK, int8_round, rms_norm

SLOTS = {"embed": 1, "lm_head": 2, "wo": 13, "w_gate": 20, "w_up": 21,
         "w_down": 22, "router": 23, "wq": 30, "wk": 31, "wv": 32,
         "wiq": 40, "wik": 41, "ww": 42}
TOKEN_BLOCK = 8192   # tokens routed together (bounds the sorted rows)
EXPERT_TILE = 512    # sorted assignment rows an expert pass computes


def sizes(cfg: dict) -> dict:
    sa = cfg["sa_config"]
    return {
        "L": cfg["num_hidden_layers"], "D": cfg["hidden_size"],
        "H": cfg["num_attention_heads"], "KV": cfg["num_key_value_heads"],
        "Hd": cfg["head_dim"], "E": cfg["num_experts"],
        "k": cfg["num_experts_per_tok"], "EF": cfg["moe_intermediate_size"],
        "HI": sa["indexer_num_heads"], "Di": sa["indexer_head_dim"],
        "K": sa["topk"], "V": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
        "theta": float(cfg["rope_theta"]),
        "tied": bool(cfg["tie_word_embeddings"]),
        "dtype": cfg["torch_dtype"],
    }


# ---- the counts ----------------------------------------------------------

def _indexer_params(z: dict) -> int:
    return z["D"] * (z["HI"] * z["Di"] + z["Di"] + z["HI"])


def matmul_params(cfg: dict) -> int:
    """Parameters a token is multiplied through: a layer's attention and
    indexer matrices, its router and the experts the token chooses, and
    the output head (the embedding lookup is a gather)."""
    z = sizes(cfg)
    attention = (z["D"] * (z["H"] + 2 * z["KV"]) * z["Hd"]
                 + z["H"] * z["Hd"] * z["D"])
    layer = (attention + _indexer_params(z) + z["D"] * z["E"]
             + z["k"] * 3 * z["D"] * z["EF"])
    return z["L"] * layer + z["D"] * z["V"]


def _index_flops_per_position(z: dict) -> float:
    """One query against one cached position, every indexer head: the
    dot (2 Di) and the weighted ReLU sum (2)."""
    return z["HI"] * (2.0 * z["Di"] + 2.0)


def indexer_flops(cfg: dict, contexts: list[int]) -> float:
    """The indexer over the whole context of a query at each of
    ``contexts``, every layer."""
    z = sizes(cfg)
    return z["L"] * _index_flops_per_position(z) * float(sum(contexts))


def indexer_bytes(cfg: dict, contexts: list[int]) -> float:
    """Indexer keys a query at each of ``contexts`` must read, every
    layer (one ``Di``-wide key a position, 2 bytes a value)."""
    z = sizes(cfg)
    return z["L"] * z["Di"] * 2.0 * float(sum(contexts))


def prompt_indexer_flops(cfg: dict, prompt_len: int) -> float:
    """The indexer over a whole prompt's causal triangle, every layer."""
    z = sizes(cfg)
    n = prompt_len
    return z["L"] * _index_flops_per_position(z) * n * (n + 1) / 2.0


def token_flops(cfg: dict, context: int, with_head: bool = True) -> float:
    """Forward FLOPs of one token at ``context``: 2 per multiply-add
    through the matrices, the indexer over the whole context, QK^T and PV
    over the chosen ``min(context, topk)`` positions."""
    z = sizes(cfg)
    dense = 2.0 * (matmul_params(cfg) - (0 if with_head else z["D"] * z["V"]))
    return (dense + z["L"] * _index_flops_per_position(z) * context
            + 4.0 * z["L"] * z["H"] * z["Hd"] * min(context, z["K"]))


def _chosen_sum(n: int, K: int) -> float:
    """sum over t < n of min(t + 1, K)."""
    m = min(n, K)
    return m * (m + 1) / 2.0 + max(0, n - K) * K


def prompt_flops(cfg: dict, prompt_len: int) -> float:
    """Prefill of a whole prompt: every token through the layers, the
    head once, the indexer over the causal triangle, attention over each
    token's chosen positions."""
    z = sizes(cfg)
    n = prompt_len
    dense = (2.0 * (matmul_params(cfg) - z["D"] * z["V"]) * n
             + 2.0 * z["D"] * z["V"])
    return (dense + prompt_indexer_flops(cfg, n)
            + 4.0 * z["L"] * z["H"] * z["Hd"] * _chosen_sum(n, z["K"]))


def kv_bytes_per_position(cfg: dict, kv_dtype_bytes: int = 2) -> int:
    """Bytes one cached position holds, all layers: keys and values, and
    the one indexer key."""
    z = sizes(cfg)
    return z["L"] * (2 * z["KV"] * z["Hd"] + z["Di"]) * kv_dtype_bytes


def sparse_attn_bytes(cfg: dict, contexts: list[int]) -> float:
    """K/V rows of the ``min(context, topk)`` chosen positions a query at
    each of ``contexts`` must read, every layer."""
    z = sizes(cfg)
    return (z["L"] * 2.0 * z["KV"] * z["Hd"] * 2.0
            * float(sum(min(c, z["K"]) for c in contexts)))


def decode_kv_bytes(cfg: dict, contexts: list[int]) -> float:
    """Bytes of cache that decoding one token at each of ``contexts``
    must read at the least: the indexer keys of the whole context and the
    K/V rows of the chosen positions."""
    return indexer_bytes(cfg, contexts) + sparse_attn_bytes(cfg, contexts)


# ---- the weights ---------------------------------------------------------

def stack_shapes(z: dict) -> dict:
    """name -> (one layer's shape, fan_in)."""
    D, H, KV, Hd, E, EF = z["D"], z["H"], z["KV"], z["Hd"], z["E"], z["EF"]
    return {"wq": ((D, H * Hd), D), "wk": ((D, KV * Hd), D),
            "wv": ((D, KV * Hd), D), "wo": ((H * Hd, D), H * Hd),
            "wiq": ((D, z["HI"] * z["Di"]), D), "wik": ((D, z["Di"]), D),
            "ww": ((D, z["HI"]), D), "router": ((D, E), D),
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
    """x [S, heads, d], rotate-half layout over all of d."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def index_rope(x, positions, theta):
    """Rotary on the leading half of the indexer dims."""
    import jax.numpy as jnp

    r = x.shape[-1] // 2
    return jnp.concatenate([rope(x[..., :r], positions, theta), x[..., r:]],
                           -1)


def layer_norm(x, eps):
    import jax.numpy as jnp
    from jax import lax

    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def top_positions(score, K: int):
    """[Q, S] bool: the ``K`` largest of each row of ``score`` (float32,
    no NaN, no -0.0), equal scores to the lower position.  The K-th
    largest value is found by bisection over the scores as ordered uint32
    (the largest ``t`` that ``K`` or more keys reach, decided from the top
    bit down), then the equal ones are taken in position order."""
    import jax.numpy as jnp
    from jax import lax

    u = lax.bitcast_convert_type(score, jnp.uint32)
    key = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))
    t = jnp.zeros((score.shape[0], 1), jnp.uint32)
    for b in range(31, -1, -1):
        cand = t | jnp.uint32(1 << b)
        reach = jnp.sum(key >= cand, axis=-1, keepdims=True) >= K
        t = jnp.where(reach, cand, t)
    above = key > t
    equal = key == t
    need = K - jnp.sum(above, axis=-1, keepdims=True)
    return above | (equal & (jnp.cumsum(equal, axis=-1) <= need))


def sparse_attention(z: dict, q, k, v, q_i, w, k_i):
    """Attention of one sequence over each query's chosen positions:
    q [S, H, Hd], k/v [S, KV, Hd], the indexer's q_i [S, HI, Di], w [S,
    HI], k_i [S, Di]; ``Q_BLOCK`` queries at a time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    S, H, Hd = q.shape
    G = H // z["KV"]
    nb = S // Q_BLOCK
    t = jnp.arange(S)

    def q_block(blk):
        qb, qib, wb, b = blk  # [Qb, H, Hd], [Qb, HI, Di], [Qb, HI]
        at = b * Q_BLOCK + jnp.arange(Q_BLOCK)
        seen = t[None, :] <= at[:, None]  # [Qb, S]

        def head(acc, hw):
            qh, wh = hw  # [Qb, Di], [Qb]
            s = jnp.maximum(qh @ k_i.T / jnp.sqrt(float(z["Di"])), 0.0)
            return acc + wh[:, None] * s, None

        score, _ = lax.scan(head, jnp.zeros((Q_BLOCK, S), jnp.float32),
                            (jnp.moveaxis(qib, 1, 0), wb.T))
        score = jnp.where(seen, jnp.where(score == 0, 0.0, score), -jnp.inf)
        if S > z["K"]:
            chosen = top_positions(score, z["K"]) & seen
        else:
            chosen = seen

        def attend(_, hq):
            i, qh = hq  # [Qb, Hd]
            g = i // G
            s = (qh @ lax.dynamic_index_in_dim(k, g, 1, keepdims=False).T
                 / jnp.sqrt(float(Hd)))
            p = jax.nn.softmax(jnp.where(chosen, s, -jnp.inf), axis=-1)
            return None, p @ lax.dynamic_index_in_dim(v, g, 1, keepdims=False)

        _, out = lax.scan(attend, None, (jnp.arange(H), jnp.moveaxis(qb, 1, 0)))
        return jnp.moveaxis(out, 0, 1)  # [Qb, H, Hd]

    out = lax.map(q_block, (q.reshape(nb, Q_BLOCK, H, Hd),
                            q_i.reshape(nb, Q_BLOCK, *q_i.shape[1:]),
                            w.reshape(nb, Q_BLOCK, -1), jnp.arange(nb)))
    return out.reshape(S, H * Hd)


def route(z: dict, x, router):
    """(experts [S, k], weights [S, k]): the top of the logits, a softmax
    over the chosen alone."""
    import jax
    from jax import lax

    top, idx = lax.top_k(x @ router, z["k"])
    return idx, jax.nn.softmax(top, axis=-1)


def expert_layer(z: dict, quant: bool, layer, m):
    """Each expert over the tokens routed to it alone, ``TOKEN_BLOCK``
    tokens at a time: their assignments sorted by expert, cut into tiles
    of ``EXPERT_TILE`` rows, and each tile through every expert that
    rows of it chose (one expert in float32 at a time), the rows of the
    other experts masked; the weighted results summed back per token."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    S, D = m.shape
    k = z["k"]
    tb = min(S, TOKEN_BLOCK)
    A = tb * k
    n_tiles = A // EXPERT_TILE

    def ffn(e, rows):
        f = [lax.dynamic_index_in_dim(layer[n], e, 0, keepdims=False
                                      ).astype(jnp.float32)
             for n in ("w_gate", "w_up", "w_down")]
        if quant:
            f = [int8_round(w, 0) for w in f]
        return (jax.nn.silu(rows @ f[0]) * (rows @ f[1])) @ f[2]

    def token_block(mb):
        ids, weights = route(z, mb, layer["router"])
        flat = ids.reshape(A)
        order = jnp.argsort(flat, stable=True)
        experts = flat[order]
        xs = mb[order // k]  # [A, D] rows in expert order

        def tile(i, ys):
            rows = lax.dynamic_slice_in_dim(xs, i * EXPERT_TILE, EXPERT_TILE)
            held = lax.dynamic_slice_in_dim(experts, i * EXPERT_TILE,
                                            EXPERT_TILE)

            def one(e, acc):
                return jnp.where((held == e)[:, None], ffn(e, rows), acc)

            out = lax.fori_loop(held[0], held[-1] + 1, one,
                                jnp.zeros_like(rows))
            return lax.dynamic_update_slice_in_dim(ys, out, i * EXPERT_TILE, 0)

        ys = lax.fori_loop(0, n_tiles, tile, jnp.zeros_like(xs))
        ys = ys * weights.reshape(A)[order][:, None]
        return jnp.zeros_like(mb).at[order // k].add(ys)

    out = lax.map(token_block, m.reshape(S // tb, tb, D))
    return out.reshape(S, D)


def layer_forward(z: dict, quant: bool, x, layer):
    """One layer: x [S, D] float32."""
    import jax.numpy as jnp

    S = x.shape[0]
    pos = jnp.arange(S)
    H, KV, Hd, HI, Di = z["H"], z["KV"], z["Hd"], z["HI"], z["Di"]

    def w_of(name):
        w = layer[name].astype(jnp.float32)
        return int8_round(w, 0) if quant else w

    h = rms_norm(x, z["eps"])
    q = rope(rms_norm((h @ w_of("wq")).reshape(S, H, Hd), z["eps"]), pos,
             z["theta"])
    k = rope(rms_norm((h @ w_of("wk")).reshape(S, KV, Hd), z["eps"]), pos,
             z["theta"])
    v = (h @ w_of("wv")).reshape(S, KV, Hd)
    q_i = index_rope((h @ w_of("wiq")).reshape(S, HI, Di), pos, z["theta"])
    k_i = index_rope(layer_norm(h @ w_of("wik"), z["eps"])[:, None, :], pos,
                     z["theta"])[:, 0]
    w = (h @ w_of("ww")) / jnp.sqrt(float(HI))
    a = x + sparse_attention(z, q, k, v, q_i, w, k_i) @ w_of("wo")
    experts = {n: layer[n] for n in ("w_gate", "w_up", "w_down")}
    experts["router"] = w_of("router")
    return a + expert_layer(z, quant, experts, rms_norm(a, z["eps"]))


def layers_forward(z: dict, quant: bool, x, layers):
    """x [S, D] float32 through the layers."""
    import jax

    with jax.default_matmul_precision("highest"):
        for l in range(z["L"]):
            x = layer_forward(z, quant, x,
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
