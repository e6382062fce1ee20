"""``model_type`` "qwen3": the architecture's plain forward and its work
counts, found by that name (``reference.py``'s docstring states what a
file in this directory gives; ``work.py`` reads the counts).

Architecture (Qwen3, huggingface.co/Qwen/Qwen3-8B, modeling_qwen3.py):
pre-norm decoder; RMSNorm; grouped-query attention with per-head
RMSNorm on q and k before rotary embedding (half-rotation layout,
theta from the config); causal softmax attention scaled by
1/sqrt(head_dim); SwiGLU feed-forward; tied or untied output head.
Departures: none in the mathematics.  Weights are random, not trained:
each matrix is N(0, 1/fan_in) from ``jax.random.normal`` under the key
``split(key(seed), 12)[slot]`` with the layer axis leading, rounded to
the configuration's dtype; norm gains are 1.  That is the recipe the
served model is documented to use for ``--seed``; it is restated here.

Layers are placed over the job's devices as pipeline stages (a model
that needs four chips to serve needs them here too); each stage is one
``lax.scan`` over its layers.

It imports nothing of the program, and no jax until a forward is built:
the counts are plain Python over the configuration's dict.
"""

from __future__ import annotations

import math
from functools import partial

import numpy as np

from reference import Q_BLOCK, int8_round, rms_norm

# slot of each matrix in split(key(seed), 12); (shape, fan_in) by name
SLOTS = {"wq": 0, "wk": 1, "wv": 2, "wo": 3, "w_gate": 5, "w_up": 6,
         "w_down": 7, "embed": 8, "lm_head": 9}


# ---- the counts ----------------------------------------------------------

def matmul_params(cfg: dict) -> int:
    """Parameters every token is multiplied through: the layers'
    matrices and the output head (the embedding lookup is a gather)."""
    D, H, KV = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Hd, F, L, V = cfg["head_dim"], cfg["intermediate_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    per_layer = D * (H + 2 * KV) * Hd + H * Hd * D + 3 * D * F
    return L * per_layer + D * V


def token_flops(cfg: dict, context: int, with_head: bool = True) -> float:
    """Forward FLOPs of one token that attends to ``context`` positions:
    2 per multiply-add through the matrices, plus QK^T and PV."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, Hd, L = cfg["num_attention_heads"], cfg["head_dim"], cfg["num_hidden_layers"]
    dense = 2.0 * (matmul_params(cfg) - (0 if with_head else D * V))
    return dense + 4.0 * L * H * Hd * context


def prompt_flops(cfg: dict, prompt_len: int) -> float:
    """Prefill of a whole prompt: every token through the layers, the
    head once (only the last position is projected), causal attention
    over sum(1..n) positions."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, Hd, L = cfg["num_attention_heads"], cfg["head_dim"], cfg["num_hidden_layers"]
    dense = 2.0 * (matmul_params(cfg) - D * V) * prompt_len + 2.0 * D * V
    return dense + 4.0 * L * H * Hd * prompt_len * (prompt_len + 1) / 2.0


def kv_bytes_per_position(cfg: dict, kv_dtype_bytes: int = 2) -> int:
    """Bytes of keys and values one cached position holds, all layers."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * kv_dtype_bytes)


def decode_kv_bytes(cfg: dict, contexts: list[int]) -> float:
    """Bytes of KV cache that decoding one token at each of ``contexts``
    must read at the least."""
    return float(kv_bytes_per_position(cfg)) * float(sum(contexts))


# ---- the forward ---------------------------------------------------------

def sizes(cfg: dict) -> dict:
    return {
        "L": cfg["num_hidden_layers"], "D": cfg["hidden_size"],
        "H": cfg["num_attention_heads"], "KV": cfg["num_key_value_heads"],
        "Hd": cfg["head_dim"], "F": cfg["intermediate_size"],
        "V": cfg["vocab_size"], "eps": cfg["rms_norm_eps"],
        "theta": float(cfg["rope_theta"]),
        "tied": bool(cfg["tie_word_embeddings"]),
        "dtype": cfg["torch_dtype"],
    }


def layer_shapes(z: dict) -> dict:
    L, D, H, KV, Hd, F = z["L"], z["D"], z["H"], z["KV"], z["Hd"], z["F"]
    return {"wq": ((L, D, H * Hd), D), "wk": ((L, D, KV * Hd), D),
            "wv": ((L, D, KV * Hd), D), "wo": ((L, H * Hd, D), H * Hd),
            "w_gate": ((L, D, F), D), "w_up": ((L, D, F), D),
            "w_down": ((L, F, D), F)}


def make_weights(z: dict, seed: int, devices: list):
    """(per-stage layer weights, embed, head): each stacked matrix is
    drawn whole under one key and born sharded over the stages on its
    layer axis (jax's counter-based generator gives every element the
    same value however the array is split)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    dtype = jnp.dtype(z["dtype"])
    n = len(devices)
    if z["L"] % n:
        raise ValueError(f"{z['L']} layers do not divide over {n} stages")
    mesh = Mesh(np.array(devices), ("stage",))
    keys = jax.random.split(jax.random.key(seed), 12)

    # The division by sqrt(fan_in) is a true division where the served
    # model draws its weights op by op (one chip), and whatever XLA makes
    # of a division by a constant where it draws them under one jit
    # (several chips): about one element in 1e5 differs by one bfloat16
    # step between the two, so the reference follows the same form.
    folded = n > 1

    def dense(k, denom, shape, fan_in):
        if folded:
            denom = jnp.sqrt(fan_in)
        return (jax.random.normal(k, shape, jnp.float32) / denom).astype(dtype)

    def draw(name, shape, fan_in, sharding):
        make = jax.jit(partial(dense, shape=shape, fan_in=fan_in),
                       out_shardings=sharding)
        return make(keys[SLOTS[name]], jnp.sqrt(fan_in))

    stacked = {
        name: draw(name, shape, fan_in, NamedSharding(mesh, P("stage")))
        for name, (shape, fan_in) in layer_shapes(z).items()}
    stages = []
    for d in devices:
        stages.append({
            name: next(s.data for s in arr.addressable_shards
                       if s.device == d)
            for name, arr in stacked.items()})
    first, last = devices[0], devices[-1]
    embed = draw("embed", (z["V"], z["D"]), z["D"],
                 SingleDeviceSharding(first))
    head = None
    if not z["tied"]:
        head = draw("lm_head", (z["D"], z["V"]), z["D"],
                    SingleDeviceSharding(last))
    return stages, embed, head


def rope(x, positions, theta):
    """x [S, heads, Hd], rotate-half layout."""
    import jax.numpy as jnp

    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal softmax attention, q [S,H,Hd], k/v [S,KV,Hd], in blocks of
    queries so the score tensor stays small."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    S, H, Hd = q.shape
    KV = k.shape[1]
    G = H // KV
    nb = S // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, KV, G, Hd)
    t = jnp.arange(S)

    def block(args):
        qi, b = args
        pos = b * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k) / math.sqrt(Hd)
        s = jnp.where(t[None, None, None, :] <= pos[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v)

    out = lax.map(block, (qb, jnp.arange(nb)))
    return out.reshape(S, H * Hd)


def stage_forward(z: dict, quant: bool, x, layers):
    """x [S, D] float32 through this stage's layers (one scan)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    S = x.shape[0]
    pos = jnp.arange(S)
    H, KV, Hd = z["H"], z["KV"], z["Hd"]

    def w_of(layer, name):
        w = layer[name].astype(jnp.float32)
        return int8_round(w, 0) if quant else w

    def body(x, layer):
        h = rms_norm(x, z["eps"])  # gain 1
        q = (h @ w_of(layer, "wq")).reshape(S, H, Hd)
        k = (h @ w_of(layer, "wk")).reshape(S, KV, Hd)
        v = (h @ w_of(layer, "wv")).reshape(S, KV, Hd)
        q = rope(rms_norm(q, z["eps"]), pos, z["theta"])
        k = rope(rms_norm(k, z["eps"]), pos, z["theta"])
        x = x + attention(q, k, v) @ w_of(layer, "wo")
        h = rms_norm(x, z["eps"])
        gate = jax.nn.silu(h @ w_of(layer, "w_gate"))
        x = x + (gate * (h @ w_of(layer, "w_up"))) @ w_of(layer, "w_down")
        return x, None

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
    """The seeded weights over ``devices`` and the forward through them."""

    def __init__(self, cfg: dict, seed: int, devices: list):
        self.z = sizes(cfg)
        self.devices = devices
        self.stages, self.embed, head = make_weights(self.z, seed, devices)
        self.head = self.embed if self.z["tied"] else head
        self._fns: dict = {}

    def _fn(self, what: str, quant: bool):
        import jax

        key = (what, quant)
        if key not in self._fns:
            f = {"embed": partial(embed_tokens, quant),
                 "stage": partial(stage_forward, self.z, quant)}[what]
            self._fns[key] = jax.jit(f)
        return self._fns[key]

    def hidden(self, padded, quant: bool):
        import jax
        import jax.numpy as jnp

        x = self._fn("embed", quant)(
            self.embed, jax.device_put(jnp.asarray(padded), self.devices[0]))
        for dev, layers in zip(self.devices, self.stages):
            x = self._fn("stage", quant)(jax.device_put(x, dev), layers)
        return jax.device_put(x, next(iter(self.head.devices())))
