"""The plain reference: the configuration's forward pass in straightforward
``jax.numpy``, float32 at ``highest`` matmul precision, no kernels, no
cache, no batching — and the weights, made from the seed by this file's
own restatement of the published initialisation.

It imports nothing of the program and takes nothing the program made.
It runs as a child process of the harness once the window has closed
and the server has exited (so the chip is free and the server's memory
peak has been read), over a sample of the requests the window finished:
the prompt followed by the tokens the server streamed, one forward per
request, and at every served position the gap by which the served
token's logit lies below the reference's best.

    python perfbench/reference.py <job.json> <out.json>

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

Layers are placed over the machine's chips as pipeline stages (a model
that needs four chips to serve needs them here too); each stage is one
``lax.scan`` over its layers.

The control (``"control": true`` in the job) is the same forward with
every matrix rounded to symmetric per-output-channel int8 — the nearest
precision below the bfloat16 the configuration states.  It need not
decode: at each position of the same prompts and tokens it reads the gap
of the token that the lower precision puts first.
"""

from __future__ import annotations

import json
import math
import sys
import time
from functools import partial

import numpy as np

# slot of each matrix in split(key(seed), 12); (shape, fan_in) by name
SLOTS = {"wq": 0, "wk": 1, "wv": 2, "wo": 3, "w_gate": 5, "w_up": 6,
         "w_down": 7, "embed": 8, "lm_head": 9}
Q_BLOCK = 512       # queries per attention block (bounds the score tensor)
SEQ_BUCKETS = (1024, 2048, 4096)  # padded lengths: three programs at most


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


def int8_round(w, axis: int):
    """Symmetric int8 with one scale per output channel, returned as the
    float32 values the int8 codes stand for."""
    import jax.numpy as jnp

    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def rms_norm(x, eps):
    import jax.numpy as jnp
    from jax import lax

    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


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


def head_logits(z: dict, quant: bool, x, rows, embed_or_head):
    """Final norm and output head at the positions ``rows`` -> [R, V]."""
    import jax
    import jax.numpy as jnp

    h = rms_norm(x[rows], z["eps"])
    w = embed_or_head.astype(jnp.float32)
    if z["tied"]:
        w = int8_round(w, 1).T if quant else w.T
    elif quant:
        w = int8_round(w, 0)
    with jax.default_matmul_precision("highest"):
        return h @ w


def head_gaps(z: dict, x, rows, tokens, embed_or_head):
    """At each of ``rows``: how far the logit of ``tokens`` lies below
    the best logit there (0 where it is the best)."""
    import jax.numpy as jnp

    logits = head_logits(z, False, x, rows, embed_or_head)
    at = jnp.take_along_axis(logits, tokens[:, None], axis=-1)[:, 0]
    return jnp.max(logits, axis=-1) - at


def head_first(z: dict, quant: bool, x, rows, embed_or_head):
    import jax.numpy as jnp

    return jnp.argmax(head_logits(z, quant, x, rows, embed_or_head), axis=-1)


class Reference:
    def __init__(self, cfg: dict, seed: int, n_devices: int):
        import jax

        self.z = sizes(cfg)
        devices = jax.local_devices()[:n_devices]
        self.devices = devices
        self.stages, self.embed, self.head = make_weights(
            self.z, seed, devices)
        self._fns: dict = {}

    def _fn(self, what: str, quant: bool = False):
        import jax

        key = (what, quant)
        if key not in self._fns:
            f = {"embed": partial(embed_tokens, quant),
                 "stage": partial(stage_forward, self.z, quant),
                 "logits": partial(head_logits, self.z, quant),
                 "first": partial(head_first, self.z, quant),
                 "gaps": partial(head_gaps, self.z)}[what]
            self._fns[key] = jax.jit(f)
        return self._fns[key]

    def hidden(self, tokens: list[int], quant: bool):
        """The last layer's output [S, D] of one sequence (padded to a
        bucket), on the device that holds the output head."""
        import jax
        import jax.numpy as jnp

        S = next((b for b in SEQ_BUCKETS if len(tokens) <= b), None)
        if S is None:
            raise ValueError(f"sequence of {len(tokens)} tokens is longer "
                             f"than the largest bucket {SEQ_BUCKETS[-1]}")
        padded = np.zeros((S,), np.int32)
        padded[:len(tokens)] = tokens  # the tail is after every real
        # position, and attention is causal: it changes nothing before it
        x = self._fn("embed", quant)(
            self.embed, jax.device_put(jnp.asarray(padded), self.devices[0]))
        for dev, layers in zip(self.devices, self.stages):
            x = self._fn("stage", quant)(jax.device_put(x, dev), layers)
        return jax.device_put(x, self._head_device())

    def _head_device(self):
        return self.devices[0] if self.z["tied"] else self.devices[-1]

    def _head(self):
        return self.embed if self.z["tied"] else self.head

    def _rows(self, rows: list[int], fill: int = 0):
        import jax
        import jax.numpy as jnp

        padded = np.full((256 * -(-len(rows) // 256),), fill, np.int32)
        padded[:len(rows)] = rows
        return jax.device_put(jnp.asarray(padded), self._head_device())

    def gaps(self, x, rows: list[int], tokens) -> np.ndarray:
        out = self._fn("gaps")(x, self._rows(rows), self._rows(tokens),
                               self._head())
        return np.asarray(out)[:len(rows)]

    def first(self, x, rows: list[int], quant: bool) -> np.ndarray:
        out = self._fn("first", quant)(x, self._rows(rows), self._head())
        return np.asarray(out)[:len(rows)]

    def logits(self, tokens: list[int], rows: list[int], quant: bool):
        """float32 logits [len(rows), V] of one sequence at ``rows``."""
        out = self._fn("logits", quant)(
            self.hidden(tokens, quant), self._rows(rows), self._head())
        return out[:len(rows)]


def gaps_of(ref: Reference, prompt_ids: list[int], served: list[int],
            control: bool) -> dict:
    """The gap of every served token below the reference's best logit at
    its position; with ``control``, the same for the token the int8
    forward puts first there."""
    m = len(served)
    tokens = list(prompt_ids) + list(served[:-1])
    rows = [len(prompt_ids) - 1 + i for i in range(m)]
    x = ref.hidden(tokens, quant=False)
    gaps = ref.gaps(x, rows, served)
    out = {"n": m, "gap_max": float(gaps.max()),
           "gap_sum": float(gaps.sum()), "gap_at": int(gaps.argmax()),
           "mismatches": int((gaps > 0).sum())}
    if control:
        first = ref.first(ref.hidden(tokens, quant=True), rows, quant=True)
        cg = ref.gaps(x, rows, [int(t) for t in first])
        out.update(ctl_gap_max=float(cg.max()), ctl_gap_sum=float(cg.sum()),
                   ctl_mismatches=int((cg > 0).sum()))
    return out


def main(argv: list[str]) -> int:
    job_path, out_path = argv[1], argv[2]
    with open(job_path) as f:
        job = json.load(f)
    t0 = time.monotonic()
    import jax

    if job.get("cache_dir"):
        jax.config.update("jax_compilation_cache_dir", job["cache_dir"])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = jax.local_devices()
    if dev[0].platform != job["platform"] or len(dev) < job["chips"]:
        print(f"reference: wanted {job['chips']} x {job['platform']}, found "
              f"{len(dev)} x {dev[0].platform}", file=sys.stderr)
        return 3
    ref = Reference(job["config"], job["seed"], job["chips"])
    t1 = time.monotonic()
    results = []
    for req in job["requests"]:
        t = time.monotonic()
        r = gaps_of(ref, req["prompt_ids"], req["tokens"],
                    bool(job.get("control")))
        r["i"] = req["i"]
        r["seconds"] = time.monotonic() - t
        results.append(r)
    with open(out_path, "w") as f:
        json.dump({"requests": results,
                   "weights_s": t1 - t0,
                   "forward_s": time.monotonic() - t1}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
