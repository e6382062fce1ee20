"""The plain reference: the configuration's forward pass in straightforward
``jax.numpy``, float32 at ``highest`` matmul precision, no kernels, no
cache, no batching — and the weights, made from the seed by the
benchmark's own restatement of the served initialisation.

It imports nothing of the program and takes nothing the program made.
It runs as a child process of the harness once the window has closed
and the server has exited (so the chip is free and the server's memory
peak has been read), over a sample of the requests the window finished:
the prompt followed by the tokens the server streamed, one forward per
request, and at every served position the gap by which the served
token's logit lies below the reference's best.

    python perfbench/reference.py <job.json> <out.json>

This file is the judge and belongs to no architecture: which positions
are compared, what a gap is, the final norm and the tied or untied output
head over ``vocab_size`` as the configuration's file gives them, the
int8 control, the padding, the job's reading and the timing.  The layers
are an architecture's, found BY NAME: the configuration's published
``model_type`` names ``perfbench/arch/<model_type>.py`` (``run.py``
resolves it before it starts the server and hands the path on as the
job's ``arch``).  A new architecture is a new file there, never an edit
here.  Such a file imports nothing of the program, and no jax until it is
called; it may import ``Q_BLOCK``, ``int8_round`` and ``rms_norm`` from
this one.  It gives:

- ``Forward(cfg, seed, devices)``: the weights, drawn from the seed by
  the architecture's own restatement of the served initialisation, in
  the configuration's dtype, placed over the job's devices as the file
  sees fit;
- ``Forward.hidden(padded, quant)``: the last layer's output ``[S, D]``
  (before the final norm) of one padded sequence of ``S`` token ids, in
  float32 at ``highest`` precision, on the device where ``head`` lies;
  with ``quant`` every matrix is first rounded to per-output-channel
  int8 (``int8_round``).  ``S`` is a power of two, at least 1024, so a
  multiple of ``Q_BLOCK``; the tail past the real tokens is zeros, after
  every real position, so a causal layer changes nothing before it.  A
  sequence past what one chip holds in one program is computed in blocks
  by the architecture's file;
- ``Forward.head``: the output head's matrix as served, ``[V, D]`` (the
  embedding) where ``tie_word_embeddings``, else ``[D, V]``;
- the five counts that ``work.py`` looks up: ``matmul_params``,
  ``token_flops``, ``prompt_flops``, ``kv_bytes_per_position``,
  ``decode_kv_bytes``, each over the configuration's dict.

The control (``"control": true`` in the job) is the same forward with
every matrix rounded to symmetric per-output-channel int8 — the nearest
precision below the bfloat16 the configuration states.  It need not
decode: at each position of the same prompts and tokens it reads the gap
of the token that the lower precision puts first.
"""

from __future__ import annotations

import json
import sys
import time
from functools import partial

import numpy as np

import work

Q_BLOCK = 512       # queries per attention block (bounds the score tensor)


def seq_bucket(n: int) -> int:
    """The padded length of a sequence of ``n`` tokens: the least power
    of two that holds it, 1024 at the least (1024, 2048 and 4096 for
    contexts to 4096: three programs)."""
    return max(1024, 1 << (n - 1).bit_length())


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
    def __init__(self, arch, cfg: dict, seed: int, n_devices: int):
        import jax

        self.z = {"eps": cfg["rms_norm_eps"],
                  "tied": bool(cfg["tie_word_embeddings"])}
        self.model = arch.Forward(cfg, seed, jax.local_devices()[:n_devices])
        self._fns: dict = {}

    def _fn(self, what: str, quant: bool = False):
        import jax

        key = (what, quant)
        if key not in self._fns:
            f = {"first": partial(head_first, self.z, quant),
                 "gaps": partial(head_gaps, self.z)}[what]
            self._fns[key] = jax.jit(f)
        return self._fns[key]

    def hidden(self, tokens: list[int], quant: bool):
        """The last layer's output [S, D] of one sequence (padded to a
        bucket), on the device that holds the output head."""
        padded = np.zeros((seq_bucket(len(tokens)),), np.int32)
        padded[:len(tokens)] = tokens  # the tail is after every real
        # position, and attention is causal: it changes nothing before it
        return self.model.hidden(padded, quant)

    def _rows(self, rows: list[int], fill: int = 0):
        import jax
        import jax.numpy as jnp

        padded = np.full((256 * -(-len(rows) // 256),), fill, np.int32)
        padded[:len(rows)] = rows
        return jax.device_put(jnp.asarray(padded),
                              next(iter(self.model.head.devices())))

    def gaps(self, x, rows: list[int], tokens) -> np.ndarray:
        out = self._fn("gaps")(x, self._rows(rows), self._rows(tokens),
                               self.model.head)
        return np.asarray(out)[:len(rows)]

    def first(self, x, rows: list[int], quant: bool) -> np.ndarray:
        out = self._fn("first", quant)(x, self._rows(rows), self.model.head)
        return np.asarray(out)[:len(rows)]


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
    ref = Reference(work.load_arch(job["arch"]), job["config"], job["seed"],
                    job["chips"])
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
