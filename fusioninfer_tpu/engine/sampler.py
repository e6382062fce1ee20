"""Token sampling: greedy, temperature, top-k, top-p, penalties, seeds.

Batched and jittable; each sequence carries its own sampling params so one
compiled sampler serves a heterogeneous continuous batch.  Per-request
seeds give reproducible sampling **independent of batch composition**:
each row draws from its own PRNG stream (``fold_in(seed, n_generated)``),
so the same request produces the same tokens whether it runs solo or
packed with strangers.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SamplingParams:
    temperature: float = 1.0
    top_k: int = 0  # 0 = disabled
    top_p: float = 1.0
    min_p: float = 0.0  # vLLM-style: drop tokens with p < min_p * p_max
    max_tokens: int = 128
    min_tokens: int = 0  # stop tokens suppressed until this many generated
    stop_token_ids: tuple[int, ...] = ()
    # decoded-text stop sequences (OpenAI `stop`): matched by the SERVER,
    # which cancels engine-side work on a hit — the engine is text-blind
    stop_strings: tuple[str, ...] = ()
    presence_penalty: float = 0.0  # subtract once per seen token id
    frequency_penalty: float = 0.0  # subtract per occurrence
    repetition_penalty: float = 1.0  # HF-style multiplicative, 1 = off
    seed: Optional[int] = None  # per-request reproducibility
    # OpenAI `logprobs`: return the sampled token's log-probability and
    # the top-N alternatives per step (raw model distribution)
    logprobs: Optional[int] = None
    # OpenAI `response_format: json_object`: constrain output to valid
    # JSON via byte-level grammar masking (engine/guided.py)
    guided_json: bool = False
    # OpenAI `response_format: json_schema`: canonical-JSON schema string
    # compiled to a schema-constrained byte machine (guided.SchemaByteMachine)
    guided_schema: str = ""
    # OpenAI `logit_bias`: additive per-token-id logit adjustments,
    # applied before sampling every step (±100 effectively bans/forces)
    logit_bias: tuple[tuple[int, float], ...] = ()

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0

    @property
    def needs_token_counts(self) -> bool:
        return (
            self.presence_penalty != 0.0
            or self.frequency_penalty != 0.0
            or self.repetition_penalty != 1.0
        )


@jax.jit
def apply_penalties(
    logits: jax.Array,  # [B, V] float32
    token_counts: jax.Array,  # [B, V] int32 — prompt + generated occurrences
    output_counts: jax.Array,  # [B, V] int32 — generated occurrences only
    presence: jax.Array,  # [B]
    frequency: jax.Array,  # [B]
    repetition: jax.Array,  # [B], 1.0 = off
) -> jax.Array:
    """OpenAI/vLLM semantics: presence/frequency penalize tokens the model
    *generated* (never mere prompt occurrences); only the HF-style
    repetition penalty spans prompt + output."""
    seen = token_counts > 0
    rep = repetition[:, None]
    logits = jnp.where(
        seen, jnp.where(logits > 0, logits / rep, logits * rep), logits
    )
    logits = logits - presence[:, None] * (output_counts > 0)
    logits = logits - frequency[:, None] * output_counts
    return logits


def filter_logits(
    logits: jax.Array,  # [B, V] float32
    temperature: jax.Array,  # [B]
    top_k: jax.Array,  # [B] int32, 0 = off
    top_p: jax.Array,  # [B]
    min_p: jax.Array | None = None,  # [B], 0 = off
) -> jax.Array:
    """Temperature-scaled logits with min_p/top-k/top-p masks applied
    (-inf outside the sampleable support).  The ONE place the filtered
    sampling distribution is defined — :func:`sample` and the
    speculative window draws both consume it, so acceptance tests can
    never drift from what sequential sampling would do."""
    B, V = logits.shape
    t = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / t

    if min_p is not None:
        # vLLM min_p: drop tokens whose probability is below
        # min_p × the row's max probability (scale-adaptive floor)
        probs = jax.nn.softmax(scaled, axis=-1)
        floor = min_p[:, None] * probs.max(axis=-1, keepdims=True)
        scaled = jnp.where(probs < floor, -jnp.inf, scaled)

    # top-k: mask logits below the k-th largest (per row)
    sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    k_idx = jnp.clip(jnp.where(top_k > 0, top_k, V) - 1, 0, V - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    scaled = jnp.where(scaled < kth, -jnp.inf, scaled)

    # top-p (nucleus): keep the smallest prefix of sorted probs covering p
    sorted_logits = jnp.sort(scaled, axis=-1)[:, ::-1]
    sorted_probs = jax.nn.softmax(sorted_logits, axis=-1)
    cumulative = jnp.cumsum(sorted_probs, axis=-1)
    # token allowed if the cumulative mass *before* it is < top_p
    cutoff_mask = (cumulative - sorted_probs) < top_p[:, None]
    threshold = jnp.where(
        cutoff_mask, sorted_logits, jnp.inf
    ).min(axis=-1, keepdims=True)
    return jnp.where(scaled < threshold, -jnp.inf, scaled)


@partial(jax.jit, static_argnames=("mode",))
def sample(
    logits: jax.Array,  # [B, V] float32 (penalties already applied)
    keys: jax.Array,  # [B] PRNG keys — one independent stream per row
    temperature: jax.Array,  # [B]
    top_k: jax.Array,  # [B] int32, 0 = off
    top_p: jax.Array,  # [B]
    min_p: jax.Array | None = None,  # [B], 0 = off
    mode: str = "filtered",
) -> jax.Array:
    """Sample one token per row; temperature <= 0 means greedy.

    ``mode`` is a STATIC fast-path hint the engine computes on the host
    from the batch's sampling params (it knows every row's request):

    * ``"greedy"``   — every row has temperature <= 0: return the
      argmax, no keys consumed, nothing else computed.
    * ``"plain"``    — no sampled row uses top-k/top-p/min-p: sample
      from the temperature-scaled logits, skipping
      :func:`filter_logits` — whose two full [B, V] sorts cost ~30 ms
      per step at a 150k vocab on TPU and dominate the decode loop if
      run unconditionally.
    * ``"topk"``     — every sampled row has 0 < top_k <= the candidate
      cap (:data:`ops.lm_head_topk.LM_HEAD_TOPK`) and min_p off: the
      draw is DEFINED over the row's top-k candidate set
      (:func:`sample_topk`), which is the whole point — the fused
      lm_head path computes the same candidates WITHOUT ever
      materializing [B, V] logits, and because both paths feed the
      identical candidate array to the identical sampler, fused and
      unfused seeded streams are bit-identical by construction.
    * ``"filtered"`` — the general path (default; always correct —
      logprobs / guided / logit_bias / min_p / unbounded-top_k rows).

    A static argument (one small compiled variant each) rather than a
    runtime ``lax.cond``: a cond nested inside the decode-burst scan
    sent XLA:TPU compile time through the roof, and the host already
    knows the batch composition exactly.  The greedy/plain fast paths
    are bit-identical to the filtered math: with top_k=0 and top_p=1
    the filter masks nothing, so its categorical draw sees the very
    same scaled logits.

    Candidate-row determinism is PER ROW, never per batch: a row that
    qualifies for the candidate draw (0 < top_k <= the cap, min_p off)
    takes it in EVERY mode that can see such a row — "topk" draws only
    candidates, and "filtered" routes its candidate-eligible rows
    through the very same :func:`sample_topk` while the rest of the
    batch draws from the full filtered distribution — so a seeded
    request's tokens never depend on which neighbors share its batch
    (the batch-composition independence this module has promised since
    round 1; the mode merely picks how much work the OTHER rows cost)."""
    greedy_tok = jnp.argmax(logits, axis=-1)
    if mode == "greedy":
        return greedy_tok
    if mode == "topk":
        vals, idx = jax.lax.top_k(logits,
                                  min(_topk_cap(), logits.shape[-1]))
        return sample_topk(vals, idx, keys, temperature, top_k, top_p,
                           mode=mode)
    if mode == "plain":
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
        sampled = jax.vmap(jax.random.categorical)(keys, scaled)
        return jnp.where(temperature <= 0.0, greedy_tok, sampled)
    scaled = filter_logits(logits, temperature, top_k, top_p, min_p)
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    # per-row candidate routing: rows the "topk" mode would serve draw
    # from the SAME candidate sampler here, so admitting (or finishing)
    # a filtered neighbor mid-stream cannot flip a seeded top-k row's
    # bits between the candidate and full-vocab draws
    cap = min(_topk_cap(), logits.shape[-1])
    vals, idx = jax.lax.top_k(logits, cap)
    cand = sample_topk(vals, idx, keys, temperature, top_k, top_p,
                       mode="topk")
    eligible = (top_k > 0) & (top_k <= cap)
    if min_p is not None:
        eligible = eligible & (min_p <= 0.0)
    sampled = jnp.where(eligible, cand, sampled)
    return jnp.where(temperature <= 0.0, greedy_tok, sampled)


def _topk_cap() -> int:
    """The candidate-set width (ops/lm_head_topk.py), imported lazily —
    sampler must stay importable without the ops stack."""
    from fusioninfer_tpu.ops.lm_head_topk import LM_HEAD_TOPK

    return LM_HEAD_TOPK


@partial(jax.jit, static_argnames=("mode",))
def sample_topk(
    vals: jax.Array,  # [B, K] penalized UNSCALED logits, value-desc,
    #                   ties vocab-index-asc (lax.top_k's contract)
    idx: jax.Array,  # [B, K] their vocab ids
    keys: jax.Array,  # [B] PRNG keys
    temperature: jax.Array,  # [B]
    top_k: jax.Array,  # [B] int32 — 0 < top_k <= K for sampled rows
    top_p: jax.Array,  # [B]
    mode: str = "topk",
) -> jax.Array:
    """The ONE candidate-set sampler — both the fused lm_head path and
    the unfused ``sample(mode="topk")`` land here with byte-identical
    candidate arrays, so their streams cannot diverge.

    Mirrors :func:`filter_logits` + categorical restricted to the
    candidates: temperature scaling, a RANK-based top-k mask (the
    candidates are already value-sorted, so rank < top_k IS the top-k
    set; exact value ties at the boundary resolve by vocab index
    instead of the filtered path's keep-all-ties — a deliberate,
    documented tightening), then the nucleus mask over the candidate
    distribution, then one categorical over [B, K].  Greedy rows read
    candidate 0 — ``lax.top_k``'s tie rule makes that exactly
    ``argmax``."""
    greedy_tok = idx[:, 0]
    if mode == "greedy":
        return greedy_tok
    K = vals.shape[1]
    scaled = vals / jnp.maximum(temperature, 1e-6)[:, None]
    ranks = jnp.arange(K)[None, :]
    scaled = jnp.where(ranks < jnp.maximum(top_k, 1)[:, None],
                       scaled, -jnp.inf)
    # nucleus over the (sorted) candidates: keep the smallest prefix
    # whose cumulative mass covers top_p — filter_logits' rule, with
    # the sort already done
    probs = jax.nn.softmax(scaled, axis=-1)
    cumulative = jnp.cumsum(probs, axis=-1)
    scaled = jnp.where((cumulative - probs) < top_p[:, None],
                       scaled, -jnp.inf)
    j = jax.vmap(jax.random.categorical)(keys, scaled)
    sampled = jnp.take_along_axis(idx, j[:, None], axis=1)[:, 0]
    return jnp.where(temperature <= 0.0, greedy_tok, sampled)


@jax.jit
def spec_window_draws(
    logits_w: jax.Array,  # [B, C, V] float32 — verify-window logits
    draft_next: jax.Array,  # [B, C] int32: token PROPOSED after position j
    keys_w: jax.Array,  # [B, C] PRNG keys — key (seed, gen_count + j)
    temperature: jax.Array,  # [B]
    top_k: jax.Array,  # [B]
    top_p: jax.Array,  # [B]
    min_p: jax.Array,  # [B]
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Everything the host-side speculative acceptance walk needs, in
    one fused call (delta-draft speculative sampling, Leviathan et al.):

    * ``full[b, j]``    — a token sampled from position j's FILTERED
      distribution with key (seed, gen+j); identical math and key
      derivation to the sequential :func:`sample` path.  ``full[b, k]``
      is the bonus token after all k drafts were accepted.
    * ``p_draft[b, j]`` — the filtered probability of the draft token
      proposed after position j.  With a delta draft (the n-gram
      proposer is deterministic), accept with probability p_draft.
    * ``u[b, j]``       — the acceptance uniform, from a fold of the
      position's key (independent of ``full``'s draw).
    * ``repl[b, j]``    — the rejection replacement, sampled from the
      filtered distribution with the draft token REMOVED (for a delta
      proposal, norm((p - q)^+) is exactly p restricted to != draft),
      from a second fold.

    Host walk: accept drafts while ``u < p_draft`` (STRICT — ``u`` can
    be exactly 0.0 and a draft outside the filtered support has
    p_draft == 0, which must never be accepted); on first rejection
    emit ``repl`` at that position; on full acceptance emit the bonus
    ``full[:, k]``.  This preserves the target distribution exactly.
    (Rows that proposed no drafts never reach this function — they
    sample through the regular :func:`sample` path.)
    """
    B, C, V = logits_w.shape
    flat = logits_w.reshape(B * C, V)

    def rep(x):
        return jnp.repeat(x, C)

    scaled = filter_logits(flat, rep(temperature), rep(top_k), rep(top_p),
                           rep(min_p))
    kf = keys_w.reshape(B * C)
    greedy = jnp.argmax(flat, axis=-1)
    full = jnp.where(rep(temperature) <= 0.0, greedy,
                     jax.vmap(jax.random.categorical)(kf, scaled))
    probs = jax.nn.softmax(scaled, axis=-1)
    d = draft_next.reshape(B * C)
    rows = jnp.arange(B * C)
    p_draft = probs[rows, d]
    u = jax.vmap(lambda k: jax.random.uniform(jax.random.fold_in(k, 1)))(kf)
    masked = scaled.at[rows, d].set(-jnp.inf)
    repl = jax.vmap(jax.random.categorical)(
        jax.vmap(lambda k: jax.random.fold_in(k, 2))(kf), masked)
    return (full.reshape(B, C), p_draft.reshape(B, C),
            u.reshape(B, C), repl.reshape(B, C))


@partial(jax.jit, static_argnames=("mode",))
def sample_first(
    logits: jax.Array,  # [1, V] — prefill's last-token logits (on device)
    prefix: jax.Array,  # [L] int32 — prompt(+resumed) tokens, pow2-padded
    ctl_i: jax.Array,  # [6] int32: n_prompt, n_prefix, top_k, min_tokens,
    #                              gen_index, seed_bits (uint32 bitcast)
    ctl_f: jax.Array,  # [6] float32: temperature, top_p, min_p,
    #                                presence, frequency, repetition
    stop_ids: jax.Array,  # [K] int32 — suppressible stop ids, -1 padded
    mode: str = "filtered",
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused first-token sampling for the admission (TTFT) path →
    ``(token, counts_row, out_row, sup_row)``.

    The legacy path issued ~14 small device ops per admission (two [V]
    histograms, a suppress row, penalties, keys, sample — each a
    separate upload/dispatch); this is the same math in ONE jitted call
    with the scalars packed into two control arrays.  Bit-identical to the unfused
    sequence: same histogram weights, penalty ordering, min-tokens
    gating, key derivation and sampling mode.  Rows with logit_bias or
    a guided machine keep the legacy path (host-side extras).

    The returned ``counts_row``/``out_row``/``sup_row`` stay on device
    for the slot-state install (``engine._register_slot``)."""
    vocab = logits.shape[-1]
    n_prompt, n_prefix = ctl_i[0], ctl_i[1]
    pos = jnp.arange(prefix.shape[0])
    w_all = (pos < n_prefix).astype(jnp.int32)
    w_out = ((pos < n_prefix) & (pos >= n_prompt)).astype(jnp.int32)
    counts_row = jnp.zeros((vocab,), jnp.int32).at[prefix].add(w_all)
    out_row = jnp.zeros((vocab,), jnp.int32).at[prefix].add(w_out)
    # match legacy scatter semantics exactly: out-of-range ids DROP
    # (JAX scatter drops OOB indices) — clip alone would mark vocab-1
    sup_valid = (stop_ids >= 0) & (stop_ids < vocab)
    sup_row = jnp.zeros((vocab,), jnp.bool_).at[
        jnp.clip(stop_ids, 0, vocab - 1)].max(sup_valid)
    logits = apply_penalties(
        logits, counts_row[None], out_row[None],
        ctl_f[3][None], ctl_f[4][None], ctl_f[5][None])
    early = ctl_i[4] < ctl_i[3]
    logits = jnp.where(early & sup_row[None], -jnp.inf, logits)
    seed = jax.lax.bitcast_convert_type(ctl_i[5], jnp.uint32)
    keys = make_row_keys(seed[None], ctl_i[4][None])
    tok = sample(logits, keys, ctl_f[0][None], ctl_i[2][None],
                 ctl_f[1][None], ctl_f[2][None], mode=mode)
    return tok[0], counts_row, out_row, sup_row


@jax.jit
def make_row_keys(seeds: jax.Array, counters: jax.Array) -> jax.Array:
    """[B] independent keys: stream ``seed``, position ``counter``."""
    return jax.vmap(
        lambda s, c: jax.random.fold_in(jax.random.fold_in(jax.random.key(s), c), 0)
    )(seeds, counters)


@jax.jit
def count_prompt_tokens(tokens: jax.Array, vocab_size_arr: jax.Array) -> jax.Array:
    """[S] prompt token ids → [V] occurrence counts (V from arr shape)."""
    V = vocab_size_arr.shape[0]
    return jnp.zeros((V,), jnp.int32).at[tokens].add(1)
