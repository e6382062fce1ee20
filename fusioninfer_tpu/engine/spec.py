"""Speculative decoding: n-gram prompt-lookup drafts.

Model-free speculation (vLLM's ``[ngram]`` speculative method, which the
reference only orchestrates via engine flags — SURVEY §0): the last
``n`` tokens of a sequence are matched against its own earlier context
(prompt + generated so far); on a hit, the tokens that followed the
match are proposed as drafts.  The engine verifies all drafts as one
window row (``q_len = 1 + drafts``) of
:func:`fusioninfer_tpu.engine.model_runner.fused_step` — decode
is weight-bandwidth-bound, so scoring ``k+1`` positions costs roughly
one decode step, and every accepted draft is a free token.  Strongest on
extractive workloads (summarization, RAG, code edits) where the output
quotes the prompt.

Proposal is exact-match and the verifier is the model itself, so greedy
outputs are bit-identical with speculation on or off (acceptance only
shortcuts steps, never changes tokens) — ``tests/test_spec_decode.py``
pins that.
"""

from __future__ import annotations

import numpy as np


class NgramProposer:
    """Propose up to ``k`` draft tokens by longest-suffix n-gram lookup.

    Tries ``max_ngram`` down to ``min_ngram``: the MOST RECENT earlier
    occurrence of the sequence's last-n-token suffix wins, and the tokens
    that followed it are the draft.  O(len · n) vectorized compares per
    call via a sliding-window view — no model, no extra weights.
    """

    def __init__(self, max_ngram: int = 3, min_ngram: int = 1):
        if not 1 <= min_ngram <= max_ngram:
            raise ValueError("need 1 <= min_ngram <= max_ngram")
        self.max_ngram = max_ngram
        self.min_ngram = min_ngram

    def propose(self, tokens: list[int], k: int) -> list[int]:
        """Drafts for the continuation of ``tokens`` (possibly empty)."""
        if k < 1:
            return []
        arr = np.asarray(tokens, np.int64)
        L = arr.shape[0]
        for n in range(min(self.max_ngram, L - 1), self.min_ngram - 1, -1):
            pattern = arr[L - n:]
            # windows over arr[:-1]: every match has ≥1 follower, and the
            # suffix's own position (L-n) is structurally excluded —
            # overlapping periodic matches remain, which is what extends
            # a run like "... a b a b" with more "a b"
            windows = np.lib.stride_tricks.sliding_window_view(arr[:-1], n)
            hits = np.nonzero((windows == pattern).all(axis=1))[0]
            if hits.size:
                # latest match with k full followers (recency bias), else
                # the match with the most followers — a run's latest
                # match sits at the end with almost nothing after it
                full = hits[L - (hits + n) >= k]
                best = int(full[-1]) if full.size else int(
                    hits[np.argmax(L - (hits + n))]
                )
                start = best + n
                return arr[start : start + k].tolist()
        return []
