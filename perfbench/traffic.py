"""The one general traffic generator: a data file in, a schedule of
requests out.  Every run of a mix replays the SAME schedule: the rows of
its file, in their order, at their instants.

A traffic mix is ``perfbench/traffic/<name>.json``.  Nothing in here knows
a mix by name: a later PR adds a mix by adding a file.  Keys:

``loop``            ``"closed"`` (each client sends its next request when
                    its last one ends) or ``"open"`` (requests are due at
                    instants fixed before the run, whatever the server does)
``ramp_s``          seconds of the same load before the window opens
``clients_per_slot`` closed loop: clients = this x the configuration's
                    ``max_batch_size``
``tail_max_s``      open loop: for how long after the window's close the
                    rows go on being offered, uncounted, so that the
                    counted requests end under the load they began under
``requests``        the rows.  Closed loop: ``[prompt tokens, output
                    tokens]``, drawn from the front.  Open loop: ``[due
                    second, prompt tokens, output tokens]``, the window
                    opening at second 0 and the ramp before it
``rate_rps``, ``drawn_from``  what the rows were drawn at and from: the
                    sweep rescales the instants by the first, the tests
                    hold the rows to both; a run reads neither
``warm``            the warm tour: prompt lengths sent before the ramp so
                    that every program the mix reaches is loaded in set-up

``--seed`` decides the weights and every prompt's bytes, and nothing about
the work: a window cuts the schedule, so another order or other instants
are other work inside it (PERF.md, section 2).
"""

from __future__ import annotations

import json
import os
import random

# one printable byte per token under the byte tokenizer; no quote and no
# backslash, so the JSON body's length is the prompt's length
ALPHABET = ("abcdefghijklmnopqrstuvwxyz"
            "ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789 ")
# the byte tokenizer the server falls back to with no network
# (bytes 0-255 -> ids 3-258, BOS = 1): restated here, not imported,
# because the reference may take nothing from the program
BYTE_OFFSET = 3
BOS_ID = 1


def load(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    width = 3 if mix["loop"] == "open" else 2
    rows = mix.get("requests") or []
    if not rows or any(len(r) != width for r in rows):
        raise ValueError(f"{path}: 'requests' must hold rows of {width} numbers")
    if width == 3 and any(a[0] > b[0] for a, b in zip(rows, rows[1:])):
        raise ValueError(f"{path}: the due seconds are not in order")
    return mix


def prompt_text(n_tokens: int, rng: random.Random) -> str:
    """Random text of exactly ``n_tokens`` byte-tokenizer tokens (BOS
    included): unique, so the prefix cache is on and hits nothing."""
    return "".join(rng.choices(ALPHABET, k=max(1, n_tokens - 1)))


def token_ids(text: str) -> list[int]:
    return [BOS_ID] + [b + BYTE_OFFSET for b in text.encode("utf-8")]


def schedule(mix: dict, seconds: float, max_batch_size: int,
             stretch: float = 1.0) -> dict:
    """Everything a run offers, fixed before it starts.

    Returns ``{"loop", "ramp_s", "clients", "requests", "tail"}`` where
    each request is ``{"i", "due", "prompt_len", "max_tokens"}`` (``due``
    in seconds from the window's opening, None in a closed loop) and
    prompts are made lazily by :func:`prompt_for` from (seed, i).
    ``stretch`` spreads the instants out from the ramp's start (the
    sweep's other rates)."""
    ramp = float(mix.get("ramp_s", 0.0))
    if mix["loop"] == "closed":
        reqs = [{"i": i, "due": None, "prompt_len": int(p), "max_tokens": int(o)}
                for i, (p, o) in enumerate(mix["requests"])]
        return {"loop": "closed", "ramp_s": ramp,
                "clients": int(mix["clients_per_slot"]) * int(max_batch_size),
                "requests": reqs, "tail": []}
    rows = [{"i": i, "due": (d + ramp) * stretch - ramp,
             "prompt_len": int(p), "max_tokens": int(o)}
            for i, (d, p, o) in enumerate(mix["requests"])]
    if rows[-1]["due"] < seconds:
        raise ValueError(f"the mix's rows end at {rows[-1]['due']:.1f} s, "
                         f"the run's window at {seconds:.1f} s")
    # past the close: uncounted load, as far as the file's rows reach
    end = seconds + float(mix.get("tail_max_s", 40.0))
    return {"loop": "open", "ramp_s": ramp, "clients": 0,
            "requests": [r for r in rows if -ramp <= r["due"] <= seconds],
            "tail": [r for r in rows if seconds < r["due"] <= end]}


def prompt_for(seed: int, i: int, prompt_len: int) -> str:
    return prompt_text(prompt_len, random.Random((seed << 20) ^ (i * 2654435761)))


def realised_means(reqs: list[dict]) -> tuple[float, float]:
    n = max(1, len(reqs))
    return (sum(r["prompt_len"] for r in reqs) / n,
            sum(r["max_tokens"] for r in reqs) / n)


def traffic_path(root: str, name: str) -> str:
    return os.path.join(root, "perfbench", "traffic", name + ".json")
