"""Batched SSE delivery (engine/server.py): a stream is handed one item
per engine step, answers it with one render pass and one socket write,
and renders incrementally (engine/tokenizer.py ``detokenizer``).

The bytes a client reads must not move: every case below streams a fixed
token stream, cut into hand-offs, through the real ``stream_completion``
and ``_send_sse``, and compares the de-chunked SSE body with what the
whole-list renderer kept here as the oracle gives for the same tokens,
one ``data:`` event per token either way."""

import itertools
import json
import random
import sys
import time
import types
import uuid

import pytest

from fusioninfer_tpu.engine import server as srvmod
from fusioninfer_tpu.engine.engine import StepOutput
from fusioninfer_tpu.engine.server import (
    _FINGERPRINT,
    EngineServer,
    _Chunk,
    _find_stop,
    _held_back,
    _MultiChannel,
    _piece,
    _RequestChannel,
)
from fusioninfer_tpu.engine.tokenizer import (
    ByteTokenizer,
    OffsetDetokenizer,
    TrieTokenizer,
    detokenizer,
)
from fusioninfer_tpu.utils import spans

TRIE = TrieTokenizer([b"lo", b" w", "中".encode(), b"\xe4\xb8", b"\xad\xe6",
                      "🎉".encode()[:3], b"ab"])


def _oracle_stream_chunks(self, chan, chat, stops=(), served_model="",
                          choice_index=0, completion_id="", created=0,
                          echo_prefix="", usage_counts=None):
    """The renderer as it was before incremental detokenisation: every
    token decodes the whole list so far.  The oracle; do not update."""
    completion_id = completion_id or (
        f"{'chatcmpl' if chat else 'cmpl'}-{uuid.uuid4().hex[:12]}")
    created = created or int(time.time())
    tokens: list[int] = []
    emitted = 0
    try:
        for out in chan.stream():
            if out is None:
                return
            t0 = self.metrics.stream.now()
            with spans.annotation("stream.render"):
                is_error = (out.finish_reason or "").startswith("error")
                counted = not is_error and not (
                    out.finished and out.finish_reason == "stop"
                    and out.token == self.tokenizer.eos_token_id)
                if counted:
                    tokens.append(out.token)
                full = self.tokenizer.decode(tokens)
                finish = (out.finish_reason or "length") if out.finished else None
                if stops:
                    hit = _find_stop(full, stops)
                    if hit is not None:
                        full, finish = full[:hit], "stop"
                        while tokens and len(
                                self.tokenizer.decode(tokens[:-1])) >= hit:
                            tokens.pop()
                            counted = False
                        self._cancel_chan(chan)
                    elif not out.finished:
                        full = full[: len(full) - _held_back(full, stops)]
                if finish is None:
                    full = full[:len(full.rstrip("�"))]
                delta, emitted = full[emitted:], max(emitted, len(full))
                if echo_prefix:
                    delta, echo_prefix = echo_prefix + delta, ""
                if chat:
                    choice = {"index": choice_index, "delta": {"content": delta},
                              "finish_reason": finish}
                    if out.logprob is not None and counted:
                        choice["logprobs"] = {"content": [{
                            "token": _piece(self.tokenizer, out.token),
                            "logprob": out.logprob,
                            "top_logprobs": [
                                {"token": _piece(self.tokenizer, t),
                                 "logprob": v}
                                for t, v in (out.top_logprobs or {}).items()
                            ],
                        }]}
                    obj = "chat.completion.chunk"
                else:
                    lp = None
                    if out.logprob is not None and counted:
                        lp = {"tokens": [_piece(self.tokenizer, out.token)],
                              "token_logprobs": [out.logprob],
                              "top_logprobs": [out.top_logprobs or {}]}
                    choice = {"index": choice_index, "text": delta,
                              "finish_reason": finish, "logprobs": lp}
                    if counted:
                        choice["token_id"] = out.token
                    obj = "text_completion"
                if is_error and out.retry_after_s is not None:
                    choice["retry_after_s"] = out.retry_after_s
                chunk = _Chunk({
                    "id": completion_id,
                    "object": obj,
                    "created": created,
                    "model": served_model or self.model_name,
                    "system_fingerprint": _FINGERPRINT,
                    "choices": [choice],
                })
            chunk.published_ns = chan.published_ns
            chunk.render_ns = self.metrics.stream.now() - t0
            yield chunk
            if finish is not None:
                break
    finally:
        if usage_counts is not None:
            usage_counts.append(len(tokens))
        self._release(chan)
    yield None


class _StubEngine:
    """Admits and cancels; the test plays the engine's hand-offs."""

    class _Cfg:
        vocab_size = 4096

    cfg = _Cfg()
    guided_enabled = True  # skips the guided-vocab bootstrap

    def __init__(self):
        self.cancelled: list = []

    def add_request(self, request):
        pass

    def cancel(self, request_id):
        self.cancelled.append(request_id)

    def has_work(self):
        return False

    def step(self):
        return []

    def fail_all(self, reason, retry_after_s=None):
        return []


class _Wire:
    """A handler's ``wfile``: every write kept apart."""

    def __init__(self):
        self.writes: list[bytes] = []

    def write(self, data: bytes) -> int:
        self.writes.append(bytes(data))
        return len(data)

    def flush(self):
        pass


def _dechunk(writes: list[bytes]) -> tuple[bytes, list[int]]:
    """The SSE body under the chunked transfer coding, and for each
    write after the headers the number of ``data:`` events it carried."""
    head, _, rest = b"".join(writes).partition(b"\r\n\r\n")
    assert b"Transfer-Encoding: chunked" in head
    body, at = b"", 0
    while True:
        eol = rest.index(b"\r\n", at)
        size = int(rest[at:eol], 16)
        if size == 0:
            assert rest[eol:] == b"\r\n\r\n"
            break
        body += rest[eol + 2:eol + 2 + size]
        assert rest[eol + 2 + size:eol + 4 + size] == b"\r\n"
        at = eol + 4 + size
    return body, [w.count(b"data: ") for w in writes[1:]]


def _outputs(tokens, finish="length", **kw):
    """One output a token, the last finished; ``kw`` per-token fields."""
    return [StepOutput(request_id="r", token=t, finished=i == len(tokens) - 1,
                       finish_reason=finish if i == len(tokens) - 1 else None,
                       **{k: v[i] for k, v in kw.items()})
            for i, t in enumerate(tokens)]


def _cut(outputs, rng) -> list[list]:
    """Hand-offs of 1-4 outputs, as a burst engine's steps give them."""
    out, at = [], 0
    while at < len(outputs):
        n = rng.randint(1, 4)
        out.append(outputs[at:at + n])
        at += n
    return out


def _fix_ids(monkeypatch) -> None:
    """Ids from a fresh counter and one clock second: two runs that make
    the same calls get the same ``completion_id`` and ``created``."""
    counter = itertools.count(1)
    monkeypatch.setattr(srvmod, "uuid", types.SimpleNamespace(
        uuid4=lambda: uuid.UUID(int=next(counter))))
    monkeypatch.setattr(time, "time", lambda: 1_760_000_000.0)


def _serve(tokenizer):
    return EngineServer(model="stub", host="127.0.0.1", port=0,
                        engine=_StubEngine(), tokenizer=tokenizer)


def _handler(srv):
    handler_cls = srv._make_handler()
    h = handler_cls.__new__(handler_cls)
    h.wfile = _Wire()
    h.request_version = "HTTP/1.1"
    h.requestline = "POST /v1/completions HTTP/1.1"
    h.command = "POST"
    return h


def _channels(chan):
    return chan.chans if isinstance(chan, _MultiChannel) else [chan]


def _batched(tokenizer, body, chat, handoffs):
    """The server as it is: each hand-off one ``put``; the body and how
    many token chunks each write carried."""
    srv = _serve(tokenizer)
    chan, gen = srv.stream_completion(body, chat=chat)
    for c, steps in zip(_channels(chan), handoffs):
        for step in steps:
            c.put(step if len(step) > 1 else step[0])
    h = _handler(srv)
    h._send_sse(gen, chan)
    srv.abort(chan)
    return (*_dechunk(h.wfile.writes), srv)


def _oracle(tokenizer, body, chat, handoffs, monkeypatch):
    """The same tokens through the whole-list renderer, one event each."""
    srv = _serve(tokenizer)
    with monkeypatch.context() as m:
        m.setattr(EngineServer, "_stream_chunks", _oracle_stream_chunks)
        chan, gen = srv.stream_completion(body, chat=chat)
        for c, steps in zip(_channels(chan), handoffs):
            for out in itertools.chain.from_iterable(steps):
                c.put(out)
        events = [b"data: [DONE]\n\n" if c is None else
                  f"data: {json.dumps(c)}\n\n".encode() for c in gen]
    srv.abort(chan)
    return b"".join(events)


BYTE = ByteTokenizer()


def _ids(tok, text: str) -> list[int]:
    return tok.encode(text, add_bos=False)


def _raw(data: bytes) -> list[int]:
    """Byte ids for bytes that need not be UTF-8."""
    return [b + ByteTokenizer.OFFSET for b in data]


CASES = {
    # (tokenizer, body fields, the tokens' outputs)
    "utf8-split": (BYTE, {}, _outputs(_ids(BYTE, "añ中文🎉 ok ü"))),
    "utf8-split-trie": (TRIE, {}, _outputs(_ids(TRIE, "中文 lo wo 🎉ab"))),
    "invalid-bytes": (BYTE, {}, _outputs(_raw(
        b"A\xffB\xe4\xb8C\xf0\x9f\x98\x80D\xc3\xe4\xb8"))),
    "stop-straddles": (BYTE, {"stop": ["lo w"]},
                       _outputs(_ids(BYTE, "hello world and more"))),
    "stop-straddles-trie": (TRIE, {"stop": ["o w", "zz"]},
                            _outputs(_ids(TRIE, "hello world and more"))),
    "stop-after-utf8": (BYTE, {"stop": ["文 "]},
                        _outputs(_ids(BYTE, "中文 tail"))),
    # "a" ships before its stop is seen: the unfinished 中 is not a stop
    # prefix, so nothing is held back, and the match starts in sent text
    "stop-ends-in-utf8": (BYTE, {"stop": ["a中"]},
                          _outputs(_ids(BYTE, "xa中yz"))),
    "stop-held-then-not": (BYTE, {"stop": ["abcd"]},
                           _outputs(_ids(BYTE, "xabcabxabcz"))),
    "echo": (BYTE, {"echo": True}, _outputs(_ids(BYTE, "echoed"))),
    "logprobs": (BYTE, {"logprobs": 2}, _outputs(
        _ids(BYTE, "lp中"),
        logprob=[-0.5, -1.0, -0.25, -2.0, -0.125],
        top_logprobs=[{70: -0.5, 71: -1.5}, {72: -1.0}, {}, {200: -2.0},
                      {201: -0.125, 202: -3.0}])),
    "eos-at-end": (BYTE, {}, _outputs(_ids(BYTE, "done") + [ByteTokenizer.EOS_ID],
                                      finish="stop")),
    "usage": (BYTE, {"stream_options": {"include_usage": True},
                     "stop": ["d x"]}, _outputs(_ids(BYTE, "word xyz"))),
    "error-finish": (BYTE, {}, _outputs(
        _ids(BYTE, "partial") + [0], finish="error:slice lost",
        retry_after_s=[None] * 7 + [1.0])),
}


def _body(fields: dict, chat: bool) -> dict:
    body = {"prompt": "p", "messages": [{"role": "user", "content": "p"}],
            "stream": True, **fields}
    if chat and "logprobs" in fields:  # chat's form of the same ask
        body.update(logprobs=True, top_logprobs=fields["logprobs"])
    return body


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("chat", [False, True], ids=["completions", "chat"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_batched_body_is_byte_identical_to_the_whole_list_renderer(
        case, chat, seed, monkeypatch):
    tok, fields, outputs = CASES[case]
    body = _body(fields, chat)
    handoffs = [_cut(outputs, random.Random(seed))]
    _fix_ids(monkeypatch)
    got, per_write, srv = _batched(tok, body, chat, handoffs)
    _fix_ids(monkeypatch)
    assert got == _oracle(tok, body, chat, handoffs, monkeypatch)
    if "stop" not in fields:
        # one event a token; one write a hand-off, the last one's
        # carrying [DONE] and the chunked EOF
        assert got.count(b"data: ") == len(outputs) + 1
        assert len(per_write) == len(handoffs[0])
        assert sum(per_write) == len(outputs) + 1
        stream = srv.metrics.stream
        assert (stream.writes, stream.chunks) == (len(handoffs[0]),
                                                  len(outputs))


@pytest.mark.parametrize("n", [2, 6])
@pytest.mark.parametrize("chat", [False, True], ids=["completions", "chat"])
def test_choices_each_stream_what_the_oracle_streams(chat, n, monkeypatch):
    """n > 1: the choices' pump threads and the writer read one another's
    hand-off state, and interleave as they run (here with a switch every
    few microseconds), so each choice's events, and the closing usage
    and [DONE], are compared apart: none lost, none out of order."""
    tok = ByteTokenizer()
    body = {"prompt": "p", "messages": [{"role": "user", "content": "p"}],
            "stream": True, "n": n, "stop": ["zz"],
            "stream_options": {"include_usage": True}}
    rng = random.Random(7)
    texts = ["first ü choice", "second 中 azzb"] + [
        "more ünïcode 中文 " * 8 + str(i) for i in range(n - 2)]
    handoffs = [_cut(_outputs(_ids(tok, t)), rng) for t in texts]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(5e-6)
    try:
        _fix_ids(monkeypatch)
        got = _batched(tok, body, chat, handoffs)[0]
    finally:
        sys.setswitchinterval(interval)
    _fix_ids(monkeypatch)
    want = _oracle(tok, body, chat, handoffs, monkeypatch)

    def by_choice(raw: bytes) -> dict:
        out: dict = {}
        for event in raw.split(b"\n\n")[:-1]:
            payload = event.removeprefix(b"data: ")
            choices = (None if payload == b"[DONE]"
                       else json.loads(payload)["choices"])
            key = choices[0]["index"] if choices else "end"
            out.setdefault(key, []).append(event)
        return out

    assert by_choice(got) == by_choice(want)
    assert set(by_choice(got)) == {*range(n), "end"}


def test_a_channel_yields_a_hand_off_one_output_at_a_time():
    ch = _RequestChannel()
    a, b = (types.SimpleNamespace(finished=False) for _ in range(2))
    end = types.SimpleNamespace(finished=True)
    ch.put([a, b])
    ch.put(end)
    seen = []
    for item in ch.stream():
        seen.append((item, ch.ready()))
    # more of the hand-off ready after a; after b the stream waits; the
    # terminal output leaves nothing to wait for
    assert seen == [(a, True), (b, False), (end, True)]


def test_a_channel_left_early_waits_for_nothing():
    """A consumer that stops reading (a stop string mid-hand-off) must
    not keep its n > 1 siblings' events from being written."""
    ch, other = _RequestChannel(), _RequestChannel()
    ch.put([types.SimpleNamespace(finished=False) for _ in range(3)])
    gen = ch.stream()
    next(gen)
    assert ch.ready() and _MultiChannel([ch, other]).ready()
    gen.close()
    assert not _MultiChannel([ch, other]).ready()
    other.ended = True
    assert _MultiChannel([ch, other]).ready()


def test_publish_hands_each_request_its_step_in_one_put():
    srv = _serve(ByteTokenizer())
    chans = [srv.submit([1, 2], srvmod.SamplingParams(max_tokens=4))
             for _ in range(2)]
    with srv._lock:
        rids = list(srv._channels)
    outs = [StepOutput(request_id=rids[i % 2], token=10 + i, finished=False,
                       is_first_token=i < 2) for i in range(6)]
    srv._publish(outs)
    first = chans[0].q.get_nowait()[0]
    second = chans[1].q.get_nowait()[0]
    assert [o.token for o in first] == [10, 12, 14]
    assert [o.token for o in second] == [11, 13, 15]
    assert chans[0].q.empty() and chans[1].q.empty()
    # every output is still observed: 2 first tokens, 4 gaps
    assert srv.metrics.ttft.n == 2 and srv.metrics.tpot.n == 4
    srv._publish([StepOutput(request_id=rids[0], token=9, finished=False)])
    assert chans[0].q.get_nowait()[0].token == 9  # one output: as it was


@pytest.mark.parametrize("tok", [ByteTokenizer(), TRIE],
                         ids=["byte", "trie"])
@pytest.mark.parametrize("seed", range(4))
def test_incremental_detokenisation_equals_the_whole_list_decode(tok, seed):
    """Seeded random ids (valid text, stray and out-of-vocabulary ids),
    cut into random hand-offs: after every hand-off the text delivered so
    far, with its unfinished tail held back, is the whole-list decode
    with its own held back; at the end nothing is held."""
    rng = random.Random(seed)
    for _ in range(60):
        text = "".join(chr(rng.choice([rng.randrange(32, 127),
                                       rng.randrange(0xA0, 0x800),
                                       rng.randrange(0x4E00, 0x9FFF),
                                       rng.randrange(0x1F300, 0x1FAFF)]))
                       for _ in range(rng.randrange(1, 16)))
        ids = [i if rng.random() > 0.1 else rng.randrange(-1, tok.vocab_size + 2)
               for i in _ids(tok, text)]
        for detok in (detokenizer(tok), OffsetDetokenizer(tok.decode)):
            seen, delivered, tail = [], "", ""
            for step in _cut(ids, rng):
                for i in step:
                    stable, tail = detok.add(i)
                    delivered += stable
                seen += step
                whole = tok.decode(seen)
                assert delivered + tail == whole
                assert (delivered + tail).rstrip("�") == whole.rstrip("�")
                assert "�" not in tail.rstrip("�")  # held: the tail alone
            assert delivered + tail == tok.decode(ids)


def test_byte_tokenizers_offer_the_utf8_detokeniser():
    from fusioninfer_tpu.engine.tokenizer import Utf8Detokenizer

    assert isinstance(detokenizer(ByteTokenizer()), Utf8Detokenizer)
    assert isinstance(detokenizer(TRIE), Utf8Detokenizer)

    class Letters:  # any other decode: offsets over it
        def decode(self, ids):
            return "".join(chr(97 + i % 26) for i in ids)

    assert isinstance(detokenizer(Letters()), OffsetDetokenizer)
