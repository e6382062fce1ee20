"""The KV fabric: layer-streamed PD transfer + cross-engine prefix pull.

Covers the versioned wire envelope (round-trip, unknown-version
rejection, legacy-frame coexistence), out-of-order stream assembly ==
the monolithic slab, the streamed PD pair generating exactly what one
monolithic engine generates (greedy + seeded-sampled + int8 KV), chaos
on both fabric paths (every fault degrades to recompute, bit-identical,
never a corrupt page), the cross-engine ``/v1/kv_export`` demand pull,
and the leader-coordinated multi-process host tier (simulated pair in
SPMD lockstep; docs/design/pd-disaggregation.md)."""

import dataclasses
import json
import random
import urllib.request
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from fusioninfer_tpu.engine import kv_fabric
from fusioninfer_tpu.engine.engine import NativeEngine, Request
from fusioninfer_tpu.engine.kv_cache import CacheConfig, init_kv_cache
from fusioninfer_tpu.engine.kv_fabric import (
    SITE_PULL,
    SITE_PULL_DATA,
    SITE_STREAM,
    SITE_STREAM_DATA,
    KVFabric,
    KVFabricError,
    SlabAssembler,
    StreamIntake,
    slab_to_frames,
)
from fusioninfer_tpu.engine.kv_host_tier import HostKVTier
from fusioninfer_tpu.engine.kv_transfer import (
    KVSlabCorrupt,
    KVWireVersionError,
    extract_slab,
    is_fabric_frame,
    pack_frame,
    slab_from_bytes,
    slab_to_bytes,
    unpack_frame,
)
from fusioninfer_tpu.engine.prefix_cache import block_hashes
from fusioninfer_tpu.engine.sampler import SamplingParams
from fusioninfer_tpu.engine.server import EngineServer
from fusioninfer_tpu.models.config import get_preset
from fusioninfer_tpu.resilience import FaultInjector

CFG = get_preset("qwen3-tiny")
CACHE = CacheConfig(n_pages=33, page_size=8, max_pages_per_seq=8)
INT8 = dataclasses.replace(CACHE, kv_dtype="int8")

PROMPT = [3, 1, 4, 1, 5, 9, 2, 6] * 5  # 40 tokens -> 5 full 8-token pages


def _greedy(max_tokens=8):
    return SamplingParams(temperature=0.0, max_tokens=max_tokens)


def _drain(engine, max_steps=200):
    outputs = {}
    for _ in range(max_steps):
        if not engine.has_work():
            break
        for out in engine.step():
            outputs.setdefault(out.request_id, []).append(out.token)
    return outputs


def _mono(params, cache_cfg=CACHE, prompt=PROMPT, **kw):
    engine = NativeEngine(CFG, cache_cfg=cache_cfg, max_batch_size=4,
                          seed=0, **kw)
    engine.add_request(Request("r", list(prompt), params))
    return _drain(engine)["r"]


def _stream_frames(prefiller, request):
    """Run one streamed prefill on the prefiller, return the raw frame
    bytes in push order."""
    raw: list[bytes] = []
    fut = prefiller.request_prefill_stream(request, raw.append)
    prefiller.step()
    n = fut.result(timeout=30)
    assert n == len(raw) and n >= 2  # at least one KV frame + meta
    return raw


def _feed_decoder(decoder, request, raw, shuffle=None):
    intake = StreamIntake(request.request_id)
    decoder.add_prefilled_stream(request, intake)
    if shuffle is not None:
        raw = list(raw)
        random.Random(shuffle).shuffle(raw)
    for b in raw:
        intake.feed_bytes(b)
    intake.close()
    return intake


# -- wire envelope -----------------------------------------------------------


def _demo_slab(cache_cfg=CACHE, pages=(3, 7, 1), tokens=(9, 8, 7, 6, 5)):
    cache = init_kv_cache(CFG, cache_cfg)
    k = jnp.arange(np.prod(cache["k"].shape)).reshape(cache["k"].shape)
    cache = dict(cache, k=(k % 13).astype(cache["k"].dtype),
                 v=(k % 7).astype(cache["v"].dtype))
    return extract_slab(cache, list(pages), list(tokens),
                        first_token=42, page_size=cache_cfg.page_size)


def _assert_slabs_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.k, np.float32),
                                  np.asarray(b.k, np.float32))
    np.testing.assert_array_equal(np.asarray(a.v, np.float32),
                                  np.asarray(b.v, np.float32))
    assert a.quantized == b.quantized
    if a.quantized:
        np.testing.assert_array_equal(np.asarray(a.k_scale, np.float32),
                                      np.asarray(b.k_scale, np.float32))


class TestWireEnvelope:
    def test_frame_roundtrip_bf16(self):
        slab = _demo_slab()
        frames = slab_to_frames(slab, "rid")
        back = SlabAssembler()
        for f in frames:
            back.feed(kv_fabric.frame_from_bytes(
                kv_fabric.frame_to_bytes(f)))
        assert back.complete
        out = back.slab()
        assert out.prompt_tokens == [9, 8, 7, 6, 5]
        assert out.first_token == 42 and out.page_size == 8
        _assert_slabs_equal(out, slab)

    def test_frame_roundtrip_int8_scales(self):
        slab = _demo_slab(cache_cfg=INT8)
        assert slab.quantized
        back = SlabAssembler()
        for f in slab_to_frames(slab, "q"):
            back.feed(kv_fabric.frame_from_bytes(
                kv_fabric.frame_to_bytes(f)))
        _assert_slabs_equal(back.slab(), slab)

    def test_unknown_wire_version_rejected_not_retryable(self):
        data = pack_frame({"request_id": "x", "seq": 0}, b"abc", version=9)
        with pytest.raises(KVWireVersionError, match="version 9"):
            unpack_frame(data)
        try:
            unpack_frame(data)
        except KVWireVersionError as e:
            assert not e.retryable  # version skew never heals by retry

    def test_corrupt_and_truncated_frames_rejected(self):
        data = kv_fabric.frame_to_bytes(
            slab_to_frames(_demo_slab(), "r")[0])
        flipped = data[:-1] + bytes([data[-1] ^ 0xFF])
        with pytest.raises(KVSlabCorrupt):
            unpack_frame(flipped)
        with pytest.raises(KVSlabCorrupt):
            unpack_frame(data[: len(data) // 2])
        with pytest.raises(KVSlabCorrupt):
            unpack_frame(b"FIKF")

    def test_legacy_slab_frames_coexist(self):
        # the fabric magic is disjoint from FIKV1/FIKV2: both wire
        # formats sniff apart in one compare and the legacy parser
        # still owns its own frames untouched
        slab = _demo_slab()
        legacy = slab_to_bytes(slab)
        fabric = kv_fabric.frame_to_bytes(slab_to_frames(slab, "r")[0])
        assert not is_fabric_frame(legacy)
        assert is_fabric_frame(fabric)
        _assert_slabs_equal(slab_from_bytes(legacy), slab)
        with pytest.raises(ValueError, match="not a KV slab"):
            slab_from_bytes(fabric)  # legacy door rejects fabric frames


# -- assembly ----------------------------------------------------------------


class TestAssembler:
    def test_out_of_order_assembly_matches_slab(self):
        slab = _demo_slab()
        frames = slab_to_frames(slab, "r", layer_groups=2)
        for seed in (1, 2, 3):
            shuffled = list(frames)
            random.Random(seed).shuffle(shuffled)
            asm = SlabAssembler()
            for f in shuffled:
                assert not asm.complete or f is shuffled[-1]
                asm.feed(f)
            assert asm.complete
            _assert_slabs_equal(asm.slab(), slab)
        assert asm.overlap_fraction == 0.0  # whole-slab shim: no overlap

    def test_duplicate_and_overlap_and_foreign_rejected(self):
        frames = slab_to_frames(_demo_slab(), "r")
        asm = SlabAssembler()
        asm.feed(frames[0])
        with pytest.raises(KVFabricError, match="duplicate"):
            asm.feed(frames[0])
        clone = dataclasses.replace(frames[0], seq=99)
        with pytest.raises(KVFabricError, match="overlap"):
            asm.feed(clone)
        with pytest.raises(KVFabricError, match="stream"):
            asm.feed(dataclasses.replace(frames[1], request_id="other"))
        assert not asm.complete and "meta" in asm.missing()

    def test_overlap_fraction_math(self):
        slab = _demo_slab()
        frames = kv_fabric.split_slab(
            slab, "r", page_start=0, n_pages_total=3, prompt_len=24,
            during_prefill=True, start_seq=0, layer_groups=1)
        frames += kv_fabric.split_slab(
            slab, "r", page_start=0, n_pages_total=3, prompt_len=24,
            during_prefill=False, start_seq=1, layer_groups=1)
        asm = SlabAssembler(keep_frames=False)
        asm.feed(frames[0])
        with pytest.raises(KVFabricError):
            asm.feed(frames[1])  # same cells: overlap is a fault
        assert asm.overlap_fraction == 1.0  # only the overlapped one fed


# -- streamed PD pair ========================================================


class TestStreamedPD:
    def _pair(self, params, cache_cfg=CACHE, shuffle=None, prompt=PROMPT,
              **engine_kw):
        prefiller = NativeEngine(CFG, cache_cfg=cache_cfg, max_batch_size=4,
                                 seed=0, **engine_kw)
        decoder = NativeEngine(CFG, cache_cfg=cache_cfg, max_batch_size=4,
                               seed=0, **engine_kw)
        raw = _stream_frames(prefiller, Request("r", list(prompt), params))
        _feed_decoder(decoder, Request("r", list(prompt), params), raw,
                      shuffle=shuffle)
        return prefiller, decoder, _drain(decoder).get("r", [])

    def test_greedy_matches_monolithic(self):
        params = _greedy()
        prefiller, decoder, got = self._pair(params)
        assert got == _mono(params)
        assert decoder.kv_stream_admissions_total == 1
        assert decoder.kv_stream_fallbacks_total == 0
        assert decoder.prompt_tokens_total == 0  # never prefilled locally
        # prefiller kept nothing resident
        assert prefiller.kv_cache_usage() == 0.0

    def test_seeded_sampled_matches_monolithic(self):
        params = SamplingParams(temperature=0.9, top_p=0.9, seed=1234,
                                max_tokens=8)
        _, decoder, got = self._pair(params)
        assert got == _mono(params)

    def test_int8_kv_matches_monolithic(self):
        for params in (_greedy(),
                       SamplingParams(temperature=0.8, seed=42,
                                      max_tokens=6)):
            _, decoder, got = self._pair(params, cache_cfg=INT8)
            assert got == _mono(params, cache_cfg=INT8)
            assert decoder.kv_stream_admissions_total == 1

    def test_out_of_order_arrival_matches(self):
        # DCN reorders: the assembler sequences frames, admission is
        # identical to in-order delivery
        params = _greedy()
        _, decoder, got = self._pair(params, shuffle=7)
        assert got == _mono(params)

    def test_transfer_overlap_fraction(self):
        # 40-token prompt, 16-token chunks: pages 0..3 stream DURING
        # the forward, only the final page + meta trail it
        _, decoder, _ = self._pair(_greedy())
        total = decoder.kv_stream_bytes_total
        overlapped = decoder.kv_stream_overlapped_bytes_total
        assert total > 0 and overlapped / total >= 0.5

    def test_guided_first_token_replayed(self):
        from fusioninfer_tpu.engine.guided import build_token_byte_table
        from fusioninfer_tpu.engine.tokenizer import ByteTokenizer

        table = build_token_byte_table(ByteTokenizer(), CFG.vocab_size)
        params = SamplingParams(temperature=0.9, max_tokens=20, seed=7,
                                guided_json=True)
        prompt = ByteTokenizer().encode("json please, streamed")
        _, decoder, got = self._pair(params, prompt=prompt,
                                     token_byte_table=table)
        assert got == _mono(params, prompt=prompt, token_byte_table=table)

    def test_cross_precision_stream_int8_to_bf16(self):
        # int8 frames dequantize into a bf16 decoder's cache at the
        # inject boundary — streaming composes with mixed precision
        params = _greedy(max_tokens=4)
        prefiller = NativeEngine(CFG, cache_cfg=INT8, max_batch_size=2,
                                 seed=0)
        decoder = NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2,
                               seed=0)
        raw = _stream_frames(prefiller, Request("x", PROMPT, params))
        _feed_decoder(decoder, Request("x", PROMPT, params), raw)
        got = _drain(decoder)["x"]
        assert len(got) == 4 and decoder.kv_stream_admissions_total == 1

    def test_streamed_kv_matches_slab_path(self):
        # chunked windows may reduce in a different order than the
        # monolithic padded window, so allow an odd bf16 ulp on the
        # values, at the scale of their head vector (a projection's sum
        # and the rotary pair mix a vector's components: one that
        # cancels to near zero carries its partners' rounding step, 16
        # ulps of its own value; here the first layer is bit-equal and
        # 80 + 77 of the second's 10 240 values move, none by more);
        # everything else (metadata, first token, layout) is exact and
        # the decoded outputs are bit-identical (tests above)
        params = _greedy()
        slab_engine = NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2,
                                   seed=0)
        fut = slab_engine.request_prefill_slab(
            Request("r", list(PROMPT), params))
        slab_engine.step()
        slab = fut.result(timeout=30)

        stream_engine = NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2,
                                     seed=0)
        raw = _stream_frames(stream_engine, Request("r", list(PROMPT), params))
        asm = SlabAssembler()
        for b in raw:
            asm.feed(kv_fabric.frame_from_bytes(b))
        assert asm.complete
        out = asm.slab()
        assert out.first_token == slab.first_token
        assert out.prompt_tokens == slab.prompt_tokens
        assert out.quantized == slab.quantized
        for got, want in ((out.k, slab.k), (out.v, slab.v)):
            got, want = (np.asarray(x, np.float32) for x in (got, want))
            top = np.abs(want).max(axis=-1, keepdims=True)
            ulp = 2.0 ** (np.floor(np.log2(np.maximum(top, 2.0 ** -126)))
                          - 7)  # bfloat16: 8 significant bits
            assert (np.abs(got - want) <= ulp).all()
        assert asm.overlap_fraction >= 0.5

    def test_incomplete_stream_falls_back_to_local_prefill(self):
        params = _greedy()
        prefiller = NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2,
                                 seed=0)
        decoder = NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2,
                               seed=0)
        raw = _stream_frames(prefiller, Request("r", list(PROMPT), params))
        _feed_decoder(decoder, Request("r", list(PROMPT), params),
                      raw[:-2])  # truncated: last KV frame + meta lost
        got = _drain(decoder)["r"]
        assert decoder.kv_stream_fallbacks_total == 1
        assert decoder.prompt_tokens_total == len(PROMPT)  # re-prefilled
        assert got == _mono(params)  # bit-identical despite the fault

    def test_failed_intake_releases_pages_and_falls_back(self):
        params = _greedy()
        prefiller = NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2,
                                 seed=0)
        decoder = NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2,
                               seed=0)
        raw = _stream_frames(prefiller, Request("r", list(PROMPT), params))
        intake = StreamIntake("r")
        decoder.add_prefilled_stream(Request("r", list(PROMPT), params),
                                     intake)
        for b in raw[:2]:
            intake.feed_bytes(b)
        decoder.step()  # pages adopted mid-stream
        intake.fail(RuntimeError("transport died"))
        got = _drain(decoder)["r"]
        assert decoder.kv_stream_fallbacks_total == 1
        assert got == _mono(params)
        assert decoder.alloc.free_pages == CACHE.n_pages - 1  # trash page

    def test_cancelled_intake_forgotten_silently(self):
        decoder = NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2,
                               seed=0)
        intake = StreamIntake("r")
        decoder.add_prefilled_stream(Request("r", list(PROMPT), _greedy()),
                                     intake)
        intake.cancel()
        assert _drain(decoder) == {}
        assert decoder.kv_stream_fallbacks_total == 0

    def test_duplicate_stream_request_id_rejected(self):
        decoder = NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2,
                               seed=0)
        decoder.add_prefilled_stream(Request("r", list(PROMPT), _greedy()),
                                     StreamIntake("r"))
        with pytest.raises(ValueError, match="request_id"):
            decoder.add_prefilled_stream(
                Request("r", list(PROMPT), _greedy()), StreamIntake("r"))


# -- chaos on the stream path ================================================


@pytest.mark.chaos
class TestStreamChaos:
    def _http_pair(self, fi=None, **decode_kw):
        prefill_srv = EngineServer(
            model="qwen3-tiny", host="127.0.0.1", port=0,
            engine=NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2,
                                seed=0))
        prefill_srv.start()
        decode_srv = EngineServer(
            model="qwen3-tiny", host="127.0.0.1", port=0,
            engine=NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2,
                                seed=0),
            prefill_upstream=f"http://127.0.0.1:{prefill_srv.port}",
            kv_fault_injector=fi, **decode_kw)
        decode_srv.start()
        return prefill_srv, decode_srv

    def _completion(self, port, prompt="hello fabric streaming!",
                    **extra):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions",
            data=json.dumps({
                "model": "qwen3-tiny", "prompt": prompt,
                "max_tokens": 6, "temperature": 0.0, **extra,
            }).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.load(r)

    def test_streamed_http_pair_matches_mono_and_overlaps(self):
        prefill_srv, decode_srv = self._http_pair()
        mono_srv = EngineServer(
            model="qwen3-tiny", host="127.0.0.1", port=0,
            engine=NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2,
                                seed=0))
        mono_srv.start()
        try:
            pd = self._completion(decode_srv.port)
            mono = self._completion(mono_srv.port)
            assert pd["choices"][0]["text"] == mono["choices"][0]["text"]
            assert pd["usage"] == mono["usage"]
            eng = decode_srv.engine
            assert eng.kv_stream_admissions_total == 1
            assert eng.prompt_tokens_total == 0  # never prefilled locally
            assert (eng.kv_stream_overlapped_bytes_total
                    / eng.kv_stream_bytes_total) >= 0.5
            # the A/B override: kv_stream=false rides the slab path
            slab = self._completion(decode_srv.port, kv_stream=False)
            assert slab["choices"][0]["text"] == mono["choices"][0]["text"]
            assert eng.kv_stream_admissions_total == 1  # unchanged
        finally:
            prefill_srv.stop()
            decode_srv.stop()
            mono_srv.stop()

    @pytest.mark.parametrize("mode,site,kwargs", [
        ("drop", SITE_STREAM, {"after": 2, "times": 1}),
        ("delay", SITE_STREAM, {"delay_s": 0.05, "times": 1}),
        ("error", SITE_STREAM, {"after": 1, "times": 1}),
        ("corrupt", SITE_STREAM_DATA, {"times": 1}),
    ])
    def test_stream_fault_degrades_bit_identical(self, mode, site, kwargs):
        fi = FaultInjector(seed=5).arm(site, mode, **kwargs)
        prefill_srv, decode_srv = self._http_pair(fi=fi)
        mono_srv = EngineServer(
            model="qwen3-tiny", host="127.0.0.1", port=0,
            engine=NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2,
                                seed=0))
        mono_srv.start()
        try:
            pd = self._completion(decode_srv.port)
            mono = self._completion(mono_srv.port)
            assert pd["choices"][0]["text"] == mono["choices"][0]["text"]
            assert pd["usage"] == mono["usage"]
            if mode != "delay":
                # the faulted stream degraded (engine-side local
                # re-prefill or connector-level fallback) — never wedged
                eng = decode_srv.engine
                assert (eng.kv_stream_fallbacks_total
                        + eng.prompt_tokens_total) > 0
            assert fi.fired_count(site) >= 1
        finally:
            prefill_srv.stop()
            decode_srv.stop()
            mono_srv.stop()

    def test_peer_without_stream_endpoint_demotes_to_slab(self):
        from fusioninfer_tpu.engine.kv_transfer import KVTransferError

        prefill_srv, decode_srv = self._http_pair()

        def legacy_404(*a, **kw):
            raise KVTransferError("not found: /v1/prefill_stream",
                                  status=404)

        decode_srv._pull_connector.pull_prefill_stream = legacy_404
        try:
            pd = self._completion(decode_srv.port)
            assert pd["usage"]["completion_tokens"] >= 1
            assert decode_srv._peer_stream_unsupported  # sticky demotion
            assert decode_srv.engine.kv_stream_admissions_total == 0
            assert decode_srv.engine.kv_stream_fallbacks_total == 0
            assert decode_srv.engine.prompt_tokens_total == 0  # slab path
        finally:
            prefill_srv.stop()
            decode_srv.stop()


# -- cross-engine prefix pull ================================================


TIER_CFG = dataclasses.replace(get_preset("qwen3-tiny"), dtype="float32")
TIER_CACHE = CacheConfig(n_pages=9, page_size=16, max_pages_per_seq=6)
WARM = list(range(1, 40))  # 39 tokens -> 2 full 16-token pages


def _tier_drain(engine, request):
    engine.add_request(request)
    toks = []
    while engine.has_work():
        for out in engine.step():
            if out.request_id == request.request_id:
                toks.append(out.token)
    return toks


def _churn(engine, n=3):
    for j in range(n):
        _tier_drain(engine, Request(
            f"churn-{j}", [500 + j * 41 + k for k in range(40)],
            SamplingParams(max_tokens=2, temperature=0.0)))


def _tier_engine(fi=None):
    tier = HostKVTier(fault_injector=fi, async_offload=False)
    return NativeEngine(TIER_CFG, cache_cfg=TIER_CACHE, max_batch_size=2,
                        host_kv_tier=tier), tier


class TestCrossEnginePull:
    def _warm_peer(self):
        """An engine whose host tier holds the WARM chain, wrapped in a
        server so /v1/kv_export answers demand pulls."""
        peer, tier = _tier_engine()
        params = SamplingParams(max_tokens=4, temperature=0.0)
        cold = _tier_drain(peer, Request("cold", WARM, params))
        _churn(peer)
        chain = block_hashes(WARM, TIER_CACHE.page_size)
        assert any(tier.contains(h) for h in chain)
        srv = EngineServer(model="qwen3-tiny", host="127.0.0.1", port=0,
                           engine=peer)
        srv.start()
        return srv, cold, params

    def test_kv_export_endpoint_serves_pairing_crc_frames(self):
        srv, _, _ = self._warm_peer()
        try:
            chain = block_hashes(WARM, TIER_CACHE.page_size)
            held = [h for h in chain
                    if srv.engine.host_kv_tier.contains(h)]
            qs = ",".join(h.hex() for h in held) + ",zz-bad-hex"
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/v1/kv_export?"
                    f"hashes={qs}&limit=8", timeout=10) as r:
                payload = json.load(r)
            frames = payload["frames"]
            assert {f["hash"] for f in frames} == {h.hex() for h in held}
            import base64
            for f in frames:
                data = base64.b64decode(f["data"])
                h = bytes.fromhex(f["hash"])
                assert kv_fabric.pairing_crc(h, data) == f["crc"]
                slab_from_bytes(data)  # parseable legacy page frame
        finally:
            srv.stop()

    def test_restore_pulls_missing_chain_from_peer(self):
        srv, cold, params = self._warm_peer()
        puller, tier = _tier_engine()
        puller.set_kv_fabric(KVFabric(
            peers=(f"http://127.0.0.1:{srv.port}",)))
        try:
            warm = _tier_drain(puller, Request("warm", WARM, params))
            assert warm == cold  # bit-identical via the pulled chain
            assert puller.kv_fabric_restored_blocks_total >= 1
            assert puller.sched.kv_restores_total >= 1
            assert puller.prompt_tokens_total < len(WARM) + 1
            # the pulled frames converged into OUR tier on the way in
            chain = block_hashes(WARM, TIER_CACHE.page_size)
            assert any(tier.contains(h) for h in chain)
        finally:
            srv.stop()

    def test_resolver_routes_the_pull(self):
        srv, cold, params = self._warm_peer()
        calls = []

        def resolver(hashes_hex):
            calls.append(list(hashes_hex))
            return {h: f"http://127.0.0.1:{srv.port}" for h in hashes_hex}

        puller, _ = _tier_engine()
        puller.set_kv_fabric(KVFabric(peers=(), resolver=resolver))
        try:
            warm = _tier_drain(puller, Request("warm", WARM, params))
            assert warm == cold
            assert calls and puller.kv_fabric_restored_blocks_total >= 1
        finally:
            srv.stop()

    @pytest.mark.chaos
    def test_pull_fault_degrades_to_recompute(self):
        srv, cold, params = self._warm_peer()
        try:
            for mode, site in (("drop", SITE_PULL), ("error", SITE_PULL),
                               ("corrupt", SITE_PULL_DATA)):
                fi = FaultInjector(seed=11).arm(site, mode)
                puller, _ = _tier_engine()
                fabric = KVFabric(
                    peers=(f"http://127.0.0.1:{srv.port}",),
                    fault_injector=fi)
                puller.set_kv_fabric(fabric)
                warm = _tier_drain(puller, Request("warm", WARM, params))
                assert warm == cold, f"{mode} corrupted the stream"
                if mode == "corrupt":
                    assert fabric.pull_rejected_total >= 1
                    assert puller.kv_fabric_restored_blocks_total == 0
                else:
                    assert fabric.pull_faults_total >= 1
                # recompute covered the chain locally
                assert puller.prompt_tokens_total >= len(WARM) - 1
        finally:
            srv.stop()

    def test_dead_peer_is_a_miss_not_an_error(self):
        params = SamplingParams(max_tokens=4, temperature=0.0)
        puller, _ = _tier_engine()
        fabric = KVFabric(peers=("http://127.0.0.1:9",), timeout_s=0.2)
        puller.set_kv_fabric(fabric)
        toks = _tier_drain(puller, Request("r", WARM, params))
        assert len(toks) == 4
        assert fabric.pull_faults_total >= 1

    def test_block_holders_resolves_from_residency(self):
        from fusioninfer_tpu.router.picker import (
            Endpoint,
            ResidencyProvider,
        )

        srv, _, _ = self._warm_peer()
        try:
            chain = block_hashes(WARM, TIER_CACHE.page_size)
            held = [h.hex() for h in chain
                    if srv.engine.host_kv_tier.contains(h)]
            eps = [Endpoint("peer", f"http://127.0.0.1:{srv.port}", {}),
                   Endpoint("self", "http://127.0.0.1:1", {})]
            rp = ResidencyProvider(ttl_s=60.0)
            holders = rp.block_holders(held + ["ff" * 16], eps,
                                       exclude="self")
            assert holders == {
                h: f"http://127.0.0.1:{srv.port}" for h in held}
        finally:
            srv.stop()


# -- leader-coordinated multi-process host tier ==============================


class TestMultiprocessHostTier:
    def test_broadcast_json_single_process_identity(self):
        from fusioninfer_tpu.engine import multihost

        obj = {"plan": ["aa"], "frames": ["YWJj"], "deferred": False}
        assert multihost.broadcast_json(obj, True) == obj
        assert multihost.broadcast_json(None, True) == {}

    def test_make_synchronous_commits_inline(self):
        tier = HostKVTier(async_offload=True)
        tier.make_synchronous()
        cache = init_kv_cache(TIER_CFG, TIER_CACHE)
        slab = extract_slab(cache, [0], [], 0, TIER_CACHE.page_size)
        tier.offload(b"h", slab)
        assert tier.contains(b"h")  # no flush needed

    def test_simulated_pair_lockstep_restore(self, monkeypatch):
        """Leader + diverged follower execute the SAME restore schedule:
        the leader's broadcast plan carries the frame bytes, so the
        follower adopts identical pages even for a block its own tier
        lost — and imports the frame, converging the tiers."""
        from fusioninfer_tpu.engine import multihost

        params = SamplingParams(max_tokens=4, temperature=0.0)
        leader, l_tier = _tier_engine()
        follower, f_tier = _tier_engine()
        # identical history on both processes (SPMD lockstep)
        for eng in (leader, follower):
            _tier_drain(eng, Request("cold", WARM, params))
            _churn(eng)
        chain = block_hashes(WARM, TIER_CACHE.page_size)
        held = [h for h in chain if l_tier.contains(h)]
        assert held and all(f_tier.contains(h) for h in held)
        # diverge the follower: one frame vanished from its tier
        f_tier._entries.pop(held[0])
        assert not f_tier.contains(held[0])

        sent: list = []

        def fake_broadcast(obj, is_leader):
            if is_leader:
                sent.append(obj)
            return dict(sent[-1]) if sent and sent[-1] else {}

        monkeypatch.setattr(multihost, "broadcast_json", fake_broadcast)
        for eng in (leader, follower):
            eng._mh = SimpleNamespace(is_leader=eng is leader)

        req = Request("warm", WARM, params)
        leader._restore_host_blocks(req, list(WARM))
        follower._restore_host_blocks(
            Request("warm", WARM, params), list(WARM))

        assert sent and sent[0]["plan"], "leader broadcast no plan"
        plan = [bytes.fromhex(h) for h in sent[0]["plan"]]
        assert leader.sched.kv_restores_total == len(plan)
        assert follower.sched.kv_restores_total == len(plan)
        for h in plan:
            assert leader.alloc.has_block(h)
            assert follower.alloc.has_block(h)
        # the follower re-imported the frame it had lost
        assert f_tier.contains(held[0])
        # identical H2D schedules: same pages adopted in the same order
        np.testing.assert_array_equal(
            np.asarray(leader.cache["k"], np.float32),
            np.asarray(follower.cache["k"], np.float32))

    def test_streamed_pd_refused_on_multiprocess_mesh(self):
        engine = NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=2,
                              seed=0)
        engine._mh = SimpleNamespace(is_leader=True)
        with pytest.raises(ValueError, match="single-process"):
            engine.request_prefill_stream(
                Request("r", list(PROMPT), _greedy()), lambda b: None)
        with pytest.raises(ValueError, match="single-process"):
            engine.add_prefilled_stream(
                Request("r", list(PROMPT), _greedy()), StreamIntake("r"))
