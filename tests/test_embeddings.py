"""/v1/embeddings: last-real-token pooled, L2-normalized embeddings."""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

from fusioninfer_tpu.engine.engine import NativeEngine
from fusioninfer_tpu.engine.kv_cache import CacheConfig
from fusioninfer_tpu.models.config import get_preset

CFG = get_preset("qwen3-tiny")
CACHE = CacheConfig(n_pages=33, page_size=16, max_pages_per_seq=4)


@pytest.fixture(scope="module")
def server():
    from fusioninfer_tpu.engine.server import EngineServer

    eng = NativeEngine(CFG, cache_cfg=CACHE, max_batch_size=4, seed=0)
    srv = EngineServer(model="qwen3-tiny", host="127.0.0.1", port=0, engine=eng)
    srv.start()
    yield srv
    srv.stop()


def _post(srv, body, timeout=300):
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/v1/embeddings",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return json.loads(urllib.request.urlopen(req, timeout=timeout).read())


class TestEmbeddings:
    def test_shape_norm_and_determinism(self, server):
        r = _post(server, {"model": "qwen3-tiny", "input": "hello world"})
        assert r["object"] == "list" and len(r["data"]) == 1
        v = np.asarray(r["data"][0]["embedding"])
        assert v.shape == (CFG.d_model,)
        assert abs(np.linalg.norm(v) - 1.0) < 1e-5
        r2 = _post(server, {"model": "qwen3-tiny", "input": "hello world"})
        np.testing.assert_allclose(v, np.asarray(r2["data"][0]["embedding"]),
                                   atol=1e-6)
        assert r["usage"]["prompt_tokens"] > 0

    def test_batch_input_indexed_and_distinct(self, server):
        r = _post(server, {"model": "qwen3-tiny",
                           "input": ["alpha", "a completely different text"]})
        assert [d["index"] for d in r["data"]] == [0, 1]
        a = np.asarray(r["data"][0]["embedding"])
        b = np.asarray(r["data"][1]["embedding"])
        assert abs(float(a @ b)) < 0.999  # not identical directions

    def test_batch_matches_singles(self, server):
        """Batched padding/pooling must equal one-at-a-time embedding."""
        texts = ["short", "a somewhat longer input text here"]
        batch = _post(server, {"input": texts})
        singles = [_post(server, {"input": t})["data"][0]["embedding"]
                   for t in texts]
        for i, s in enumerate(singles):
            np.testing.assert_allclose(
                np.asarray(batch["data"][i]["embedding"]), np.asarray(s),
                atol=2e-3)

    def test_bad_inputs_reject_400(self, server):
        for bad in ({}, {"input": ""}, {"input": []}, {"input": [1, 2]},
                    {"input": 5}, {"input": "x" * 100000},
                    {"input": ["ok", "y" * 100000]}):
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/v1/embeddings",
                data=json.dumps({"model": "qwen3-tiny", **bad}).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=30)
            assert ei.value.code == 400

    def test_coexists_with_completions(self, server):
        import threading

        results = {}

        def complete():
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/v1/completions",
                data=json.dumps({"model": "qwen3-tiny", "prompt": "hi",
                                 "max_tokens": 6, "temperature": 0.0}).encode(),
                headers={"Content-Type": "application/json"})
            results["c"] = json.loads(
                urllib.request.urlopen(req, timeout=300).read())

        def embed():
            results["e"] = _post(server, {"input": "concurrent embedding"})

        ts = [threading.Thread(target=complete), threading.Thread(target=embed)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert results["c"]["choices"][0]["finish_reason"] in ("length", "stop")
        assert len(results["e"]["data"]) == 1


def test_embeddings_on_sharded_mesh():
    """A dp×tp mesh serves /v1/embeddings through the same SPMD forward
    as generation — results match the single-device engine (the r4-era
    mesh rejection was stricter than the partitioner requires; only
    MULTI-PROCESS meshes still reject, since a one-process forward
    would desync the lockstep group)."""
    import dataclasses

    import jax
    import numpy as np

    from fusioninfer_tpu.parallel import MeshConfig, build_mesh

    cfg = dataclasses.replace(CFG, dtype="float32", attn_impl="reference")
    ref_eng = NativeEngine(cfg, cache_cfg=CACHE, max_batch_size=2, seed=0)
    f = ref_eng.request_embedding([3, 1, 4, 1, 5])
    ref_eng.step()
    ref = np.asarray(f.result(timeout=60))

    mesh = build_mesh(MeshConfig(dp=2, tp=2).validate(4), jax.devices()[:4])
    eng = NativeEngine(cfg, cache_cfg=CACHE, max_batch_size=2, seed=0,
                       mesh=mesh)
    f2 = eng.request_embedding([3, 1, 4, 1, 5])
    eng.step()
    got = np.asarray(f2.result(timeout=120))
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)
