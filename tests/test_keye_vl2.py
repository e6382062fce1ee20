"""Keye-VL-2.0-30B-A3B's language model through the normal serving path,
against its plain reference (``perfbench/arch/KeyeVL2.py``, loaded by
path as ``perfbench/work.py`` does): GQA with QK-norm whose queries
attend over the ``index_topk`` positions a learned indexer scores highest
(DeepSeek Sparse Attention), over paged indexer keys kept beside K/V; 128
routed experts (8 here) with a softmax over the chosen.

Tiny preset (top 24 of contexts to 90 over 16-token pages, so the
selection crosses pages), CPU, seeded weights, logits and not tokens.
``F32_TOL`` = 2e-3 on logits of magnitude ~1 with the model in float32:
program and reference then differ by the order of float32 sums only, and
the SAME positions are chosen (a position chosen apart moves a logit by
far more, ``test_a_different_choice_is_seen``); in bfloat16 the served
scores round, a near-tie at the 24th place flips and the comparison
reads 0.05-1."""

import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fusioninfer_tpu.engine import model_runner as mr
from fusioninfer_tpu.engine.engine import (
    _NOT_YET,
    NativeEngine,
    Request,
    cache_refusal,
    sparse_cache_refusal,
)
from fusioninfer_tpu.engine.kv_cache import (
    CacheConfig,
    auto_cache_config,
    init_kv_cache,
    page_bytes,
)
from fusioninfer_tpu.engine.sampler import SamplingParams
from fusioninfer_tpu.models import transformer as tf
from fusioninfer_tpu.models.config import get_preset
from fusioninfer_tpu.ops import sparse_attention as sa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
F32_TOL = 2e-3
SEED = 11
PS, TOPK = 16, 24
CC = CacheConfig(n_pages=24, page_size=PS, max_pages_per_seq=8)


@pytest.fixture(scope="module")
def arch():
    sys.path.insert(0, BENCH)
    import work

    return work.load_arch(os.path.join(BENCH, "arch", "KeyeVL2.py"))


def config_file(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def f32(cfg, impl="reference"):
    return dataclasses.replace(cfg, dtype="float32", attn_impl=impl)


@pytest.fixture(scope="module")
def model(arch):
    """(cfg in float32, seeded params, 90 tokens, the reference's logits
    [90, V] over them)."""
    cfg = f32(get_preset("keye-vl2-tiny"))
    assert cfg.index_topk == TOPK and cfg.is_sparse
    params = tf.init_params(cfg, jax.random.key(SEED))
    tokens = np.random.default_rng(3).integers(3, 500, 90).astype(np.int32)
    conf = dict(config_file("keye-vl2-tiny-cpu"), torch_dtype="float32")
    fw = arch.Forward(conf, SEED, jax.devices()[:1])
    for name in ("wq", "wo", "wiq", "wik", "ww", "w_gate", "w_down",
                 "router"):  # bit for bit
        assert np.array_equal(np.asarray(fw.layers[name]),
                              np.asarray(params["layers"][name])), name
    padded = np.zeros(1024, np.int32)
    padded[:len(tokens)] = tokens
    hidden = np.asarray(fw.hidden(padded, False))[:len(tokens)]
    var = np.mean(hidden * hidden, axis=-1, keepdims=True)
    logits = (hidden / np.sqrt(var + cfg.rms_eps)) @ np.asarray(fw.head)
    return cfg, params, tokens, logits


def test_the_no_cache_forward_is_the_reference(model):
    cfg, params, tokens, want = model
    with jax.default_matmul_precision("highest"):
        got = np.asarray(tf.forward(cfg, params, jnp.asarray(tokens)[None])[0])
    assert np.abs(got - want).max() < F32_TOL
    # the same comparison in the served precision fails it: the
    # tolerance is float32's, not a loose one
    bf = dataclasses.replace(cfg, dtype="bfloat16")
    served = np.asarray(tf.forward(
        bf, jax.tree.map(lambda x: x.astype(jnp.bfloat16)
                         if x.ndim > 1 and x.shape[-1] != 8 else x, params),
        jnp.asarray(tokens)[None])[0])
    assert np.abs(served - want).max() > 10 * F32_TOL


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_prefill_chunks_and_decode_through_the_cache(model, impl):
    """Sequence a: a whole-prompt prefill of 40 tokens (past the top 24),
    then decode steps over page boundaries; sequence b prefills in
    16-token chunks beside a's decode row in ONE fused step — every
    logit the reference's."""
    cfg, params, tokens, want = model
    cfg = f32(cfg, impl)
    cache = init_kv_cache(cfg, CC)
    table_a = np.arange(0, 8, dtype=np.int32)
    table_b = np.arange(8, 16, dtype=np.int32)
    n = 40
    padded = np.zeros((1, 64), np.int32)
    padded[0, :n] = tokens[:n]
    with jax.default_matmul_precision("highest"):
        cache, got = mr.prefill(cfg, CC, params, cache, jnp.asarray(padded),
                                jnp.asarray([n], jnp.int32),
                                jnp.asarray(table_a[None]))
        assert np.abs(np.asarray(got[0]) - want[n - 1]).max() < F32_TOL
        tables = np.stack([table_a, np.full(8, CC.trash_page, np.int32)])
        for t in range(n, n + 10):  # decode, teacher-forced, past page 2
            cache, got = mr.decode_step(
                cfg, CC, params, cache,
                jnp.asarray([tokens[t], 0], jnp.int32),
                jnp.asarray([t, 0], jnp.int32), jnp.asarray(tables),
                jnp.asarray([True, False]), coalesce=True, kv_splits=0)
            assert np.abs(np.asarray(got[0]) - want[t]).max() < F32_TOL
        t = n + 10
        for start in (0, 16, 32, 48):
            rows = np.full((4, 8), CC.trash_page, np.int32)
            rows[0], rows[1] = table_a, table_b
            flat = np.zeros(32, np.int32)
            flat[0], flat[1:17] = tokens[t], tokens[start:start + 16]
            cache, dec, chunk = mr.fused_step(
                cfg, CC, params, cache, jnp.asarray(flat),
                jnp.asarray([t, start, 0, 0], jnp.int32),
                jnp.asarray([0, 1, 17, 17], jnp.int32),
                jnp.asarray([1, 16, 0, 0], jnp.int32), jnp.asarray(rows),
                jnp.asarray([[0]], jnp.int32), jnp.asarray([16], jnp.int32),
                coalesce=True, kv_splits=0)
            assert np.abs(np.asarray(dec[0, 0]) - want[t]).max() < F32_TOL
            assert np.abs(np.asarray(chunk[0])
                          - want[start + 15]).max() < F32_TOL
            t += 1
    # every forward counted what its indexer read and its attention chose
    lo_hi = np.asarray(cache["dsa_stats"]).astype(np.int64)
    scored, chosen = (lo_hi[:, 1] << 32) | lo_hi[:, 0]
    ctx = ([*range(1, n + 1)] + [*range(n + 1, n + 11)]
           + [c for s in (0, 16, 32, 48) for c in range(s + 1, s + 17)]
           + [*range(n + 11, n + 15)])
    L = cfg.n_layers
    assert scored == L * sum(ctx)
    assert chosen == L * sum(min(c, TOPK) for c in ctx)


def test_the_served_selection_is_the_references(model, arch):
    """Layer 0's indexer over the embedded tokens: the program's scores
    and exact top 24 choose the positions the reference's ``lax.top_k``
    chooses, query by query."""
    cfg, params, tokens, _ = model
    layer = jax.tree.map(lambda w: w[0], params["layers"])
    x = params["embed"][jnp.asarray(tokens)][None].astype(jnp.float32)
    S = len(tokens)
    pos = jnp.arange(S)
    with jax.default_matmul_precision("highest"):
        q_i, w, k_i = tf.indexer_proj(cfg, layer, x, pos[None])
        causal = pos[None, :] <= pos[:, None]
        scores = sa.index_scores(q_i[0], w[0], jnp.broadcast_to(
            k_i[0], (S, S, cfg.index_head_dim)), causal)
        served = sa.selection_mask(scores, *sa.sparse_threshold(
            scores, TOPK)) & causal
        z = arch.sizes(dict(config_file("keye-vl2-tiny-cpu"),
                            torch_dtype="float32"))
        h = x[0] * jax.lax.rsqrt(jnp.mean(x[0] ** 2, -1, keepdims=True)
                                 + z["eps"])
        rq = arch.index_rope((h @ layer["wiq"]).reshape(S, 4, 16), pos,
                             z["theta"])
        rk = arch.index_rope(arch.layer_norm(h @ layer["wik"], z["eps"])[
            :, None], pos, z["theta"])[:, 0]
        rw = (h @ layer["ww"]) / 2.0
        ref = jnp.einsum("thd,sd->ths", rq, rk) / 4.0
        ref = jnp.einsum("ths,th->ts", jnp.maximum(ref, 0.0), rw)
        ref = jnp.where(causal, ref, -jnp.inf)
        vals, idx = jax.lax.top_k(ref, TOPK)
        chosen = jnp.zeros((S, S), bool).at[
            jnp.arange(S)[:, None], idx].set(True) & causal
    assert np.abs(np.asarray(jnp.where(causal, scores - ref, 0))).max() < 1e-5
    assert np.array_equal(np.asarray(served), np.asarray(chosen))
    assert int(served[-1].sum()) == TOPK and int(served[10].sum()) == 11


def test_a_different_choice_is_seen(model):
    """The top 24 against the top 12 of the same scores: logits move far
    past the float32 tolerance, so the comparison above tells a choice
    apart."""
    cfg, params, tokens, want = model
    fewer = dataclasses.replace(cfg, index_topk=12)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(tf.forward(fewer, params, jnp.asarray(tokens)[None])[0])
    assert np.abs(got - want).max() > 50 * F32_TOL


@pytest.mark.parametrize("impl", ["reference", "flash"])
def test_within_topk_the_sparse_path_is_dense_attention(model, impl):
    """Contexts no longer than ``index_topk``: every earlier position is
    chosen, and the served logits are dense GQA attention's (the same
    weights through the model without an indexer)."""
    cfg, params, tokens, _ = model
    wide = f32(dataclasses.replace(cfg, index_topk=128), impl)
    dense = f32(dataclasses.replace(cfg, index_topk=0, index_n_heads=0,
                                    index_head_dim=0), impl)
    tok = jnp.asarray(tokens[:64])[None]
    with jax.default_matmul_precision("highest"):
        a = np.asarray(tf.forward(wide, params, tok)[0])
        b = np.asarray(tf.forward(dense, params, tok)[0])
        assert np.abs(a - b).max() < 1e-5
        cache = init_kv_cache(wide, CC)
        cache, got = mr.prefill(wide, CC, params, cache, tok,
                                jnp.asarray([64], jnp.int32),
                                jnp.asarray(np.arange(8, dtype=np.int32)[None]))
    assert np.abs(np.asarray(got[0]) - b[63]).max() < F32_TOL


# -- the three operations, kernels against their exact forms ----------------


def _paged_setup(seed=0, Di=16, HI=4, KV=2, G=2, Hd=32):
    ks = jax.random.split(jax.random.key(seed), 6)
    L, n_pages, mp = 2, 12, 4
    k_idx = jnp.pad(jax.random.normal(ks[0], (L, n_pages, PS, Di)),
                    ((0, 0), (0, 0), (0, 0), (0, 128 - Di)))
    kp = jax.random.normal(ks[1], (L, KV, n_pages, PS, Hd)).astype(jnp.bfloat16)
    vp = jax.random.normal(ks[2], (L, KV, n_pages, PS, Hd)).astype(jnp.bfloat16)
    tables = jnp.asarray([[3, 5, 7, 1], [2, 4, 6, 8], [0, 9, 10, 11],
                          [11, 11, 11, 11]], jnp.int32)
    # a decode row, a 19-token chunk across pages, a 2-token window, an
    # inert row
    items = sa.sparse_items(jnp.asarray([0, 1, 20, 22], jnp.int32),
                            jnp.asarray([1, 19, 2, 0], jnp.int32),
                            jnp.asarray([40, 3, 50, 0], jnp.int32), 32, 8)
    q_i = jax.random.normal(ks[3], (32, HI, Di)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[4], (32, HI))
    q = jax.random.normal(ks[5], (32, KV * G, Hd)).astype(jnp.bfloat16)
    return k_idx, kp, vp, tables, items, q_i, w, q


def test_the_items_of_a_ragged_layout():
    *_, items, _, _, _ = _paged_setup()
    assert items.row.tolist() == [0, 1, 1, 1, 2, 3, 3, 3]
    assert items.pos0.tolist() == [40, 3, 11, 19, 50, 0, 8, 16]
    assert items.n.tolist() == [1, 8, 8, 3, 2, 0, 0, 0]
    assert items.item_of[:4].tolist() == [0, 1, 1, 1]
    assert items.slot_of[17:22].tolist() == [0, 1, 2, 0, 1]
    assert not bool(items.tok_live[22:].any())


def test_the_indexer_kernel_is_its_exact_form():
    k_idx, _, _, tables, items, q_i, w, _ = _paged_setup()
    qi = jnp.moveaxis(sa.to_items(q_i, items), 2, 1)
    wi = sa.to_items(w, items)
    want = sa.reference_indexer_paged_scores(qi, wi, k_idx, tables, items,
                                             layer=1)
    got = sa.indexer_paged_scores(qi, wi, k_idx, tables, items, layer=1,
                                  interpret=True)
    finite = np.isfinite(np.asarray(want))
    assert np.array_equal(finite, np.isfinite(np.asarray(got)))
    assert np.abs(np.asarray(want - got)[finite]).max() < 1e-5
    # a query sees its row's positions up to its own, nothing past
    assert finite[0, 0].sum() == 41 and not finite[0, 1:].any()
    assert finite[5:].sum() == 0


def test_the_sparse_attention_kernel_is_its_exact_form():
    k_idx, kp, vp, tables, items, q_i, w, q = _paged_setup()
    N, bq = items.tok.shape
    qi = jnp.moveaxis(sa.to_items(q_i, items), 2, 1)
    scores = sa.reference_indexer_paged_scores(
        qi, sa.to_items(w, items), k_idx, tables, items, layer=1)
    thr_s, thr_c = sa.sparse_threshold(scores.reshape(N * bq, -1), 10)
    qa = sa.to_items(q, items).reshape(N, bq, 2, 2, 32).transpose(
        0, 2, 3, 1, 4).reshape(N, 2, 2 * bq, 32)
    args = (qa, kp, vp, scores, thr_s.reshape(N, bq), thr_c.reshape(N, bq),
            tables, items)
    want = sa.reference_sparse_paged_attention(*args, layer=1)
    got = sa.sparse_paged_attention(*args, layer=1, interpret=True)
    assert np.abs(np.asarray(want, np.float32)
                  - np.asarray(got, np.float32)).max() < 2e-2
    assert not np.asarray(got[5:]).any()  # inert items give zeros


@pytest.mark.parametrize("method", ["top_k", "kernel"])
def test_the_selection_is_exact_with_ties_to_the_lower_position(method):
    inf = jnp.inf
    scores = jnp.asarray([
        [1.0, 2.0, 2.0, 2.0, 2.0, 0.0, 3.0, -inf],   # a tie straddles
        [0.5, -0.0, 0.0, 0.0, -1.0, 0.25, -inf, -inf],  # zeros of both signs
        [1.0, 2.0, -inf, -inf, -inf, -inf, -inf, -inf],  # fewer than k
    ], jnp.float32)
    scores = jnp.where(scores == 0, 0.0, scores)  # as every scorer leaves them
    if method == "top_k":
        thr = sa.sparse_threshold(scores, 3)
    else:  # one item of the three queries at positions 5-7, one inert
        items = sa.sparse_items(jnp.asarray([0], jnp.int32),
                                jnp.asarray([3], jnp.int32),
                                jnp.asarray([5], jnp.int32), 3, 3)
        both = jnp.stack([scores, jnp.full_like(scores, -inf)])
        thr_s, thr_c = sa.sparse_select(both, items, 3, interpret=True)
        thr = thr_s[0, :, 0], thr_c[0, :, 0]
    # -inf positions chosen to make up k are removed by the causal mask
    got = sa.selection_mask(scores, *thr) & jnp.isfinite(scores)
    want = [[0, 1, 1, 0, 0, 0, 1, 0], [1, 1, 0, 0, 0, 1, 0, 0],
            [1, 1, 0, 0, 0, 0, 0, 0]]
    assert np.asarray(got).astype(int).tolist() == want


@pytest.mark.parametrize("C,k,step", [
    (64, 10, None),      # the context within one block
    (256, 10, 0.5),      # equal scores: ties straddle the k-th place
    (384, 5, 0.25),      # three blocks of 128
    (4096, 30, 1.0),     # two blocks of 2048, many ties
])
def test_the_selection_kernel_is_the_exact_top_k(C, k, step):
    """The kernel's thresholds choose what ``lax.top_k`` (lower position
    first) chooses, for every live query of a decode row, a chunk whose
    early queries see fewer than ``k`` positions, a short window and an
    inert row; positions past an item's last query are never read (NaN
    there changes nothing)."""
    items = sa.sparse_items(jnp.asarray([0, 1, 20, 22], jnp.int32),
                            jnp.asarray([1, 19, 2, 0], jnp.int32),
                            jnp.asarray([C - 20, 3, C // 2, 0], jnp.int32),
                            32, 8)
    N, bq = items.tok.shape
    s = jax.random.normal(jax.random.key(C + k), (N, bq, C))
    if step:
        s = jnp.round(s / step) * step
    valid = sa._item_valid(items, C)
    s = jnp.where(valid, jnp.where(s == 0, 0.0, s), -jnp.inf)
    last_block = (items.pos0 + items.n - 1) // math.gcd(C, sa.SELECT_BLOCK)
    past = (jnp.arange(C)[None, None, :] // math.gcd(C, sa.SELECT_BLOCK)
            > last_block[:, None, None]) | (items.n == 0)[:, None, None]
    thr_s, thr_c = sa.sparse_select(jnp.where(past, jnp.nan, s), items, k,
                                    interpret=True)
    flat, valid = s.reshape(N * bq, C), valid.reshape(N * bq, C)
    want = sa.selection_mask(flat, *sa.sparse_threshold(flat, k))
    got = sa.selection_mask(flat, thr_s.reshape(-1), thr_c.reshape(-1))
    assert np.array_equal(np.asarray(want & valid), np.asarray(got & valid))
    chosen = np.asarray(got & valid).sum(-1)
    assert (chosen == np.minimum(np.asarray(valid).sum(-1), k)).all()


def test_the_references_selection_is_lax_top_k(arch):
    """The judge's bisection chooses what ``lax.top_k`` (lower position
    first) chooses: distinct scores, many equal ones, both signs, rows
    with fewer finite scores than ``k``."""
    rnd = jax.random.normal(jax.random.key(5), (48, 700))
    for score in (rnd, jnp.round(rnd * 4) / 4,
                  jnp.where(jnp.arange(700) < 30, rnd, -jnp.inf),
                  jnp.where(rnd > 1.5, 1.5, -rnd)):
        score = jnp.where(score == 0, 0.0, score)
        vals, idx = jax.lax.top_k(score, 40)
        c = jnp.arange(700)[None, :]
        want = (score > vals[:, -1:]) | ((score == vals[:, -1:])
                                         & (c <= idx[:, -1:]))
        got = arch.top_positions(score, 40)
        assert np.array_equal(np.asarray(want), np.asarray(got))
        assert (np.asarray(got).sum(-1) == 40).all()


# -- the engine ------------------------------------------------------------


def _engine(cfg, **kw):
    return NativeEngine(cfg, CC, max_batch_size=2, seed=SEED,
                        token_budget=32, **kw)


def _serve(eng, prompts, n_out=12):
    out = {}
    for rid, p in prompts.items():
        eng.add_request(Request(rid, p, SamplingParams(
            max_tokens=n_out, temperature=0.0)))
    while eng.has_work():
        for o in eng.step():
            out.setdefault(o.request_id, []).append(o.token)
    return out


def test_the_engine_serves_it_through_the_normal_path(model):
    """Greedy streams through admission, budgeted chunks, the mixed step
    and decode bursts equal the no-cache forward's argmax in float32; a
    prompt served twice reuses its whole pages (indexer keys included)
    and streams the same tokens; the counters reach ``/metrics``."""
    from fusioninfer_tpu.engine.metrics import EngineMetrics

    cfg, params, tokens, _ = model
    eng = NativeEngine(cfg, CC, max_batch_size=2, params=params,
                       token_budget=32, decode_burst_steps=4)
    info = eng.runtime_info()
    assert info["sparse_attention"] == {
        "topk": TOPK, "index_heads": 4, "index_head_dim": 16,
        "selection": "exact:top_k", "kernels": "reference"}
    assert info["moe_experts"] == "ragged_dot dropless 8/8"
    prompts = {"a": tokens[:70].tolist(), "b": tokens[5:30].tolist()}
    out = _serve(eng, prompts)
    for rid, p in prompts.items():
        full = np.asarray(tf.forward(cfg, params, jnp.asarray(
            p + out[rid])[None])[0])
        assert (full.argmax(-1)[len(p) - 1:-1] == out[rid]).all(), rid
    again = _serve(eng, {"c": prompts["a"]})
    assert again["c"] == out["a"] and eng.prefix_cache_hit_rate() > 0
    eng._drain_dsa_stats()
    page = EngineMetrics("m").render(eng)
    scored = eng.dsa_stats_total["scored"]
    assert 0 < eng.dsa_stats_total["selected"] < scored
    assert f"fusioninfer:dsa_positions_scored_total{{model_name=\"m\"}} {scored}" in page


@pytest.mark.parametrize("asked", sorted(_NOT_YET))
def test_every_refusal_names_its_flag(asked):
    cfg = get_preset("keye-vl2-tiny")
    refusal = sparse_cache_refusal(cfg, **{asked: True})
    assert refusal and _NOT_YET[asked] in refusal and "indexer" in refusal
    assert sparse_cache_refusal(cfg, **{asked: False}) is None
    assert cache_refusal(cfg, **{asked: True}) == refusal
    for other in ("qwen3-tiny", "smallthinker-tiny", "deepseek-v2-tiny"):
        assert sparse_cache_refusal(get_preset(other), **{asked: True}) is None
    if asked in ("int8_kv", "speculative", "host_tier", "int8_weights"):
        kw = {"int8_kv": {"cache_cfg": dataclasses.replace(
                  CC, kv_dtype="int8")},
              "speculative": {"speculative_k": 2},
              "host_tier": {"host_kv_tier": object()},
              "int8_weights": {}}[asked]
        c = (dataclasses.replace(cfg, quantization="int8")
             if asked == "int8_weights" else cfg)
        with pytest.raises(ValueError, match="indexer-key cache"):
            NativeEngine(c, kw.pop("cache_cfg", CC), max_batch_size=2, **kw)
    if asked in ("kv_transfer", "kv_fabric", "evacuate"):
        eng = _engine(cfg)
        with pytest.raises(ValueError, match=_NOT_YET[asked].split(" (")[0]):
            eng._refuse_if_latent(**{asked: True})


def test_the_cut_and_its_pages():
    cfg = get_preset("keye-vl2-30b-a3b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.n_experts, cfg.n_experts_active,
            cfg.moe_d_ff) == (4, 2048, 32, 4, 128, 128, 8, 768)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk,
            cfg.index_row_width) == (16, 64, 2048, 128)
    # K/V 2048 B a position and layer, the indexer key stored in a whole
    # 128-lane tile beside it
    assert page_bytes(cfg, 128) == 4 * 128 * (2048 + 256)
    cc = auto_cache_config(cfg, page_size=128, max_model_len=65536,
                           max_batch_size=8, hbm_bytes=int(16.9e9),
                           prefix_caching=False)
    assert cc.max_pages_per_seq == 512 and cc.n_pages == 8 * 512 + 1
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, cc))
    assert cache["k_idx"].shape == (4, cc.n_pages, 128, 128)
    assert cache["k"].shape == (4, 4, cc.n_pages, 128, 128)
