"""SmallThinker through the normal serving path, against its plain
reference (``perfbench/arch/smallthinker.py``, loaded by path as
``perfbench/work.py`` does): a per-layer pattern of full attention
without positional encoding and rotary layers over a sliding window,
scanned by period over a KV cache kept by layer kind; ReLU-gated experts
chosen from the layer's raw input.

Tiny preset (two periods, a window of 24, 8-token pages: a 3-page
context passes the window), CPU, seeded weights, logits and not tokens.
``F32_TOL`` = 2e-3 on logits of magnitude ~1 with the model in float32
(``tests/test_deepseek_v2.py`` states why): program and reference then
differ by the order of float32 sums only (read 2e-5 here); in bfloat16
a router near-tie flips an expert and the same comparison reads 0.1-5.

What nothing else may feel is pinned at the end: the seeded weights and
the lowered text of the served programs of every architecture that was
here before, against values read on the parent commit."""

import dataclasses
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fusioninfer_tpu.engine import model_runner as mr
from fusioninfer_tpu.engine.engine import (
    NativeEngine,
    Request,
    kind_cache_refusal,
    latent_cache_refusal,
)
from fusioninfer_tpu.engine.kv_cache import (
    CacheConfig,
    PageAllocator,
    auto_cache_config,
    init_kv_cache,
    kv_cache_bytes,
    page_bytes,
)
from fusioninfer_tpu.engine.sampler import SamplingParams
from fusioninfer_tpu.models import transformer as tf
from fusioninfer_tpu.models.config import get_preset

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
F32_TOL = 2e-3
SEED = 11
PS, WINDOW = 8, 24


@pytest.fixture(scope="module")
def arch():
    sys.path.insert(0, BENCH)
    import work

    return work.load_arch(os.path.join(BENCH, "arch", "smallthinker.py"))


def config_file(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def f32(cfg, impl="reference"):
    return dataclasses.replace(cfg, dtype="float32", attn_impl=impl)


@pytest.fixture(scope="module")
def model():
    """(cfg in float32, seeded params, 90 tokens, the full forward's
    logits [90, V] over them)."""
    cfg = f32(get_preset("smallthinker-tiny"))
    assert cfg.sliding_window == WINDOW and cfg.period == 4
    params = tf.init_params(cfg, jax.random.key(SEED))
    tokens = np.random.default_rng(3).integers(3, 500, 90).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(tf.forward(cfg, params, jnp.asarray(tokens)[None])[0])
    return cfg, params, tokens, logits


def test_the_served_forward_is_the_reference(arch, model):
    cfg, params, tokens, logits = model
    conf = dict(config_file("smallthinker-tiny-cpu"), torch_dtype="float32")
    fw = arch.Forward(conf, SEED, jax.devices()[:1])
    for name in ("wq", "wo", "w_gate", "w_down", "router"):  # bit for bit
        assert np.array_equal(np.asarray(fw.layers[name]),
                              np.asarray(params["layers"][name])), name
    padded = np.zeros(1024, np.int32)
    padded[:len(tokens)] = tokens
    hidden = np.asarray(fw.hidden(padded, False))[:len(tokens)]
    var = np.mean(hidden * hidden, axis=-1, keepdims=True)
    want = (hidden / np.sqrt(var + cfg.rms_eps)) @ np.asarray(fw.head)
    assert np.abs(logits - want).max() < F32_TOL
    # the same comparison in the served precision fails it: the tolerance
    # is float32's, not a loose one
    bf = dataclasses.replace(cfg, dtype="bfloat16")
    served = np.asarray(tf.forward(
        bf, jax.tree.map(lambda x: x.astype(jnp.bfloat16)
                         if x.ndim > 1 and x.shape[-1] != 8 else x, params),
        jnp.asarray(tokens)[None])[0])
    assert np.abs(served - want).max() > 10 * F32_TOL


def _cache_setup(cfg):
    cc = auto_cache_config(cfg, page_size=PS, max_model_len=96,
                           max_batch_size=2, step_span=16)
    return cc, PageAllocator(cc, window=WINDOW), init_kv_cache(cfg, cc)


@pytest.mark.parametrize("impl", ["reference", "flash"])
@pytest.mark.parametrize("n", [10, 24, 50], ids=["below", "at", "past"])
def test_prefill_then_decode_and_a_mixed_step_through_both_pools(model, impl, n):
    """A prompt of ``n`` tokens below, at and past the window: the
    whole-prompt prefill keeps only the window-kind pages the next query
    can see (the rest are trash placeholders), three decode steps extend
    both lists and trim the window kind's, a second sequence prefills in
    16-token chunks beside the first's decode row in ONE fused step —
    every logit equal to the no-cache forward's."""
    cfg, params, tokens, logits = model
    cfg = f32(cfg, impl)
    cc, alloc, cache = _cache_setup(cfg)
    ks = (0, 0)
    alloc.allocate("a", n + 1)
    alloc.cover_window("a", n, n)
    row = alloc.page_table_row("a")
    assert row.shape == (2, cc.max_pages_per_seq)
    first_live = max(0, n - WINDOW + 1) // PS
    assert (row[1, :first_live] == cc.window_trash_page).all()
    assert (row[0, :-(-n // PS)] != cc.trash_page).all()
    padded = np.zeros((1, 64), np.int32)
    padded[0, :n] = tokens[:n]
    with jax.default_matmul_precision("highest"):
        cache, got = mr.prefill(cfg, cc, params, cache, jnp.asarray(padded),
                                jnp.asarray([n], jnp.int32),
                                jnp.asarray(row[None]))
        assert np.abs(np.asarray(got[0]) - logits[n - 1]).max() < F32_TOL
        tables = alloc.blank_page_tables(2)
        for t in range(n, n + 3):  # decode steps, teacher-forced
            alloc.extend("a", t, 1)
            alloc.cover_window("a", t, t + 1)
            tables[0] = alloc.page_table_row("a")
            cache, got = mr.decode_step(
                cfg, cc, params, cache,
                jnp.asarray([tokens[t], 0], jnp.int32),
                jnp.asarray([t, 0], jnp.int32), jnp.asarray(tables),
                jnp.asarray([True, False]), coalesce=True, kv_splits=ks)
            assert np.abs(np.asarray(got[0]) - logits[t]).max() < F32_TOL
        # sequence b prefills in chunks while a decodes: mixed rows
        alloc.allocate("b", 49)
        t = n + 3
        for start in (0, 16, 32):
            alloc.extend("a", t, 1)
            alloc.cover_window("a", t, t + 1)
            alloc.cover_window("b", start, start + 16)
            rows = alloc.blank_page_tables(4)
            rows[0], rows[1] = (alloc.page_table_row("a"),
                                alloc.page_table_row("b"))
            flat = np.zeros(32, np.int32)
            flat[0], flat[1:17] = tokens[t], tokens[start:start + 16]
            cache, dec, chunk = mr.fused_step(
                cfg, cc, params, cache, jnp.asarray(flat),
                jnp.asarray([t, start, 0, 0], jnp.int32),
                jnp.asarray([0, 1, 17, 17], jnp.int32),
                jnp.asarray([1, 16, 0, 0], jnp.int32), jnp.asarray(rows),
                jnp.asarray([[0]], jnp.int32), jnp.asarray([16], jnp.int32),
                coalesce=True, kv_splits=ks)
            assert np.abs(np.asarray(dec[0, 0]) - logits[t]).max() < F32_TOL
            assert np.abs(np.asarray(chunk[0])
                          - logits[start + 15]).max() < F32_TOL
            t += 1
    # b ran past the window: its first window-kind pages are gone, its
    # full-kind pages all stay
    b = alloc.page_table_row("b")
    assert b[1, 0] == cc.window_trash_page and (b[0, :6] != cc.trash_page).all()
    assert alloc.window_pages_trimmed_total > 0


def test_nope_layers_ignore_positions_and_rotary_ones_do_not(model):
    cfg, params, _, _ = model
    layer = jax.tree.map(lambda w: w[0], params["layers"])
    x = jax.random.normal(jax.random.key(0), (1, 6, cfg.d_model))
    at = jnp.arange(6)[None]
    full, window = cfg.layer_kinds[0], cfg.layer_kinds[1]
    assert (full.rope, full.window, full.pool) == (False, None, "")
    assert (window.rope, window.window, window.pool) == (True, WINDOW, "_win")
    q0, k0, _ = tf.qkv_proj(cfg, layer, x, at, rope=full.rope)
    q1, k1, _ = tf.qkv_proj(cfg, layer, x, at + 7, rope=full.rope)
    assert jnp.array_equal(q0, q1) and jnp.array_equal(k0, k1)
    q0, k0, _ = tf.qkv_proj(cfg, layer, x, at, rope=window.rope)
    q1, k1, _ = tf.qkv_proj(cfg, layer, x, at + 7, rope=window.rope)
    assert not jnp.allclose(q0, q1, atol=1e-3)
    assert not jnp.allclose(k0, k1, atol=1e-3)


def test_the_router_reads_the_layers_raw_input(model, monkeypatch):
    """The experts are chosen from ``x`` before attention: not from the
    normed input of the expert layer (a model with ``router_input``
    "mlp_norm" is handed that one)."""
    cfg, params, _, _ = model
    layer = jax.tree.map(lambda w: w[1], params["layers"])
    x = jax.random.normal(jax.random.key(1), (1, 5, cfg.d_model))
    seen = []
    route = tf.moe_route

    def spy(cfg_, h, *rest):
        seen.append(np.asarray(h))
        return route(cfg_, h, *rest)

    monkeypatch.setattr(tf, "moe_route", spy)
    at = jnp.arange(5)[None]
    tf.layer_forward(cfg, layer, x, at, kind=cfg.layer_kinds[1])
    assert len(seen) == 1 and np.array_equal(seen[0], np.asarray(x[0]))
    seen.clear()
    normed = dataclasses.replace(cfg, router_input="mlp_norm")
    tf.layer_forward(normed, layer, x, at, kind=cfg.layer_kinds[1])
    assert len(seen) == 1 and not np.allclose(seen[0], np.asarray(x[0]))


def test_the_experts_gate_is_relu(model):
    cfg, params, _, _ = model
    layer = jax.tree.map(lambda w: w[0], params["layers"])
    h = jax.random.normal(jax.random.key(2), (7, cfg.d_model))
    got, stats = tf.moe_layer(cfg, layer, h)
    ids, w = tf.moe_route(cfg, h, layer["router"])
    want = np.zeros((7, cfg.d_model), np.float32)
    for t in range(7):
        for e, w_e in zip(np.asarray(ids[t]), np.asarray(w[t])):
            gate = np.maximum(np.asarray(h[t] @ layer["w_gate"][e]), 0.0)
            want[t] += w_e * np.asarray(
                (gate * (h[t] @ layer["w_up"][e])) @ layer["w_down"][e])
    assert np.abs(np.asarray(got) - want).max() < 1e-4
    silu, _ = tf.moe_layer(dataclasses.replace(cfg, expert_act="silu"),
                           layer, h)
    assert np.abs(np.asarray(silu) - want).max() > 1e-2
    assert int(stats[0]) == int(stats[1]) == 7 * cfg.n_experts_active


def test_pools_and_page_bytes_by_kind():
    tiny = get_preset("smallthinker-tiny")
    cc, _, cache = _cache_setup(tiny)
    assert cache["k"].shape == (2, 2, cc.n_pages, PS, 32)
    assert cache["k_win"].shape == (6, 2, cc.n_window_pages, PS, 32)
    assert cc.max_window_pages_per_seq == -(-(WINDOW + 16) // PS) + 1
    # the cut's sizes: 2048 B a position and layer, two pools
    cfg = get_preset("smallthinker-21b-a3b")
    assert page_bytes(cfg, 128) == 2 * 128 * 2048
    assert page_bytes(cfg, 128, pool="_win") == 6 * 128 * 2048
    big = auto_cache_config(cfg, page_size=128, max_model_len=16384,
                            max_batch_size=32, hbm_bytes=int(16.9e9),
                            step_span=256)
    assert big.max_pages_per_seq == 128 and big.max_window_pages_per_seq == 35
    assert big.n_pages >= 32 * 128 + 1 and big.n_window_pages >= 32 * 35 + 1
    # both pools grown by one factor; the whole within the budget
    assert abs(big.n_window_pages / big.n_pages - (32 * 35 + 1) / 4097) < 1e-3
    assert kv_cache_bytes(cfg, big) <= 0.85 * 16.9e9 - 7.93e9
    # ONE pool of 8 layers at these flags does not start
    one_pool = dataclasses.replace(cfg, layer_pattern=None,
                                   sliding_window=None, name="one-pool")
    with pytest.raises(ValueError, match="needs 4097 KV pages"):
        auto_cache_config(one_pool, page_size=128, max_model_len=16384,
                          max_batch_size=32, hbm_bytes=int(16.9e9))
    # a model of one kind keeps today's pool, names and size
    for name in ("qwen3-tiny", "mistral-tiny"):
        one = auto_cache_config(get_preset(name), page_size=16,
                                max_model_len=256, max_batch_size=4)
        assert not one.by_kind and one.n_pages == 4 * 16 + 1
        assert set(init_kv_cache(get_preset(name), one)) == {"k", "v"}
    with pytest.raises(ValueError, match="a pool a layer kind"):
        init_kv_cache(tiny, CacheConfig(n_pages=9, page_size=PS,
                                        max_pages_per_seq=4))


@pytest.mark.parametrize("short", ["full", "window"])
def test_capacity_is_refused_when_either_pool_is_short(short):
    """Two sequences' worth of one pool, one sequence's worth of the
    other: the second sequence is not admitted, whichever is short."""
    per_seq, window_per_seq = 6, 5
    cc = CacheConfig(
        n_pages=(2 if short == "window" else 1) * per_seq + 1, page_size=PS,
        max_pages_per_seq=per_seq,
        n_window_pages=(2 if short == "full" else 1) * window_per_seq + 1,
        max_window_pages_per_seq=window_per_seq).validate()
    alloc = PageAllocator(cc, window=WINDOW)
    assert alloc.can_allocate(40)
    alloc.allocate("a", 40)
    alloc.cover_window("a", 40, 40)
    assert not alloc.can_allocate(40)
    with pytest.raises(MemoryError):
        alloc.allocate("b", 40)
    alloc.release("a")
    assert alloc.can_allocate(40) and alloc.pages_in_use() == {
        "full": 0, "window": 0}


def test_a_sequences_window_pages_go_while_its_full_pages_stay():
    cc, alloc, _ = _cache_setup(get_preset("smallthinker-tiny"))
    alloc.allocate("s", 1)
    for t in range(80):  # decode, a token a step
        alloc.extend("s", t, 1)
        alloc.cover_window("s", t, t + 1)
        held = alloc.pages_in_use()
        assert held["full"] == t // PS + 1
        assert held["window"] <= -(-WINDOW // PS) + 1
    row = alloc.page_table_row("s")
    assert (row[0, :10] != cc.trash_page).all()
    assert (row[1, :7] == cc.window_trash_page).all()
    assert (row[1, 7:10] != cc.window_trash_page).all()
    assert alloc.window_pages_trimmed_total == 7
    assert alloc.pages_allocated_total == {"full": 10, "window": 10}
    assert alloc.utilization() == max(10 / (cc.n_pages - 1),
                                      3 / (cc.n_window_pages - 1))
    # a row past what the pool was sized for is refused by name
    with pytest.raises(MemoryError, match="step_span"):
        alloc.cover_window("s", 80, 96 + 64)


REFUSED = ("mesh", "int8_weights", "int8_kv", "lora", "speculative",
           "host_tier", "kv_transfer", "kv_fabric", "evacuate", "checkpoint")


@pytest.mark.parametrize("asked", REFUSED)
def test_what_a_cache_by_kind_does_not_carry_is_refused_by_name(asked):
    cfg = get_preset("smallthinker-tiny")
    refusal = kind_cache_refusal(cfg, **{asked: True})
    assert refusal and "by layer kind" in refusal and "--" in refusal
    assert kind_cache_refusal(cfg, **{asked: False}) is None
    assert latent_cache_refusal(cfg, **{asked: True}) is None
    for other in ("qwen3-tiny", "mistral-tiny", "deepseek-v2-tiny"):
        assert kind_cache_refusal(get_preset(other), **{asked: True}) is None


def test_the_engine_refuses_at_start_up_and_says_what_it_keeps():
    cfg = f32(get_preset("smallthinker-tiny"))
    cc = auto_cache_config(cfg, page_size=PS, max_model_len=96,
                           max_batch_size=4, step_span=16)
    with pytest.raises(ValueError, match="speculative decoding"):
        NativeEngine(cfg, cc, max_batch_size=4, speculative_k=2)
    with pytest.raises(ValueError, match="int8 KV pages|int8 pages"):
        NativeEngine(cfg, dataclasses.replace(cc, kv_dtype="int8"),
                     max_batch_size=4)
    with pytest.raises(ValueError, match="--tokens-per-step"):
        NativeEngine(cfg, cc, max_batch_size=4, token_budget=64)
    eng = NativeEngine(cfg, cc, max_batch_size=4, token_budget=16)
    info = eng.runtime_info()
    assert info["layer_pattern"] == ["full+nope"] + ["window:24+rope"] * 3
    assert info["pages_by_kind"]["window"] == {
        "layers": 6, "n_pages": cc.n_window_pages,
        "max_pages_per_seq": cc.max_window_pages_per_seq}
    assert info["pages_by_kind"]["full"]["layers"] == 2
    assert "registers nothing" in info["prefix_cache"]
    with pytest.raises(ValueError, match="--tokens-per-step"):
        eng.set_token_budget(64)
    with pytest.raises(ValueError, match="evacuation"):
        eng._refuse_if_latent(evacuate=True)
    one = NativeEngine(get_preset("qwen3-tiny"), max_batch_size=2)
    assert one.runtime_info()["layer_pattern"] == ["full+rope"]
    assert one.runtime_info()["pages_by_kind"] is None


@pytest.mark.parametrize("burst,budget", [(8, 16), (1, None)],
                         ids=["burst8-chunked", "classic-monolithic"])
def test_served_streams_are_the_full_forwards(model, burst, budget):
    """Four requests below, at and past the window through the whole
    engine (admission, chunked prefill, mixed steps, decode bursts with
    dispatch-ahead, trimming): every greedy token is the no-cache
    forward's, and the pools drain."""
    cfg, params, tokens, _ = model
    cc = auto_cache_config(cfg, page_size=PS, max_model_len=96,
                           max_batch_size=4, step_span=budget or 96)
    eng = NativeEngine(cfg, cc, max_batch_size=4, params=params,
                       token_budget=budget, decode_burst_steps=burst)
    prompts = {f"r{n}": [int(t) for t in tokens[i:i + n]]
               for i, n in enumerate((5, 24, 40, 61))}
    for rid, prompt in prompts.items():
        eng.add_request(Request(rid, prompt, SamplingParams(
            max_tokens=28, temperature=0.0)))
    out = {rid: [] for rid in prompts}
    for _ in range(600):
        for o in eng.step():
            assert not (o.finish_reason or "").startswith("error"), o
            out[o.request_id].append(o.token)
        if not eng.has_work():
            break
    wrong = 0
    for rid, prompt in prompts.items():
        seq = jnp.asarray(prompt + out[rid])[None]
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jnp.argmax(tf.forward(cfg, params, seq)[0], -1))
        assert len(out[rid]) == 28
        wrong += int((want[len(prompt) - 1:-1] != np.asarray(out[rid])).sum())
    assert wrong <= 1  # float32 sums in two orders: a near-tie at most
    assert eng.alloc.pages_in_use() == {"full": 0, "window": 0}
    assert eng.alloc.window_pages_trimmed_total > 0
    assert eng.alloc.pages_allocated_total["window"] > 0
    assert not eng.prefix_caching and eng.kv_cache_usage() == 0.0


# -- nothing else moves ----------------------------------------------------

# sha256[:16] over every leaf (path, dtype, bytes) of init_params(preset,
# key(11)), read on the parent commit (e4dbd32): the recipes of the
# served presets' architectures (qwen3-1.7b is qwen3-tiny's init,
# deepseek-v2-ep4 deepseek-v2-tiny's, longcat-flash-ep32
# longcat-flash-tiny's; the real sizes hold 3-10 GB, no test draws them)
PARENT_WEIGHTS = {
    "qwen3-tiny": "66ad1273951895dd",
    "mistral-tiny": "da47c189133dd00b",
    "moe-tiny": "382c77e017973ff2",
    "deepseek-v2-tiny": "12b3844898a3bc9a",
    "longcat-flash-tiny": "da6eb1f77eea9a6d",
    # read on the parent commit 820a192 (the layer-pattern recipe, whose
    # draw the indexer's slots leave as it was)
    "smallthinker-tiny": "27b864e2d5498b17",
}


@pytest.mark.parametrize("preset", sorted(PARENT_WEIGHTS))
def test_seeded_weights_are_the_parents_bit_for_bit(preset):
    params = tf.init_params(get_preset(preset), jax.random.key(SEED))
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in sorted(leaves,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(leaf.dtype).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest()[:16] == PARENT_WEIGHTS[preset]


# sha256[:16] of the lowered text (``.lower(...).as_text()``) of four
# served programs of an engine built as below, read on the parent commit
# (e4dbd32) with this jax: "+flash" lowers the Pallas kernels (interpret
# mode on the CPU), the others the portable branch.  A pool's names and
# shapes and the programs' signatures are part of the text.  The third,
# the mixed program, was read again when it took the decode rows' token
# carry (two operands and one scatter: ``fused_step``'s ``carry``).
PARENT_LOWERED = {
    "deepseek-v2-tiny": ("69e175e50750b7c8", "3d6b8bf5b7051210",
                         "e06e909bfed84ebe", "961c123c34b30d80"),
    "deepseek-v2-tiny+flash": ("3f4276d5a079e912", "7b556aff6518dd68",
                               "529fb7ddb1ef5819", "ee019e3b5bd17244"),
    "longcat-flash-tiny": ("cbac70680b2bbc6b", "f9a0fc32b4bd0e4e",
                           "a3140c5cdae1b157", "c884cfba892f655e"),
    "mistral-tiny": ("8e81c8d97f7e6dd0", "d7d8dbc3661ff65a",
                     "202c1fe3cd4b99be", "6cdd37a3da3d8332"),
    "mistral-tiny+flash": ("b21954f69169ccf3", "a10a6f6c9fd692de",
                           "03611b52488c334a", "b3c84bff4124c5b3"),
    "qwen3-tiny": ("b1667d3d9898b704", "e780ed95b7f9b83c",
                   "c4c8d47dca3ec2f3", "8e3211b8d3029993"),
    "qwen3-tiny+flash": ("5fd27836213f138b", "8100d9bdc3f55148",
                         "a8ccb423f57c6637", "0718bd7217a5c46f"),
    # a cache kept by layer kind (its pools sized by auto_cache_config),
    # read on the parent commit 820a192 before the sparse-attention path
    "smallthinker-tiny": ("d1fc5a37b9dfdf1a", "019d13e265268762",
                          "2744284bd0079d31", "62234242bd577cdb"),
    "smallthinker-tiny+flash": ("71cc221e4feab165", "c262f50a4a4edb21",
                                "e353c1744a231185", "84f90a542b30f8e4"),
}
PROGRAMS = ("prefill/b32r2", "fused/decode-t16", "fused/mixed-hidden-t64",
            "burst/s8-greedy")


@pytest.mark.parametrize("variant", sorted(PARENT_LOWERED))
def test_the_served_programs_lower_to_the_parents_text(variant):
    """A model of period one lowers to the text it lowered to before the
    scan went by period and the cache by kind (mistral-tiny: one WINDOW
    kind, still one pool under today's names)."""
    cfg = get_preset(variant.split("+")[0])
    if variant.endswith("+flash"):
        cfg = dataclasses.replace(cfg, attn_impl="flash")
    cc = (auto_cache_config(cfg, page_size=16, max_model_len=128,
                            max_batch_size=4, step_span=64)
          if cfg.cache_by_kind else
          CacheConfig(n_pages=33, page_size=16, max_pages_per_seq=8))
    eng = NativeEngine(cfg, cc, max_batch_size=4, token_budget=64,
                       decode_burst_steps=8)
    lower = dict(eng.aot_signatures())
    got = tuple(hashlib.sha256(lower[p]().as_text().encode()).hexdigest()[:16]
                for p in PROGRAMS)
    assert got == PARENT_LOWERED[variant]
