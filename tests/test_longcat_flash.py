"""LongCat-Flash through the normal serving path, against its plain
reference (``perfbench/arch/longcat_flash.py``, loaded by path as
``perfbench/work.py`` does): shortcut-connected double layers (two latent
attentions, two dense FFNs, one expert layer on the shortcut) over a
latent cache of two layers a layer, scaled latent attention, a router
over real and identity ("zero-compute") experts with a score-correction
bias, and the share of an expert-parallel deployment.

Tiny preset, CPU, seeded weights, logits and not tokens.  Tolerances:

- ``F32_TOL`` = 2e-3 on logits of magnitude ~1 with the model in float32
  (``tests/test_deepseek_v2.py`` states why): program and reference then
  differ by the order of float32 sums only, read 6e-6 to 3e-4 here; the
  program in bfloat16 reads 0.02-0.1 and fails it, which a test pins.
- in float32 at this size no router near-tie flips between program and
  reference, so no allowance is made for one.
"""

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
from fusioninfer_tpu.engine.engine import NativeEngine, Request
from fusioninfer_tpu.engine.kv_cache import (
    CacheConfig,
    auto_cache_config,
    init_kv_cache,
    page_bytes,
)
from fusioninfer_tpu.engine.sampler import SamplingParams
from fusioninfer_tpu.models import transformer as tf
from fusioninfer_tpu.models.config import ModelConfig, get_preset

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
F32_TOL = 2e-3
SEED = 11


@pytest.fixture(scope="module")
def arch():
    sys.path.insert(0, BENCH)
    import work

    return work.load_arch(os.path.join(BENCH, "arch", "longcat_flash.py"))


def config_file(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def tiny_file(dtype="float32") -> dict:
    return dict(config_file("longcat-flash-tiny-cpu"), torch_dtype=dtype)


def tiny_cfg(dtype="float32", **kw) -> ModelConfig:
    return dataclasses.replace(get_preset("longcat-flash-tiny"), dtype=dtype,
                               attn_impl="reference", **kw)


@pytest.fixture(scope="module")
def served():
    cfg = tiny_cfg()
    return cfg, tf.init_params(cfg, jax.random.key(SEED))


@pytest.fixture(scope="module")
def reference(arch):
    return arch.Forward(tiny_file(), SEED, jax.local_devices()[:1])


def reference_logits(ref, tokens: list[int]) -> np.ndarray:
    """The reference's logits at every position of ``tokens``."""
    from reference import rms_norm, seq_bucket

    padded = np.zeros((seq_bucket(len(tokens)),), np.int32)
    padded[:len(tokens)] = tokens
    x = ref.hidden(padded, quant=False)[:len(tokens)]
    with jax.default_matmul_precision("highest"):
        return np.asarray(rms_norm(x, 1e-5) @ ref.head.astype(jnp.float32))


def prompt(n: int, seed: int = 0) -> list[int]:
    return [1] + [int(t) for t in
                  np.random.default_rng(seed).integers(3, 259, n - 1)]


# ---- the configuration's file and the preset say the same model -----------

def test_preset_and_configuration_file_agree(arch):
    cfg, z = get_preset("longcat-flash-tiny"), arch.sizes(tiny_file())
    assert (z["L"], z["D"], z["H"], z["F"], z["EF"], z["V"]) == (
        cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.expert_d_ff,
        cfg.vocab_size)
    assert (z["ql"], z["r"], z["nope"], z["rope"], z["v"]) == (
        cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
        cfg.v_head_dim)
    assert (z["held"], z["routed"], z["zero"], z["offset"], z["k"]) == (
        cfg.n_experts_held, cfg.n_experts, cfg.n_zero_experts,
        cfg.expert_offset, cfg.n_experts_active)
    assert (z["routed_scale"], z["q_scale"], z["kv_scale"], z["eps"],
            z["theta"]) == (cfg.routed_scaling, cfg.mla_q_scale,
                            cfg.mla_kv_scale, cfg.rms_eps, cfg.rope_theta)
    assert cfg.n_cache_layers == 2 * cfg.n_layers == arch.SUB * z["L"]


def test_the_cells_preset_is_the_published_model_cut_as_its_file_says(arch):
    file = config_file("longcat-flash-ep32")
    cfg, z = get_preset("longcat-flash-ep32"), arch.sizes(file)
    assert file["reduced"] == ["num_layers", "n_routed_experts", "vocab_size"]
    assert file["published"] == {"num_layers": 28, "n_routed_experts": 512,
                                 "vocab_size": 131072}
    assert "num_hidden_layers" not in file
    assert file["moe_intermediate_size"] == file["expert_ffn_hidden_size"]
    assert (cfg.n_layers, cfg.n_cache_layers, cfg.n_experts_held,
            cfg.n_experts, cfg.n_zero_experts, cfg.router_width,
            cfg.vocab_size, cfg.expert_offset) == (
        4, 8, 16, 512, 256, 768, 16384, 0)
    assert (z["L"], z["held"], z["routed"], z["zero"], z["V"], z["k"]) == (
        4, 16, 512, 256, 16384, 12)
    assert (cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.expert_d_ff,
            cfg.latent_dim, cfg.q_lora_rank, cfg.v_head_dim,
            cfg.n_experts_active, cfg.routed_scaling) == (
        6144, 64, 12288, 2048, 576, 1536, 128, 12, 6.0)
    assert cfg.mla_q_scale == 2.0 and cfg.rope_yarn is None
    assert cfg.mla_kv_scale == pytest.approx(3.4641016, abs=1e-6)
    assert (cfg.rope_theta, cfg.rms_eps) == (1e7, 1e-5)
    # 10.38 GB of weights (ISSUE 34's table; the router in float32)
    shapes = jax.eval_shape(lambda: tf.init_params(cfg, jax.random.key(0)))
    n_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                  for s in jax.tree.leaves(shapes))
    assert 10.36e9 < n_bytes < 10.41e9
    # the served flags reserve 64 x 4096 positions of 8 cache layers
    cc = auto_cache_config(cfg, page_size=128, max_model_len=4096,
                           max_batch_size=64, hbm_bytes=int(16.9e9))
    assert cc.n_pages >= 64 * 32 + 1
    assert page_bytes(cfg, 128) == 8 * 128 * 640 * 2


# ---- seeded weights --------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_reference_restates_the_seeded_weights_bit_for_bit(arch, dtype):
    cfg = tiny_cfg(dtype)
    params = tf.init_params(cfg, jax.random.key(SEED))
    ref = arch.Forward(tiny_file(dtype), SEED, jax.local_devices()[:1])
    assert set(ref.layers) | {"attn_norm", "mlp_norm", "q_a_norm",
                              "kv_a_norm", "router_bias"} == set(params["layers"])
    for name, w in ref.layers.items():
        ours = params["layers"][name]
        assert ours.shape == w.shape, name
        np.testing.assert_array_equal(
            np.asarray(ours, np.float32), np.asarray(w, np.float32), name)
    np.testing.assert_array_equal(np.asarray(params["embed"], np.float32),
                                  np.asarray(ref.embed, np.float32))
    np.testing.assert_array_equal(np.asarray(params["lm_head"], np.float32),
                                  np.asarray(ref.head, np.float32))
    # the score-correction bias is a buffer of zeros at initialisation
    assert not np.asarray(params["layers"]["router_bias"]).any()
    assert params["layers"]["router_bias"].dtype == jnp.float32
    assert params["layers"]["router"].dtype == jnp.float32


# digests of the parent commit's seeded weights (PR 33's tree, key(7),
# every leaf as float32 in path order)
PARENT_WEIGHTS = {
    "qwen3-tiny":
        "5febf7d94e829033055dd5e6a8acf1afdba804f34a1410ac195d8cdc9f1c7d2a",
    "moe-tiny":
        "ad5b4428c82a3bf544df5eb0951ca2e39e684f3890f059fd5bb37cca16f1cc9e",
    "deepseek-v2-tiny":
        "0bda7c246649f08cbefea05afa5510425bfb9184aa9471cd04f5654eea02484f",
}


@pytest.mark.parametrize("name", sorted(PARENT_WEIGHTS))
def test_the_other_architectures_seeded_weights_are_the_parents(name):
    params = tf.init_params(get_preset(name), jax.random.key(7))
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in sorted(leaves, key=lambda kv: str(kv[0])):
        h.update(str(path).encode())
        h.update(np.asarray(leaf, np.float32).tobytes())
    assert h.hexdigest() == PARENT_WEIGHTS[name]


def test_the_benchmarks_other_presets_draw_what_they_drew():
    """``deepseek-v2-ep4`` and ``qwen3-1.7b`` are too large to draw here:
    a draw's bits are its key and its shape, so the recipe is pinned
    (slots, shapes, fan-in), and the grouped product's tiles."""
    assert {k: tf.STACK_SLOTS[k] for k in (
        "embed", "lm_head", "wo", "wq_a", "wq_b", "wkv_a", "wkv_b", "w_gate",
        "w_up", "w_down", "router", "ws_gate", "ws_up", "ws_down")} == {
        "embed": 1, "lm_head": 2, "wo": 13, "wq_a": 14, "wq_b": 15,
        "wkv_a": 16, "wkv_b": 17, "w_gate": 20, "w_up": 21, "w_down": 22,
        "router": 23, "ws_gate": 24, "ws_up": 25, "ws_down": 26}
    assert tf.DENSE_STACK_SLOT_OFFSET == 100
    ds = get_preset("deepseek-v2-ep4")
    assert tf.stack_matrix_shapes(ds, True) == {
        "wq_a": ((5120, 1536), 5120), "wq_b": ((1536, 24576), 1536),
        "wkv_a": ((5120, 576), 5120), "wkv_b": ((512, 32768), 512),
        "wo": ((16384, 5120), 16384), "router": ((5120, 160), 5120),
        "w_gate": ((40, 5120, 1536), 5120), "w_up": ((40, 5120, 1536), 5120),
        "w_down": ((40, 1536, 5120), 1536),
        "ws_gate": ((5120, 3072), 5120), "ws_up": ((5120, 3072), 5120),
        "ws_down": ((3072, 5120), 3072)}
    assert tf.stack_matrix_shapes(ds, False)["w_down"] == ((12288, 5120), 12288)
    assert "router_bias" not in jax.eval_shape(
        lambda: tf.init_params(get_preset("deepseek-v2-tiny"),
                               jax.random.key(0)))["layers"]
    q = jax.eval_shape(lambda: tf.init_params(get_preset("qwen3-1.7b"),
                                              jax.random.key(0)))
    assert q["layers"]["wq"].shape == (28, 2048, 2048) and "lm_head" not in q
    # DeepSeek-V2's two products keep their tuple; LongCat's have whole tiles
    assert tf.gmm_tiling(5120, 1536) == tf.gmm_tiling(1536, 5120) == (
        128, 2560, 768)
    for k, n in ((6144, 2048), (2048, 6144), (2048, 768), (768, 2048)):
        tm, tk, tn = tf.gmm_tiling(k, n)
        assert tm == 128 and k % tk == 0 and n % tn == 0, (k, n)


# ---- full forward against the reference -----------------------------------

def test_full_forward_logits_match_the_reference(served, reference):
    cfg, params = served
    tokens = prompt(200, seed=1)
    want = reference_logits(reference, tokens)
    got = np.asarray(tf.forward(cfg, params, jnp.asarray([tokens]))[0])
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)


def test_bfloat16_for_float32_fails_the_tolerance(reference):
    cfg = tiny_cfg("bfloat16")
    params = tf.init_params(cfg, jax.random.key(SEED))
    tokens = prompt(200, seed=1)
    want = reference_logits(reference, tokens)
    got = np.asarray(tf.forward(cfg, params, jnp.asarray([tokens]))[0])
    assert np.abs(got - want).max() > 5 * F32_TOL


@pytest.mark.parametrize("left_out", ["mla_scale_q_lora", "mla_scale_kv_lora",
                                      "zero_expert_num"])
def test_a_mechanism_left_out_of_the_reference_fails_the_tolerance(
        arch, served, left_out):
    """Each of the scales and the identity term is a large share of the
    logits: a reference without it reads far outside ``F32_TOL``."""
    cfg, params = served
    file = tiny_file()
    if left_out == "zero_expert_num":
        ref = arch.Forward(file, SEED, jax.local_devices()[:1])
        ref.z = dict(ref.z, routed=ref.z["routed"] + ref.z["zero"])  # no
        # output of the router is an identity expert's any more
        ref.z["zero"] = 0
    else:
        ref = arch.Forward(dict(file, **{left_out: False}), SEED,
                           jax.local_devices()[:1])
    tokens = prompt(200, seed=1)
    got = np.asarray(tf.forward(cfg, params, jnp.asarray([tokens]))[0])
    assert np.abs(got - reference_logits(ref, tokens)).max() > 20 * F32_TOL


# ---- prefill, then decode through the two-layers-a-layer latent cache ------

def cache_for(cfg, n_pages=24, page_size=16, mp=12):
    cc = CacheConfig(n_pages=n_pages, page_size=page_size,
                     max_pages_per_seq=mp)
    return cc, init_kv_cache(cfg, cc)


def page_rows(cc, rows: int, pages_each: int) -> np.ndarray:
    out = np.full((rows, cc.max_pages_per_seq), cc.trash_page, np.int32)
    for r in range(rows):
        out[r, :pages_each] = np.arange(r * pages_each, (r + 1) * pages_each)
    return out


def burst_controls(tokens, positions, active):
    B = len(tokens)
    ctl_i = np.zeros((B, len(mr.CTL_I_COLS)), np.int32)
    ctl_i[:, 0], ctl_i[:, 1], ctl_i[:, 7] = tokens, positions, active
    ctl_f = np.zeros((B, len(mr.CTL_F_COLS)), np.float32)
    ctl_f[:, 1], ctl_f[:, 5] = 1.0, 1.0  # top_p 1, repetition 1; greedy
    return jnp.asarray(ctl_i), jnp.asarray(ctl_f)


@pytest.mark.parametrize("attn_impl", ["reference", "flash"],
                         ids=["portable", "kernel-interpreted"])
def test_prefill_then_decode_match_the_full_forward_at_every_position(
        served, reference, attn_impl):
    """A whole-prompt prefill (expanded form) writes BOTH attentions'
    latent rows, each into its own cache layer; decode steps then attend
    in the absorbed form over the pages of all four cache layers; a
    greedy ``decode_burst`` from the same cache samples the reference's
    best token at each of its steps."""
    cfg, params = served
    cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    cc, cache = cache_for(cfg)
    assert cache["kv"].shape[0] == 4
    tokens = prompt(60, seed=2)
    n0 = 37
    want = reference_logits(reference, tokens)
    rows = page_rows(cc, 2, 6)
    toks = np.zeros((2, 64), np.int32)
    toks[0, :n0] = tokens[:n0]
    toks[1, :5] = tokens[:5]
    cache, logits = mr.prefill(cfg, cc, params, cache, jnp.asarray(toks),
                               jnp.asarray([n0, 5]), jnp.asarray(rows))
    np.testing.assert_allclose(np.asarray(logits[0]), want[n0 - 1],
                               atol=F32_TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(logits[1]), want[4],
                               atol=F32_TOL, rtol=0)
    # every cache layer holds the prompt's rows, and they differ
    pool = np.asarray(cache["kv"])[:, 0, :3]
    assert all(np.abs(pool[l]).max() > 0 for l in range(4))
    assert not np.allclose(pool[0], pool[1]) and not np.allclose(pool[1], pool[2])
    for pos in range(n0, len(tokens)):
        cache, logits = mr.decode_step(
            cfg, cc, params, cache, jnp.asarray([tokens[pos], 0]),
            jnp.asarray([pos, 0]), jnp.asarray(rows),
            jnp.asarray([True, False]), coalesce=True, kv_splits=0)
        np.testing.assert_allclose(np.asarray(logits[0]), want[pos],
                                   atol=F32_TOL, rtol=0, err_msg=str(pos))
    # the expert layer's counters: one pass a double layer and forward,
    # 4 choices a live token, the identity share among them
    stats = np.asarray(cache["moe_stats"]).tolist()
    live = n0 + 5 + len(tokens) - n0
    assert stats[3] == 2 * (1 + len(tokens) - n0)
    assert stats[0] == 4 * 2 * live
    assert 0 < stats[1] < stats[0] and 0 < stats[4] < stats[0]
    assert stats[1] + stats[4] < stats[0]  # the rest is held elsewhere
    # a burst of greedy steps over the same cache: the token it samples
    # at each step is the reference's best for the sequence so far
    V = cfg.vocab_size
    seq = list(tokens)
    ctl_i, ctl_f = burst_controls([seq[-1], 0], [len(seq) - 1, 0], [1, 0])
    # the last position's row is written again with the same token
    cache, sampled, *_ = mr.decode_burst(
        cfg, cc, params, cache, ctl_i, ctl_f,
        jnp.zeros((2, V), jnp.int32), jnp.zeros((2, V), jnp.int32),
        jnp.zeros((2, V), bool), jnp.asarray(rows), n_steps=4,
        sample_mode="greedy", coalesce=True, kv_splits=0)
    for got in np.asarray(sampled)[:, 0].tolist():
        at = reference_logits(reference, seq)[-1]
        assert at.max() - at[got] <= F32_TOL
        seq.append(got)


@pytest.mark.parametrize("attn_impl", ["reference", "flash"],
                         ids=["portable", "kernel-interpreted"])
def test_a_prompt_split_into_chunks_and_a_fused_step_match_the_full_forward(
        served, reference, attn_impl):
    """The one ragged forward: a prompt prefilled as chunks of uneven
    length (each attending over the chunks before it through the latent
    pages of both attentions), then fused steps that carry a decode row
    of one sequence and a chunk of another side by side."""
    from fusioninfer_tpu.engine.fused import pack_ragged_batch

    cfg, params = served
    cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    cc, cache = cache_for(cfg)
    a, b = prompt(70, seed=3), prompt(45, seed=4)
    want_a = reference_logits(reference, a)
    want_b = reference_logits(reference, b)
    rows = page_rows(cc, 2, 6)
    mp = cc.max_pages_per_seq

    def step(decode, chunks):
        nonlocal cache
        B = len(decode)
        packed = pack_ragged_batch(
            np.asarray([[t] for t, _, _ in decode], np.int32).reshape(B, 1),
            np.ones((B,), np.int32),
            np.asarray([p for _, p, _ in decode], np.int32),
            np.asarray([t for _, _, t in decode], np.int32).reshape(B, mp),
            np.zeros((B,), np.int32),
            [(toks, start, table, 0) for toks, start, table in chunks],
            cc.trash_page, rows=4, chunk_rows=2)
        cache, logits, chunk_logits = mr.fused_step(
            cfg, cc, params, cache, jnp.asarray(packed.tokens),
            jnp.asarray(packed.row_starts), jnp.asarray(packed.q_begins),
            jnp.asarray(packed.q_lens), jnp.asarray(packed.page_tables),
            jnp.asarray(packed.sel), jnp.asarray(packed.chunk_sel),
            coalesce=True, kv_splits=0)
        return np.asarray(logits), np.asarray(chunk_logits)

    at = 0
    for n in (23, 9, 30):
        _, chunk_logits = step([], [(a[at:at + n], at, rows[0])])
        at += n
        np.testing.assert_allclose(chunk_logits[0], want_a[at - 1],
                                   atol=F32_TOL, rtol=0)
    bt = 0
    for n in (20, 25):
        logits, chunk_logits = step([(a[at], at, rows[0])],
                                    [(b[bt:bt + n], bt, rows[1])])
        np.testing.assert_allclose(logits[0, 0], want_a[at], atol=F32_TOL,
                                   rtol=0)
        at += 1
        bt += n
        np.testing.assert_allclose(chunk_logits[0], want_b[bt - 1],
                                   atol=F32_TOL, rtol=0)


def test_engine_streams_sit_on_the_references_best_logit(reference):
    """NativeEngine end to end (admission, budgeted chunks, decode bursts,
    dispatch-ahead): every served token is the reference's best at its
    position, or within ``F32_TOL`` of it; a request of exactly
    ``max_model_len`` tokens (the cell's longest pair) runs to its end."""
    cfg = tiny_cfg()
    eng = NativeEngine(
        cfg, cache_cfg=CacheConfig(n_pages=64, page_size=16,
                                   max_pages_per_seq=8),
        max_batch_size=4, seed=SEED, token_budget=24, decode_burst_steps=4)
    info = eng.runtime_info()
    assert info["kv_layout"] == "latent"
    assert info["moe_experts"] == "ragged_dot dropless 4/16 + 8 identity"
    assert eng.cache["kv"].shape[0] == cfg.n_cache_layers == 4
    prompts = {f"r{i}": prompt(n, seed=10 + i)
               for i, n in enumerate((5, 40, 70, 23))}
    outs = {"r0": 14, "r1": 14, "r2": 128 - 70, "r3": 14}
    for rid, p in prompts.items():
        eng.add_request(Request(rid, p, SamplingParams(
            max_tokens=outs[rid], temperature=0.0)))
    out: dict = {rid: [] for rid in prompts}
    while eng.has_work():
        for o in eng.step():
            out[o.request_id].append(o.token)
    for rid, p in prompts.items():
        toks = out[rid]
        assert len(toks) == outs[rid]
        logits = reference_logits(reference, p + toks[:-1])
        at = logits[len(p) - 1:]
        gap = at.max(axis=-1) - at[np.arange(len(toks)), toks]
        assert gap.max() <= F32_TOL, (rid, gap)
    eng._drain_moe_stats()
    total = eng.moe_stats_total
    assert total["layer_passes"] > 0 and total["expert_touches"] > 0
    assert 0 < total["assignments_local"] < total["assignments"]
    assert 0 < total["assignments_zero"] < total["assignments"]
    from fusioninfer_tpu.engine.metrics import EngineMetrics

    page = EngineMetrics("m").render(eng)
    for name, value in total.items():
        assert f"fusioninfer:moe_{name}_total{{" in page
        assert f'}} {value}\n' in page
    assert "# HELP fusioninfer:moe_assignments_zero_total" in page


# ---- the router: identity experts and the score-correction bias ------------

def expert_layer_weights(cfg, seed=0, experts=None):
    E = cfg.n_experts if experts is None else experts
    D, F = cfg.d_model, cfg.expert_d_ff
    ks = jax.random.split(jax.random.key(seed), 4)
    return {"router": jax.random.normal(ks[0], (D, cfg.router_width)),
            "router_bias": jnp.zeros((cfg.router_width,), jnp.float32),
            "w_gate": jax.random.normal(ks[1], (E, D, F)) / 8,
            "w_up": jax.random.normal(ks[2], (E, D, F)) / 8,
            "w_down": jax.random.normal(ks[3], (E, F, D)) / 8}


def test_a_score_bias_moves_the_choice_and_never_the_weights(arch):
    cfg = tiny_cfg()
    h = jax.random.normal(jax.random.key(5), (64, cfg.d_model))
    router = jax.random.normal(jax.random.key(6),
                               (cfg.d_model, cfg.router_width))
    scores = np.asarray(jax.nn.softmax(h @ router, axis=-1))
    plain_idx, plain_w = tf.moe_route(cfg, h, router, jnp.zeros(
        (cfg.router_width,)))
    top = np.argsort(-scores, axis=1)[:, :cfg.n_experts_active]
    assert [sorted(r) for r in np.asarray(plain_idx).tolist()] == [
        sorted(r) for r in top.tolist()]
    # a bias that lifts expert 2 and identity expert 20 over everything
    bias = np.zeros((cfg.router_width,), np.float32)
    bias[2], bias[20] = 3.0, 1.5
    idx, w = (np.asarray(a) for a in tf.moe_route(cfg, h, router,
                                                  jnp.asarray(bias)))
    assert (idx[:, 0] == 2).all() and (idx[:, 1] == 20).all()
    assert any(sorted(a) != sorted(b) for a, b in
               zip(idx.tolist(), np.asarray(plain_idx).tolist()))
    # the weights are the UNCORRECTED scores x the scaling, not renormalised
    np.testing.assert_allclose(
        w, cfg.routed_scaling * np.take_along_axis(scores, idx, axis=1),
        rtol=1e-6)
    assert w.sum(axis=1).min() < 0.9 * cfg.routed_scaling  # not renormalised
    # the reference's router, with the same bias, says the same
    z = arch.sizes(tiny_file())
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(arch.route(z, h, router, jnp.asarray(bias)))
    ours = np.zeros_like(theirs)
    np.put_along_axis(ours, idx, w, axis=1)
    np.testing.assert_allclose(ours, theirs, atol=1e-6)


def test_a_token_of_identity_experts_only_and_one_with_none():
    """Token 0's four are all identity experts: its result is its own
    input times the sum of their weights, and no expert is touched for
    it.  Token 1 chooses real experts only: the identity term is 0."""
    cfg = tiny_cfg()
    w = expert_layer_weights(cfg, experts=cfg.n_experts_held)
    router = np.zeros((cfg.d_model, cfg.router_width), np.float32)
    router[0, [16, 17, 18, 19]] = 30.0, 28.0, 26.0, 24.0   # identity experts
    router[1, [4, 5, 6, 9]] = 30.0, 28.0, 26.0, 24.0       # 9 is held elsewhere
    w["router"] = jnp.asarray(router)
    h = np.zeros((2, cfg.d_model), np.float32)
    h[0, 0], h[1, 1] = 1.0, 1.0
    h[:, 2:] = np.asarray(jax.random.normal(jax.random.key(9),
                                            (2, cfg.d_model - 2)))
    h = jnp.asarray(h)
    y, stats = tf.moe_layer(cfg, w, h)  # holds experts 4-7
    assert stats.tolist() == [8, 3, 3, 1, 4]
    sc = np.asarray(jax.nn.softmax(h @ w["router"], axis=-1))
    factor = cfg.routed_scaling * sc[0, 16:20].sum()
    np.testing.assert_allclose(np.asarray(y[0]), factor * np.asarray(h[0]),
                               rtol=1e-5, atol=1e-6)
    want = sum(cfg.routed_scaling * sc[1, e] * tf.swiglu(
        h[1:2], *(w[k][e - cfg.expert_offset]
                  for k in ("w_gate", "w_up", "w_down")))[0]
        for e in (4, 5, 6))
    np.testing.assert_allclose(np.asarray(y[1]), np.asarray(want), atol=1e-4,
                               rtol=1e-5)
    # a token that is not live chooses nothing, identity or not
    _, stats = tf.moe_layer(cfg, w, h, jnp.asarray([False, True]))
    assert stats.tolist() == [4, 3, 3, 1, 0]


def test_the_shares_parts_with_the_identity_term_once_are_the_uncut_layer():
    """Four processes each hold 4 of the 16 real experts and route over
    all 24 outputs: their routed parts add up, with the identity term
    (which every process computes alike for its own tokens) counted once,
    to what one process holding all 16 computes."""
    cfg = tiny_cfg()
    full = expert_layer_weights(cfg)
    full["router_bias"] = jax.random.normal(jax.random.key(3),
                                            (cfg.router_width,)) * 0.02
    h = jax.random.normal(jax.random.key(7), (48, cfg.d_model))
    whole, stats = tf.moe_layer(
        dataclasses.replace(cfg, n_experts_held=0, expert_offset=0), full, h)
    n_zero = int(stats[4])
    assert stats.tolist() == [48 * 4, 48 * 4 - n_zero, int(stats[2]), 1, n_zero]
    assert 0 < n_zero < 48 * 4
    idx, w = tf.moe_route(cfg, h, full["router"], full["router_bias"])
    identity = jnp.sum(jnp.where(idx >= cfg.n_experts, w, 0.0),
                       axis=1)[:, None] * h
    parts, local = [], 0
    for share in range(4):
        lo = share * 4
        mine = dict(full, **{k: full[k][lo:lo + 4]
                             for k in ("w_gate", "w_up", "w_down")})
        y, st = tf.moe_layer(dataclasses.replace(
            cfg, n_experts_held=4, expert_offset=lo), mine, h)
        parts.append(y - identity)
        local += int(st[1])
        assert (int(st[0]), int(st[4])) == (48 * 4, n_zero)
    # every assignment is an identity one or computed by exactly one share
    assert local + n_zero == 48 * 4
    np.testing.assert_allclose(np.asarray(sum(parts) + identity),
                               np.asarray(whole), atol=2e-5, rtol=0)


def test_the_shares_of_the_whole_double_layer_add_up_to_the_uncut_layer(arch):
    """The same through the whole block and against the reference: with
    every expert held (the uncut layer) program and reference agree.  The
    expert layer sits on the shortcut, so within ONE double layer its
    result reaches nothing but the output: a share's output less what
    every chip computes alike (the two attentions, the two dense FFNs
    and the identity term: the layer with its experts' weights zeroed) is
    its experts' part, and the four parts and the common path, counted
    once, add up to the uncut layer's output."""
    cfg = tiny_cfg(n_layers=1)
    uncut = dataclasses.replace(cfg, n_experts_held=0, expert_offset=0)
    params = tf.init_params(uncut, jax.random.key(SEED))
    tokens = prompt(512, seed=5)  # a whole block of the reference's queries
    x = params["embed"][jnp.asarray([tokens])]
    pos = jnp.arange(len(tokens))[None]
    layer = jax.tree.map(lambda a: a[0], params["layers"])
    whole = tf.layer_forward(uncut, layer, x, pos)[0]
    ref = arch.Forward(dict(tiny_file(), num_layers=1, n_routed_experts=16,
                            deployment={"expert_offset": 0}),
                       SEED, jax.local_devices()[:1])
    with jax.default_matmul_precision("highest"):
        theirs = arch.layers_forward(ref.z, False, x[0], ref.layers,
                                     ref.router_bias)
    np.testing.assert_allclose(np.asarray(whole[0]), np.asarray(theirs),
                               atol=1e-4, rtol=0)

    def share_out(lo, n, zeroed=False):
        mine = dict(layer, **{k: layer[k][lo:lo + n]
                              for k in ("w_gate", "w_up", "w_down")})
        if zeroed:
            mine["w_down"] = jnp.zeros_like(mine["w_down"])
        return tf.layer_forward(dataclasses.replace(
            cfg, n_experts_held=n, expert_offset=lo), mine, x, pos)[0]

    common = share_out(0, 4, zeroed=True)
    parts = [share_out(lo, 4) - common for lo in range(0, 16, 4)]
    assert all(np.abs(np.asarray(p)).max() > 1e-3 for p in parts)
    np.testing.assert_allclose(np.asarray(sum(parts) + common),
                               np.asarray(whole), atol=1e-4, rtol=0)


# ---- the latent pool: cache layers are not layers --------------------------

def test_the_pool_and_a_pages_bytes_follow_the_cache_layers():
    cfg = get_preset("longcat-flash-ep32")
    assert (cfg.n_layers, cfg.n_cache_layers, cfg.latent_row_width) == (4, 8, 640)
    assert page_bytes(cfg, 128) == 8 * 128 * 640 * 2 == 1_310_720
    tiny = tiny_cfg()
    cache = init_kv_cache(tiny, CacheConfig(n_pages=8, page_size=16,
                                            max_pages_per_seq=4))
    assert set(cache) == {"kv", "moe_stats"}
    assert cache["kv"].shape == (4, 1, 8, 16, 128)
    assert cache["moe_stats"].shape == (len(tf.MOE_STATS),) == (5,)
    assert page_bytes(tiny, 16) == 4 * 16 * 128 * 4
    # one attention a layer: cache layers are the layers, as before
    for name in ("qwen3-tiny", "deepseek-v2-tiny", "deepseek-v2-ep4"):
        other = get_preset(name)
        assert other.n_cache_layers == other.n_layers and other.sublayers == 1
    assert page_bytes(get_preset("deepseek-v2-ep4"), 128) == 5 * 128 * 640 * 2


def test_validate_refuses_a_double_layer_without_its_parts():
    with pytest.raises(AssertionError, match="shortcut-connected"):
        dataclasses.replace(get_preset("qwen3-tiny"),
                            block="shortcut_double").validate()
    with pytest.raises(AssertionError, match="identity experts"):
        dataclasses.replace(get_preset("deepseek-v2-tiny"),
                            n_zero_experts=4).validate()
    with pytest.raises(AssertionError, match="expert fields"):
        dataclasses.replace(get_preset("qwen3-tiny"),
                            n_zero_experts=4).validate()
