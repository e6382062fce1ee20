"""DeepSeek-V2 through the normal serving path, against its plain
reference (``perfbench/arch/deepseek_v2.py``, loaded by path as
``perfbench/work.py`` does): latent (MLA) paged cache, YaRN rotary
embedding, a leading dense layer, shared + group-routed experts of which
this process holds a share, and what a latent cache refuses at start-up.

Tiny preset, CPU, seeded weights.  Tolerances, and why:

- ``F32_TOL`` = 2e-3 on logits of magnitude ~1 with the model in float32:
  program and reference then differ only by the order of float32 sums
  (CPU matmul blocking, absorbed against expanded attention, the online
  softmax), read 1e-5 to 3e-4 here.  The same comparison with the program
  in bfloat16 reads 0.02-0.1 and fails it, which a test below pins.
- a token whose top expert choices are a near-tie may route differently
  in program and reference (the router sees differently rounded inputs);
  in float32 at this size none flips, so no allowance is made for it.
"""

import argparse
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
    NativeEngine,
    Request,
    latent_cache_refusal,
)
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

    return work.load_arch(os.path.join(BENCH, "arch", "deepseek_v2.py"))


def tiny_file(dtype="float32") -> dict:
    with open(os.path.join(BENCH, "configs", "deepseek-v2-tiny-cpu.json")) as f:
        return dict(json.load(f), torch_dtype=dtype)


def tiny_cfg(dtype="float32", **kw) -> ModelConfig:
    return dataclasses.replace(get_preset("deepseek-v2-tiny"), dtype=dtype,
                               attn_impl="reference", **kw)


@pytest.fixture(scope="module")
def served():
    cfg = tiny_cfg()
    return cfg, tf.init_params(cfg, jax.random.key(SEED))


@pytest.fixture(scope="module")
def reference(arch):
    return arch.Forward(tiny_file(), SEED, jax.local_devices()[:1])


def reference_logits(arch, ref, tokens: list[int]) -> np.ndarray:
    """The reference's logits at every position of ``tokens``."""
    from reference import rms_norm, seq_bucket

    padded = np.zeros((seq_bucket(len(tokens)),), np.int32)
    padded[:len(tokens)] = tokens
    x = ref.hidden(padded, quant=False)[:len(tokens)]
    with jax.default_matmul_precision("highest"):
        return np.asarray(rms_norm(x, 1e-6) @ ref.head.astype(jnp.float32))


def prompt(n: int, seed: int = 0) -> list[int]:
    return [1] + [int(t) for t in
                  np.random.default_rng(seed).integers(3, 259, n - 1)]


# ---- the configuration's file and the preset say the same model -----------

def test_preset_and_configuration_file_agree(arch):
    cfg, z = get_preset("deepseek-v2-tiny"), arch.sizes(tiny_file())
    assert (z["L"], z["D"], z["H"], z["F"], z["V"]) == (
        cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.vocab_size)
    assert (z["ql"], z["r"], z["nope"], z["rope"], z["v"]) == (
        cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
        cfg.v_head_dim)
    assert (z["held"], z["routed"], z["offset"], z["shared"], z["k"]) == (
        cfg.n_experts_held, cfg.n_experts, cfg.expert_offset,
        cfg.n_shared_experts, cfg.n_experts_active)
    assert (z["n_group"], z["topk_group"], z["routed_scale"], z["nd"]) == (
        cfg.n_group, cfg.topk_group, cfg.routed_scaling, cfg.first_k_dense)


def test_the_cells_preset_is_the_published_model_cut_as_its_file_says(arch):
    with open(os.path.join(BENCH, "configs", "deepseek-v2-ep4.json")) as f:
        file = json.load(f)
    cfg, z = get_preset("deepseek-v2-ep4"), arch.sizes(file)
    assert file["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert file["published"] == {"num_hidden_layers": 60,
                                 "n_routed_experts": 160, "vocab_size": 102400}
    assert (cfg.n_layers, cfg.n_experts_held, cfg.n_experts, cfg.vocab_size,
            cfg.expert_offset) == (5, 40, 160, 25600, 0)
    assert (z["L"], z["held"], z["routed"], z["V"], z["offset"]) == (
        5, 40, 160, 25600, 0)
    assert (cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.expert_d_ff,
            cfg.latent_dim, cfg.q_lora_rank, cfg.v_head_dim) == (
        5120, 128, 12288, 1536, 576, 1536, 128)
    assert cfg.rope_yarn == (40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    # 10.33 GB of bfloat16 weights (ISSUE 29's arithmetic)
    shapes = jax.eval_shape(lambda: tf.init_params(cfg, jax.random.key(0)))
    n_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                  for s in jax.tree.leaves(shapes))
    assert 10.30e9 < n_bytes < 10.36e9


# ---- seeded weights: the reference restates the served recipe bit for bit --

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_reference_restates_the_seeded_weights_bit_for_bit(arch, dtype):
    cfg = tiny_cfg(dtype)
    params = tf.init_params(cfg, jax.random.key(SEED))
    ref = arch.Forward(tiny_file(dtype), SEED, jax.local_devices()[:1])
    for stack, theirs in ((params["dense_layers"], ref.dense),
                          (params["layers"], ref.moe)):
        for name, w in theirs.items():
            ours = stack[name]
            assert ours.shape == w.shape, name
            np.testing.assert_array_equal(
                np.asarray(ours, np.float32), np.asarray(w, np.float32), name)
    np.testing.assert_array_equal(np.asarray(params["embed"], np.float32),
                                  np.asarray(ref.embed, np.float32))
    np.testing.assert_array_equal(np.asarray(params["lm_head"], np.float32),
                                  np.asarray(ref.head, np.float32))


def test_one_stack_models_keep_their_weights_bit_for_bit():
    """``init_params`` draws under one jit per matrix now; the recipe of
    a one-stack model (and so perfbench/arch/qwen3.py's restatement) is
    the op-by-op one it had."""
    cfg = get_preset("qwen3-tiny")
    key = jax.random.key(3)
    params = tf.init_params(cfg, key)
    keys = jax.random.split(key, 12)
    L, D, H, Hd = cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.head_dim

    def op_by_op(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(cfg.jax_dtype)

    for name, k, shape, fan_in in (
            ("wq", keys[0], (L, D, H * Hd), D),
            ("wo", keys[3], (L, H * Hd, D), H * Hd),
            ("w_down", keys[7], (L, cfg.d_ff, D), cfg.d_ff)):
        np.testing.assert_array_equal(
            np.asarray(params["layers"][name], np.float32),
            np.asarray(op_by_op(k, shape, fan_in), np.float32), name)
    np.testing.assert_array_equal(
        np.asarray(params["embed"], np.float32),
        np.asarray(op_by_op(keys[8], (cfg.vocab_size, D), D), np.float32))


# ---- full forward against the reference -----------------------------------

def test_full_forward_logits_match_the_reference(arch, served, reference):
    cfg, params = served
    tokens = prompt(200, seed=1)
    want = reference_logits(arch, reference, tokens)
    got = np.asarray(tf.forward(cfg, params, jnp.asarray([tokens]))[0])
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)


def test_bfloat16_for_float32_fails_the_tolerance(arch, reference):
    """The control of the tolerance: the same weights rounded to bfloat16
    and computed in it read far outside ``F32_TOL``."""
    cfg = tiny_cfg("bfloat16")
    params = tf.init_params(cfg, jax.random.key(SEED))
    tokens = prompt(200, seed=1)
    want = reference_logits(arch, reference, tokens)
    got = np.asarray(tf.forward(cfg, params, jnp.asarray([tokens]))[0])
    assert np.abs(got - want).max() > 5 * F32_TOL


# ---- prefill, then decode through the latent cache ------------------------

def cache_for(cfg, n_pages=24, page_size=16, mp=12):
    cc = CacheConfig(n_pages=n_pages, page_size=page_size,
                     max_pages_per_seq=mp)
    return cc, init_kv_cache(cfg, cc)


def page_rows(cc, rows: int, pages_each: int) -> np.ndarray:
    out = np.full((rows, cc.max_pages_per_seq), cc.trash_page, np.int32)
    for r in range(rows):
        out[r, :pages_each] = np.arange(r * pages_each, (r + 1) * pages_each)
    return out


@pytest.mark.parametrize("attn_impl", ["reference", "flash"],
                         ids=["portable", "kernel-interpreted"])
def test_prefill_then_decode_steps_match_the_full_forward_at_every_position(
        arch, served, reference, attn_impl):
    """A whole-prompt prefill (expanded form) writes latent rows; decode
    steps then attend in the absorbed form over the pages.  Logits, not
    tokens, at every decoded position."""
    cfg, params = served
    cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    cc, cache = cache_for(cfg)
    tokens = prompt(60, seed=2)
    n0 = 37
    want = reference_logits(arch, reference, tokens)
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
    for pos in range(n0, len(tokens)):
        cache, logits = mr.decode_step(
            cfg, cc, params, cache, jnp.asarray([tokens[pos], 0]),
            jnp.asarray([pos, 0]), jnp.asarray(rows),
            jnp.asarray([True, False]), coalesce=True, kv_splits=0)
        np.testing.assert_allclose(np.asarray(logits[0]), want[pos],
                                   atol=F32_TOL, rtol=0, err_msg=str(pos))
    # every forward through an expert layer was counted on the device
    stats = np.asarray(cache["moe_stats"])
    passes = 2 * (1 + len(tokens) - n0)
    assert stats[3] == passes and stats[0] == 3 * 2 * (
        n0 + 5 + len(tokens) - n0) and 0 < stats[1] < stats[0]


@pytest.mark.parametrize("attn_impl", ["reference", "flash"],
                         ids=["portable", "kernel-interpreted"])
def test_a_prompt_split_into_chunks_and_a_fused_step_match_the_full_forward(
        arch, served, reference, attn_impl):
    """The one ragged forward: a prompt prefilled as chunks of uneven
    length (each attending over the chunks before it through the latent
    pages), then fused steps that carry a decode row of one sequence and
    a chunk of another side by side."""
    from fusioninfer_tpu.engine.fused import pack_ragged_batch

    cfg, params = served
    cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    cc, cache = cache_for(cfg)
    a, b = prompt(70, seed=3), prompt(45, seed=4)
    want_a = reference_logits(arch, reference, a)
    want_b = reference_logits(arch, reference, b)
    rows = page_rows(cc, 2, 6)
    mp = cc.max_pages_per_seq

    def step(decode, chunks):
        """decode: [(token, position, table)]; chunks: [(tokens, start,
        table)] -> (decode logits [B, V], chunk logits [NC, V])."""
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

    # sequence a: chunks of 23, 9 and 30 tokens, the last token of each
    # chunk read; then decode rows
    at = 0
    for n in (23, 9, 30):
        _, chunk_logits = step([], [(a[at:at + n], at, rows[0])])
        at += n
        np.testing.assert_allclose(chunk_logits[0], want_a[at - 1],
                                   atol=F32_TOL, rtol=0)
    # sequence b's chunks ride with a's decode rows in ONE forward
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


def test_engine_streams_sit_on_the_references_best_logit(arch, reference):
    """NativeEngine end to end (admission, budgeted chunks, decode bursts,
    dispatch-ahead): every served token is the reference's best at its
    position, or within ``F32_TOL`` of it."""
    cfg = tiny_cfg()
    eng = NativeEngine(
        cfg, cache_cfg=CacheConfig(n_pages=64, page_size=16,
                                   max_pages_per_seq=16),
        max_batch_size=4, seed=SEED, token_budget=24, decode_burst_steps=4)
    info = eng.runtime_info()
    assert info["kv_layout"] == "latent"
    # the portable path runs no kernel: no grid, no ring to report
    assert (info["grid"], info["latent_ring_pages"]) == (None, None)
    assert info["moe_experts"] == "ragged_dot dropless 4/16"
    prompts = {f"r{i}": prompt(n, seed=10 + i)
               for i, n in enumerate((5, 40, 70, 23))}
    for rid, p in prompts.items():
        eng.add_request(Request(rid, p, SamplingParams(
            max_tokens=14, temperature=0.0)))
    out: dict = {rid: [] for rid in prompts}
    while eng.has_work():
        for o in eng.step():
            out[o.request_id].append(o.token)
    for rid, p in prompts.items():
        toks = out[rid]
        assert len(toks) == 14
        logits = reference_logits(arch, reference, p + toks[:-1])
        at = logits[len(p) - 1:]
        gap = at.max(axis=-1) - at[np.arange(len(toks)), toks]
        assert gap.max() <= F32_TOL, (rid, gap)
    eng._drain_moe_stats()
    total = eng.moe_stats_total
    assert total["layer_passes"] > 0 and total["expert_touches"] > 0
    assert 0 < total["assignments_local"] < total["assignments"]
    # ... and served on /metrics under the names the benchmark reads
    from fusioninfer_tpu.engine.metrics import EngineMetrics

    page = EngineMetrics("m").render(eng)
    for name, value in total.items():
        assert f"fusioninfer:moe_{name}_total{{" in page
        assert f'}} {value}\n' in page


# ---- absorbed = expanded --------------------------------------------------

def test_a_kernel_engine_reports_the_latent_grid_and_its_page_ring():
    """``/health`` says the stream is on: the ring's pages beside
    ``"grid": "latent"`` (perfbench's ``expect`` names the grid)."""
    from fusioninfer_tpu.ops import mla_attention as mla

    eng = NativeEngine(
        dataclasses.replace(tiny_cfg(), attn_impl="flash"),
        cache_cfg=CacheConfig(n_pages=16, page_size=16, max_pages_per_seq=4),
        max_batch_size=2, seed=SEED, token_budget=16)
    info = eng.runtime_info()
    assert (info["attention"], info["grid"], info["kv_splits"]) == (
        "flash", "latent", 0)
    assert info["latent_ring_pages"] == (
        mla.MLA_RING_SLOTS * mla.MLA_PAGES_PER_UPDATE)


def test_absorbed_attention_equals_expanded_attention():
    """Scores and values straight over latent rows (``q W_UK^T`` against
    the row, the row's first ``rank`` columns through ``W_UV``) are the
    published per-head attention: the same mathematics, float32."""
    cfg = tiny_cfg()
    H, r, nope, rp, v = (cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_dim,
                         cfg.qk_rope_dim, cfg.v_head_dim)
    ks = jax.random.split(jax.random.key(0), 5)
    S = 40
    layer = {"wkv_b": jax.random.normal(ks[0], (r, H * (nope + v))) / 8,
             "wo": jax.random.normal(ks[1], (H * v, cfg.d_model)) / 8}
    latent = jax.random.normal(ks[2], (1, S, r + rp))
    q_nope = jax.random.normal(ks[3], (1, S, H, nope))
    q_rope = jax.random.normal(ks[4], (1, S, H, rp))
    k, val = tf.mla_expand_kv(cfg, layer, latent)
    expanded = tf._mla_fresh_attention(
        cfg, jnp.concatenate([q_nope, q_rope], -1), k, val) @ layer["wo"]
    q_lat, q_r = tf.mla_absorb_queries(cfg, layer, q_nope[0], q_rope[0])
    s = jnp.einsum("shd,td->hst", jnp.concatenate([q_lat, q_r], -1), latent[0])
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o_lat = jnp.einsum("hst,tr->shr", jax.nn.softmax(s, -1), latent[0, :, :r])
    absorbed = tf.mla_attn_out(cfg, layer, o_lat)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded[0]),
                               atol=2e-4, rtol=0)


def test_latent_kernel_matches_its_oracle_over_random_ragged_batches():
    from fusioninfer_tpu.ops.mla_attention import (
        mla_ragged_paged_attention,
        reference_mla_ragged_paged_attention,
    )

    L, n_pages, ps, rank, rope, H, W = 2, 40, 16, 64, 16, 4, 128
    rng = np.random.default_rng(1)
    for _ in range(4):
        pages = jnp.asarray(rng.normal(size=(L, 1, n_pages, ps, W)),
                            jnp.float32)
        R, mp = 8, 8
        kinds = rng.integers(0, 3, R)
        q_lens = np.where(kinds == 0, 1, np.where(
            kinds == 1, rng.integers(2, 30, R), 0)).astype(np.int32)
        starts = rng.integers(0, 60, R).astype(np.int32)
        q_begins = np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(np.int32)
        T = max(16, int(-(-q_lens.sum() // 16) * 16))
        perm = rng.permutation(n_pages - 1)
        tables = np.full((R, mp), n_pages - 1, np.int32)
        used = 0
        for r in range(R):
            need = -(-(starts[r] + q_lens[r]) // ps)
            tables[r, :need] = perm[used:used + need]
            used += need
        args = (jnp.asarray(rng.normal(size=(T, H, rank)) * 0.3, jnp.float32),
                jnp.asarray(rng.normal(size=(T, H, rope)) * 0.3, jnp.float32),
                pages, jnp.asarray(tables), jnp.asarray(starts),
                jnp.asarray(q_begins), jnp.asarray(q_lens))
        got = mla_ragged_paged_attention(*args, layer=1, rank=rank,
                                         interpret=True)
        want = reference_mla_ragged_paged_attention(*args, layer=1, rank=rank)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=0)


# ---- the expert layer ------------------------------------------------------

def expert_layer_weights(cfg, seed=0, experts=None):
    E = cfg.n_experts if experts is None else experts
    D, F, SF = cfg.d_model, cfg.expert_d_ff, cfg.n_shared_experts * cfg.expert_d_ff
    ks = jax.random.split(jax.random.key(seed), 7)
    return {"router": jax.random.normal(ks[0], (D, cfg.n_experts)),
            "w_gate": jax.random.normal(ks[1], (E, D, F)) / 8,
            "w_up": jax.random.normal(ks[2], (E, D, F)) / 8,
            "w_down": jax.random.normal(ks[3], (E, F, D)) / 8,
            "ws_gate": jax.random.normal(ks[4], (D, SF)) / 8,
            "ws_up": jax.random.normal(ks[5], (D, SF)) / 8,
            "ws_down": jax.random.normal(ks[6], (SF, D)) / 8}


def brute_force_choice(cfg, scores: np.ndarray):
    """Group-limited greedy selection, token by token in plain Python."""
    per = cfg.n_experts // cfg.n_group
    chosen = []
    for row in scores:
        best = [row[g * per:(g + 1) * per].max() for g in range(cfg.n_group)]
        groups = sorted(range(cfg.n_group), key=lambda g: -best[g])[:cfg.topk_group]
        allowed = [e for g in groups for e in range(g * per, (g + 1) * per)]
        chosen.append(sorted(sorted(allowed, key=lambda e: -row[e])
                             [:cfg.n_experts_active]))
    return chosen


def test_group_limited_selection_matches_brute_force():
    cfg = tiny_cfg()
    h = jax.random.normal(jax.random.key(5), (64, cfg.d_model))
    router = jax.random.normal(jax.random.key(6), (cfg.d_model, cfg.n_experts))
    idx, w = tf.moe_route(cfg, h, router)
    scores = np.asarray(jax.nn.softmax(h @ router, axis=-1))
    assert [sorted(r) for r in np.asarray(idx).tolist()] == brute_force_choice(
        cfg, scores)
    # weights: the softmax over ALL experts, scaled, not renormalised
    np.testing.assert_allclose(
        np.asarray(w), cfg.routed_scaling * np.take_along_axis(
            scores, np.asarray(idx), axis=1), rtol=1e-6)
    # and a choice confined to the best groups is not always the global one
    global_top = np.argsort(-scores, axis=1)[:, :cfg.n_experts_active]
    assert any(sorted(a) != sorted(b) for a, b in
               zip(np.asarray(idx).tolist(), global_top.tolist()))


def test_the_shares_routed_parts_plus_the_shared_experts_once_are_the_uncut_layer():
    """Four processes each hold 4 of the 16 experts and route over all
    16: their routed parts add up, with the shared experts counted once,
    to what one process holding all 16 computes."""
    cfg = tiny_cfg()
    full = expert_layer_weights(cfg)
    h = jax.random.normal(jax.random.key(7), (48, cfg.d_model))
    whole, stats = tf.moe_layer(
        dataclasses.replace(cfg, n_experts_held=0, expert_offset=0), full, h)
    assert stats.tolist() == [48 * 3, 48 * 3, int(stats[2]), 1, 0]
    shared = tf.swiglu(h, full["ws_gate"], full["ws_up"], full["ws_down"])
    parts, local = [], 0
    for share in range(4):
        lo = share * 4
        mine = dict(full, **{k: full[k][lo:lo + 4]
                             for k in ("w_gate", "w_up", "w_down")})
        y, st = tf.moe_layer(dataclasses.replace(
            cfg, n_experts_held=4, expert_offset=lo), mine, h)
        parts.append(y - shared)
        local += int(st[1])
        assert int(st[0]) == 48 * 3
    assert local == 48 * 3  # every assignment is computed by exactly one share
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), atol=2e-5, rtol=0)


def test_no_assignment_is_lost_when_every_token_picks_one_expert():
    """The worst skew: all tokens route to the same experts.  A capacity
    would drop most of them; the grouped product computes every one."""
    cfg = tiny_cfg(n_group=1, topk_group=1)
    w = expert_layer_weights(cfg, experts=cfg.n_experts_held)
    # a router that sends every token to experts 5, 6 and 4, in that order
    router = np.zeros((cfg.d_model, cfg.n_experts), np.float32)
    router[0, 5], router[0, 6], router[0, 4] = 30.0, 20.0, 10.0
    w["router"] = jnp.asarray(router)
    h = jnp.abs(jax.random.normal(jax.random.key(8), (200, cfg.d_model))) + 0.5
    y, stats = tf.moe_layer(cfg, w, h)  # holds experts 4-7
    assert stats.tolist() == [600, 600, 3, 1, 0]
    sc = np.asarray(jax.nn.softmax(h @ w["router"], axis=-1))
    want = tf.swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    for e in (4, 5, 6):
        want = want + cfg.routed_scaling * sc[:, e:e + 1] * tf.swiglu(
            h, *(w[k][e - cfg.expert_offset]
                 for k in ("w_gate", "w_up", "w_down")))
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4,
                               rtol=1e-5)
    # tokens that are not live choose nothing
    live = jnp.arange(200) < 50
    _, stats = tf.moe_layer(cfg, w, h, live)
    assert stats.tolist() == [150, 150, 3, 1, 0]


# ---- YaRN ------------------------------------------------------------------

def test_yarn_frequencies_and_mscale_against_hand_computed_values():
    cfg = get_preset("deepseek-v2-ep4")
    # find_correction_range(32, 1, 64, 10000, 4096): 64 ln(4096 / (32 2pi))
    # / (2 ln 10000) = 10.47 -> 10; 64 ln(4096 / 2pi) / (2 ln 10000) =
    # 22.51 -> 23
    assert tf.yarn_correction_range(32, 1, 64, 10000.0, 4096) == (10, 23)
    inv = np.asarray(tf.yarn_frequencies(64, 10000.0, cfg.rope_yarn))
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)  # ramp 0
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)  # ramp 1
    # pair 16: ramp (16 - 10) / 13
    ramp = 6 / 13
    np.testing.assert_allclose(
        inv[16], plain[16] / 40 * ramp + plain[16] * (1 - ramp), rtol=1e-6)
    # m = 0.1 x 0.707 x ln 40 + 1 = 1.26080; s = 192^-1/2 x m^2 = 0.114722
    assert tf.yarn_mscale(40.0, 0.707) == pytest.approx(1.260804, abs=1e-6)
    assert tf.mla_softmax_scale(cfg) == pytest.approx(0.114722, abs=1e-6)
    assert tf.yarn_mscale(1.0, 0.707) == 1.0


def test_rope_rotates_interleaved_pairs_and_writes_them_half_by_half():
    x = jnp.arange(8, dtype=jnp.float32).reshape(1, 1, 8) + 1
    freqs = jnp.asarray([0.5, 0.25, 0.125, 0.0625])
    out = np.asarray(tf.apply_rope_pairs(x, jnp.asarray([3]), freqs))[0, 0]
    for i in range(4):
        a, b, ang = 2 * i + 1, 2 * i + 2, 3 * float(freqs[i])
        assert out[i] == pytest.approx(a * math.cos(ang) - b * math.sin(ang), abs=1e-5)
        assert out[4 + i] == pytest.approx(b * math.cos(ang) + a * math.sin(ang), abs=1e-5)


# ---- the latent pool -------------------------------------------------------

def test_latent_pool_is_one_array_priced_at_its_stored_width():
    cfg = get_preset("deepseek-v2-ep4")
    assert (cfg.latent_dim, cfg.latent_row_width) == (576, 640)
    assert page_bytes(cfg, 128) == 5 * 128 * 640 * 2
    # against K and V per head: 2 x 128 heads x 192... the cache the
    # published (expanded) form would keep is 71 x as large
    per_head = 5 * 128 * 128 * (192 + 128) * 2
    assert per_head / page_bytes(cfg, 128) > 60
    cc = auto_cache_config(cfg, page_size=128, max_model_len=8192,
                           max_batch_size=64, hbm_bytes=int(16.9e9))
    assert cc.n_pages >= 64 * 64 + 1  # the 64 x 8192 reservation fits
    tiny = tiny_cfg()
    cache = init_kv_cache(tiny, CacheConfig(n_pages=8, page_size=16,
                                            max_pages_per_seq=4))
    assert set(cache) == {"kv", "moe_stats"}
    assert cache["kv"].shape == (3, 1, 8, 16, 128)
    with pytest.raises(ValueError, match="int8 pages"):
        init_kv_cache(tiny, CacheConfig(n_pages=8, page_size=16,
                                        max_pages_per_seq=4, kv_dtype="int8"))


# ---- what a latent cache refuses, at start-up, by name ---------------------

def serve_args(**kw) -> argparse.Namespace:
    base = dict(model="deepseek-v2-tiny", max_batch_size=2, max_model_len=256,
                page_size=16, hbm_utilization=0.85, tensor_parallel_size=1,
                quantization="none", seed=0, kv_host_tier_mb=0,
                no_prefix_caching=False, prefill_chunk_size=0,
                tokens_per_step=32, no_token_budget=False,
                speculative_ngram=0, decode_burst=4, no_decode_pipeline=False,
                fused_step=True, fused_sampling=True, kv_splits=-1, dtype="",
                kv_cache_dtype="auto", lora=[], load_hf="", load_checkpoint="",
                prefill_upstream="", kv_peer=[], evacuate_grace_s=0.0,
                evacuate_peer=[])
    base.update(kw)
    return argparse.Namespace(**base)


@pytest.mark.parametrize("flags, named", [
    (dict(tensor_parallel_size=2), "--tensor-parallel-size"),
    (dict(quantization="int8"), "--quantization int8"),
    (dict(kv_cache_dtype="int8"), "--kv-cache-dtype int8"),
    (dict(lora=["a=/nowhere"]), "--lora"),
    (dict(speculative_ngram=3), "--speculative-ngram"),
    (dict(kv_host_tier_mb=64), "--kv-host-tier-mb"),
    (dict(prefill_upstream="http://127.0.0.1:1"), "--prefill-upstream"),
    (dict(kv_peer=["http://127.0.0.1:1"]), "--kv-peer"),
    (dict(evacuate_grace_s=5.0), "--evacuate-grace-s"),
    (dict(evacuate_peer=["http://127.0.0.1:1"]), "--evacuate-peer"),
    (dict(load_hf="/nowhere"), "--load-hf"),
    (dict(load_checkpoint="/nowhere"), "--load-checkpoint"),
])
def test_engine_serve_refuses_at_start_up_by_the_flags_name(flags, named):
    from fusioninfer_tpu.engine.server import _engine_from_args

    with pytest.raises(SystemExit) as refusal:
        _engine_from_args(serve_args(**flags))
    message = str(refusal.value)
    assert "latent (MLA) KV cache" in message and named in message


def test_engine_serve_builds_the_same_flags_without_the_refused_ones():
    from fusioninfer_tpu.engine.server import _engine_from_args

    engine, name = _engine_from_args(serve_args())
    assert name == "deepseek-v2-tiny" and engine.token_budget == 32
    assert engine.runtime_info()["kv_layout"] == "latent"


@pytest.mark.parametrize("kwargs, named", [
    (dict(speculative_k=2), "speculative decoding"),
    (dict(lora_adapters={"a": {}}), "LoRA adapters"),
    (dict(cache_cfg=CacheConfig(n_pages=8, page_size=16, max_pages_per_seq=4,
                                kv_dtype="int8")), "int8 KV pages"),
    (dict(host_kv_tier=object()), "host KV tier"),
    (dict(mesh=object()), "device mesh"),
])
def test_the_engine_itself_refuses_what_the_cli_refuses(kwargs, named):
    with pytest.raises(ValueError, match=named):
        NativeEngine(tiny_cfg(), **kwargs)


def test_a_running_latent_engine_refuses_transfer_fabric_and_evacuation():
    eng = NativeEngine(tiny_cfg(), cache_cfg=CacheConfig(
        n_pages=8, page_size=16, max_pages_per_seq=4), max_batch_size=2)
    req = Request("r", prompt(5), SamplingParams(max_tokens=2))
    for call, named in ((lambda: eng.request_prefill_slab(req), "KV transfer"),
                        (lambda: eng.add_prefilled_request(req, None), "KV transfer"),
                        (lambda: eng.set_kv_fabric(object()), "KV fabric"),
                        (lambda: eng.begin_evacuation(5.0), "evacuation")):
        with pytest.raises(ValueError, match=named):
            call()
    assert latent_cache_refusal(get_preset("qwen3-tiny"), mesh=True) is None
    with pytest.raises(KeyError):
        latent_cache_refusal(tiny_cfg(), no_such_feature=True)


# ---- the latent and sparse-attention kernels compile for the chip ----------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_latent_kernel_compiles_for_a_v5e_at_published_widths(one_chip):
    from fusioninfer_tpu.ops.mla_attention import mla_ragged_paged_attention

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    T, H, rank, rope, R, mp = 576, 128, 512, 64, 128, 64

    def attend(q_lat, q_rope, pages, tables, starts, begins, lens, layer):
        return mla_ragged_paged_attention(q_lat, q_rope, pages, tables, starts,
                                          begins, lens, layer=layer, rank=rank)

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(attend).lower(
            sds((T, H, rank), jnp.bfloat16), sds((T, H, rope), jnp.bfloat16),
            sds((5, 1, 512, 128, 640), jnp.bfloat16), sds((R, mp)), sds((R,)),
            sds((R,)), sds((R,)), sds(())).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert "tpu_custom_call" in compiled.as_text()


def test_sparse_attention_kernels_compile_for_a_v5e_at_published_widths(
        one_chip):
    """``keye-vl2-30b-a3b``'s three kernels (the indexer's scores over
    paged indexer keys, the exact top-2048 selection, attention over the
    chosen positions) in one mixed step of 2 048 flat tokens over a pool
    of 8 x 65 536 positions: Mosaic refuses here what interpret mode
    lets through (a slice off the tiling, too much VMEM)."""
    from fusioninfer_tpu.ops import sparse_attention as sa

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    T, R, mp, ps, n_pages = 2048, 16, 512, 128, 8 * 512 + 1
    HI, Di, KV, G, Hd, K = 16, 64, 4, 8, 128, 2048

    def step(q_i, w, q, k_idx, kp, vp, tables, begins, lens, starts):
        items = sa.sparse_items(begins, lens, starts, T, sa.SPARSE_BLOCK_Q)
        N, bq = items.tok.shape
        scores = sa.indexer_paged_scores(
            jnp.moveaxis(sa.to_items(q_i, items), 2, 1),
            sa.to_items(w, items), k_idx, tables, items, layer=1)
        thr_s, thr_c = sa.sparse_select(scores, items, K)
        qa = sa.to_items(q, items).reshape(N, bq, KV, G, Hd).transpose(
            0, 2, 3, 1, 4).reshape(N, KV, G * bq, Hd)
        return sa.sparse_paged_attention(
            qa, kp, vp, scores, thr_s.reshape(N, bq), thr_c.reshape(N, bq),
            tables, items, layer=1)

    pool = (4, KV, n_pages, ps, Hd)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(step).lower(
            sds((T, HI, Di), jnp.bfloat16), sds((T, HI), jnp.float32),
            sds((T, KV * G, Hd), jnp.bfloat16),
            sds((4, n_pages, ps, 128), jnp.bfloat16),
            sds(pool, jnp.bfloat16), sds(pool, jnp.bfloat16), sds((R, mp)),
            sds((R,)), sds((R,)), sds((R,))).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    text = compiled.as_text()
    for kernel in ("indexer_paged_scores", "sparse_select",
                   "sparse_paged_attention"):
        assert kernel in text, kernel
