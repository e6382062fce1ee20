"""Tier-1 pin of the benchmark's yardstick (``perfbench/tests`` is not
collected by ``pytest tests/``): an architecture's file is found by the
``model_type`` its configuration publishes, and the five work counts of
BOTH architecture files at their configurations are these, to the
integer: ``step_mfu`` and the roofline shares divide by them, so a count
that drifts moves a metric with no change in the program."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")


@pytest.fixture(scope="module")
def work():
    sys.path.insert(0, BENCH)
    import work

    return work


def config_of(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("config, model_type", [
    ("qwen3-1.7b", "qwen3"), ("qwen3-tiny-cpu", "qwen3"),
    ("deepseek-v2-ep4", "deepseek_v2"), ("deepseek-v2-tiny-cpu", "deepseek_v2"),
    ("longcat-flash-ep32", "longcat_flash"),
    ("longcat-flash-tiny-cpu", "longcat_flash"),
    ("smallthinker-21b-a3b", "smallthinker"),
    ("smallthinker-tiny-cpu", "smallthinker"),
    ("keye-vl2-30b-a3b", "KeyeVL2"), ("keye-vl2-tiny-cpu", "KeyeVL2")])
def test_a_configuration_resolves_to_its_architectures_file(work, config,
                                                            model_type):
    cfg = config_of(config)
    assert cfg["model_type"] == model_type
    path = work.arch_path(cfg)
    assert path == os.path.join(BENCH, "arch", model_type + ".py")
    mod = work.load_arch(path)
    assert callable(mod.Forward)
    for count in ("matmul_params", "token_flops", "prompt_flops",
                  "kv_bytes_per_position", "decode_kv_bytes"):
        assert callable(getattr(mod, count)), count


def test_every_configuration_of_the_benchmark_resolves(work):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [c["name"] for c in bench["configs"]] == [
        "qwen3-1.7b", "deepseek-v2-ep4", "longcat-flash-ep32",
        "smallthinker-21b-a3b", "keye-vl2-30b-a3b"]
    for entry in bench["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            cfg = json.load(f)
        assert os.path.isfile(work.arch_path(cfg))
        assert work.matmul_params(cfg) > 0 and work.decode_kv_bytes(cfg, [1]) > 0


def test_an_unknown_model_type_raises_with_the_path(work):
    cfg = dict(config_of("qwen3-tiny-cpu"), model_type="no_such_arch")
    with pytest.raises(ValueError, match="arch/no_such_arch.py"):
        work.token_flops(cfg, 10)
    del cfg["model_type"]
    with pytest.raises(ValueError, match="no model_type"):
        work.matmul_params(cfg)


def test_the_counts_of_qwen3_1_7b(work):
    cfg = config_of("qwen3-1.7b")
    assert work.matmul_params(cfg) == 1_720_451_072
    assert work.token_flops(cfg, 1000) == 3_670_278_144
    assert work.token_flops(cfg, 1000, with_head=False) == 3_047_948_288
    assert work.prompt_flops(cfg, 512) == 1_473_854_832_640
    assert work.kv_bytes_per_position(cfg) == 114_688
    assert work.kv_bytes_per_position(cfg, kv_dtype_bytes=1) == 57_344
    assert work.decode_kv_bytes(cfg, [100, 200]) == 34_406_400


def test_the_counts_of_deepseek_v2_ep4(work):
    """One chip's share: 5 x 149 225 472 of attention, the dense layer's
    3 x 5120 x 12288, per expert layer the router (5120 x 160), two
    shared experts and the EXPECTED 6 x 40 / 160 = 1.5 held routed experts
    of 3 x 5120 x 1536, the head at 25 600; attention counted in the
    published (expanded) form, 2 x 128 x (192 + 128) = 81 920 FLOP a
    cached position and layer; a latent row of 576 values a layer."""
    cfg = config_of("deepseek-v2-ep4")
    mod = work.load_arch(work.arch_path(cfg))
    assert mod._attention_params(mod.sizes(cfg)) == 149_225_472
    assert work.matmul_params(cfg) == 1_399_521_280
    assert work.token_flops(cfg, 1000) == 3_208_642_560
    assert work.token_flops(cfg, 1000, with_head=False) == 2_946_498_560
    assert (work.token_flops(cfg, 1001) - work.token_flops(cfg, 1000)
            == 5 * 81_920)
    assert work.prompt_flops(cfg, 512) == 1_352_946_155_520
    assert work.kv_bytes_per_position(cfg) == 5 * 576 * 2 == 5760
    assert work.kv_bytes_per_position(cfg, kv_dtype_bytes=1) == 2880
    assert work.decode_kv_bytes(cfg, [100, 200]) == 1_728_000
    # the latent kernel's arithmetic: 2 x 128 x (576 + 512) = 278 528 FLOP
    # a cached position and layer, 241.8 FLOP a byte of latent row
    assert mod.decode_attn_flops(cfg, [100, 200]) == 5 * 278_528 * 300
    assert mod.decode_attn_flops(cfg, [1]) / work.decode_kv_bytes(
        cfg, [1]) == pytest.approx(241.8, abs=0.1)


def test_the_counts_of_the_tiny_deepseek_configuration(work):
    cfg = config_of("deepseek-v2-tiny-cpu")
    mod = work.load_arch(work.arch_path(cfg))
    assert work.matmul_params(cfg) == 524_288
    assert work.token_flops(cfg, 10) == 1_067_776
    assert work.kv_bytes_per_position(cfg) == 3 * 80 * 2
    assert mod.decode_attn_flops(cfg, [10]) == 3 * 2 * 4 * (80 + 64) * 10


def test_the_counts_of_longcat_flash_ep32(work):
    """One chip's share (ISSUE 34's table): a double layer's two
    attentions of 90 570 752 and two dense FFNs of 3 x 6144 x 12288, its
    router over all 768 outputs and the EXPECTED 12 x 16 / 768 = 0.25 held
    routed experts of 3 x 6144 x 2048 (the identity experts take a third
    of the choices and multiply nothing), four layers, the head at 16 384;
    attention counted in the published (expanded) form, 2 x 64 x (192 +
    128) = 40 960 FLOP a cached position and CACHE layer, of which a layer
    has two; a latent row of 576 values a cache layer."""
    cfg = config_of("longcat-flash-ep32")
    mod = work.load_arch(work.arch_path(cfg))
    z = mod.sizes(cfg)
    assert mod._attention_params(z) == 90_570_752
    layer = 2 * (90_570_752 + 3 * 6144 * 12288) + 6144 * 768 + 9_437_184
    assert work.matmul_params(cfg) == 4 * layer + 6144 * 16384 == 2_693_791_744
    assert work.token_flops(cfg, 1000) == 5_715_263_488
    assert work.token_flops(cfg, 1000, with_head=False) == 5_513_936_896
    assert (work.token_flops(cfg, 1001) - work.token_flops(cfg, 1000)
            == 8 * 40_960)
    assert work.prompt_flops(cfg, 512) == 2_698_598_416_384
    assert work.kv_bytes_per_position(cfg) == 8 * 576 * 2 == 9216
    assert work.kv_bytes_per_position(cfg, kv_dtype_bytes=1) == 4608
    assert work.decode_kv_bytes(cfg, [100, 200]) == 2_764_800
    # the latent kernel's arithmetic: 2 x 64 x (576 + 512) = 139 264 FLOP a
    # cached position and cache layer, 120.9 FLOP a byte of latent row:
    # half DeepSeek-V2's (64 heads for 128) and half the v5e's ridge
    assert mod.decode_attn_flops(cfg, [100, 200]) == 8 * 139_264 * 300
    assert mod.decode_attn_flops(cfg, [1]) / work.decode_kv_bytes(
        cfg, [1]) == pytest.approx(120.9, abs=0.1)
    assert (z["q_scale"], round(z["kv_scale"], 4)) == (2.0, 3.4641)


def test_the_counts_of_the_tiny_longcat_configuration(work):
    cfg = config_of("longcat-flash-tiny-cpu")
    mod = work.load_arch(work.arch_path(cfg))
    assert work.matmul_params(cfg) == 792_576
    assert work.token_flops(cfg, 10) == 1_610_752
    assert work.kv_bytes_per_position(cfg) == 4 * 80 * 2
    assert mod.decode_attn_flops(cfg, [10]) == 4 * 2 * 4 * (80 + 64) * 10


def test_the_counts_of_smallthinker_21b_a3b(work):
    """Stage 0 of 7 (ISSUE 36's table): a layer's attention of 20 971 520,
    its router of 2560 x 64 and the 6 chosen experts of 3 x 2560 x 768,
    eight layers, the untied head at 151 936.  Attention by layer KIND:
    a full layer (2 of 8) sees the whole context, a windowed one (6) the
    lesser of the context and 4096; a prefill's windowed layers attend
    over the band's area, not the triangle's; 2048 B a position and
    layer."""
    cfg = config_of("smallthinker-21b-a3b")
    mod = work.load_arch(work.arch_path(cfg))
    z = mod.sizes(cfg)
    assert z["windowed"] == [False, True, True, True] * 2 == z["rotary"]
    layer = 20_971_520 + 2560 * 64 + 6 * 5_898_240
    assert work.matmul_params(cfg) == 8 * layer + 2560 * 151_936 == 841_154_560
    per_position = 4 * 28 * 128  # QK^T and PV, every head, one layer
    assert work.token_flops(cfg, 1000) == 2 * 841_154_560 + per_position * 8 * 1000
    assert work.token_flops(cfg, 12_288) == 2 * 841_154_560 + per_position * (
        2 * 12_288 + 6 * 4096) == 2_386_952_192
    assert work.token_flops(cfg, 1000, with_head=False) == 1_019_084_800
    assert work.prompt_flops(cfg, 512) == 478_890_819_584
    band = 4096 * 4097 / 2 + (8192 - 4096) * 4096
    assert work.prompt_flops(cfg, 8192) == (
        2 * 452_198_400 * 8192 + 2 * 2560 * 151_936
        + per_position * (2 * 8192 * 8193 / 2 + 6 * band)) == 10_536_626_290_688
    assert work.kv_bytes_per_position(cfg) == 8 * 2048
    assert [work.decode_kv_bytes(cfg, [c]) for c in (1024, 4096, 12_288)] == [
        8 * 1024 * 2048, 8 * 4096 * 2048, (2 * 12_288 + 6 * 4096) * 2048]
    assert [mod.decode_window_kv_bytes(cfg, [c])
            for c in (1024, 4096, 12_288)] == [
        6 * 1024 * 2048, 6 * 4096 * 2048, 6 * 4096 * 2048]


def test_the_counts_of_the_tiny_smallthinker_configuration(work):
    cfg = config_of("smallthinker-tiny-cpu")
    mod = work.load_arch(work.arch_path(cfg))
    assert work.matmul_params(cfg) == 860_160
    assert work.token_flops(cfg, 10) == 1_761_280
    assert work.token_flops(cfg, 100) == 1_896_448  # windows of 24
    assert work.kv_bytes_per_position(cfg) == 8 * 2 * 2 * 32 * 2
    assert work.decode_kv_bytes(cfg, [10, 100]) == 108_544
    assert mod.decode_window_kv_bytes(cfg, [10, 100]) == 52_224


def test_the_counts_of_keye_vl2_30b_a3b(work):
    """Stage 0 of 12: a layer's attention of 18 874 368
    (wq, wk, wv, wo at 32 / 4 heads of 128), its indexer of 2048 x (16 x
    64 + 64 + 16), its router of 2048 x 128 and the 8 chosen experts of 3 x
    2048 x 768, four layers, the untied head at 151 936.  The indexer
    reads the whole context (16 heads x (2 x 64 + 2) FLOPs a position), the
    attention min(context, 2048) positions; a position caches 2 x 4 x 128
    values and one 64-wide indexer key a layer."""
    cfg = config_of("keye-vl2-30b-a3b")
    mod = work.load_arch(work.arch_path(cfg))
    layer = 18_874_368 + 2048 * 1104 + 2048 * 128 + 8 * 3 * 2048 * 768
    assert layer == 59_146_240
    assert work.matmul_params(cfg) == 4 * layer + 2048 * 151_936 == 547_749_888
    index, attend = 16 * 130, 4 * 32 * 128  # a position, one layer
    assert work.token_flops(cfg, 1000) == (
        2 * 547_749_888 + 4 * (index + attend) * 1000) == 1_169_355_776
    assert work.token_flops(cfg, 40_000) == (
        2 * 547_749_888 + 4 * (index * 40_000 + attend * 2048))
    assert work.token_flops(cfg, 1000, with_head=False) == (
        1_169_355_776 - 2 * 2048 * 151_936)
    n = 4096
    chosen = 2048 * 2049 / 2 + (n - 2048) * 2048
    assert work.prompt_flops(cfg, n) == (
        2 * 4 * layer * n + 2 * 2048 * 151_936
        + 4 * index * n * (n + 1) / 2 + 4 * attend * chosen)
    assert work.kv_bytes_per_position(cfg) == 4 * (2 * 4 * 128 + 64) * 2
    assert work.decode_kv_bytes(cfg, [1000, 40_000]) == (
        4 * 64 * 2 * 41_000 + 4 * 2048 * (1000 + 2048))
    assert mod.indexer_bytes(cfg, [1000]) == 4 * 64 * 2 * 1000
    assert mod.sparse_attn_bytes(cfg, [40_000]) == 4 * 2048 * 2048
    assert mod.indexer_flops(cfg, [10, 20]) == 4 * index * 30
    assert mod.prompt_indexer_flops(cfg, 100) == 4 * index * 5050


def test_the_counts_of_the_tiny_keye_vl2_configuration(work):
    cfg = config_of("keye-vl2-tiny-cpu")
    layer = (128 * 8 * 32 + 4 * 32 * 128 + 128 * (4 * 16 + 16 + 4)
             + 128 * 8 + 2 * 3 * 128 * 64)
    assert work.matmul_params(cfg) == 2 * layer + 128 * 512 == 285_696
    assert work.token_flops(cfg, 10) == (
        2 * 285_696 + 2 * (4 * 34 + 4 * 4 * 32) * 10)
    assert work.token_flops(cfg, 100) == (
        2 * 285_696 + 2 * (4 * 34 * 100 + 4 * 4 * 32 * 24))
    assert work.kv_bytes_per_position(cfg) == 2 * (2 * 2 * 32 + 16) * 2


class _Record:
    def __init__(self, prompt_len, stamps):
        self.prompt_len, self.stamps = prompt_len, stamps


class _Run:
    """What a metric's reader is handed, as ``perfbench/run.py`` records
    it: counters at the window's ends, the reduced trace, the client's
    records."""

    def __init__(self, config, ops, counters):
        self.config, self.chips, self.seconds = config, 1, 2.0
        self.t_open, self.t_close = 10.0, 12.0
        self.peaks = {"hbm_bytes_per_s": 819e9, "flops_bf16": 197e12}
        self.trace = {"ops": ops, "window_s": 1.0, "chips": 1}
        self.counters_open = {k: 0.0 for k in counters}
        self.counters_close = counters
        # one request: prompt 8000, tokens 1..3 stamped inside the window
        self.records = [_Record(8000, [9.0, 10.5, 11.0, 11.5, 13.0])]

    def delta(self, family):
        a, b = self.counters_open.get(family), self.counters_close.get(family)
        return None if a is None or b is None else b - a


def _reader(name):
    sys.path.insert(0, BENCH)
    import run

    return run.metric_reader(ROOT, name)


def test_the_two_new_readers_on_a_recorded_run(work):
    cfg = config_of("smallthinker-21b-a3b")
    ops = {"jit_decode_burst/ragged_paged_attention_window": 0.002,
           "jit_fused_step/ragged_paged_attention_window_kvsplit": 0.001,
           "jit_fused_step/ragged_paged_attention_kvsplit": 0.5,
           "jit_prefill/ragged_paged_attention_window": 9.0}
    counters = {"fusioninfer:kv_window_pages_trimmed_total": 36.0,
                "fusioninfer:kv_window_pages_allocated_total": 80.0,
                "fusioninfer:kv_pages_allocated_total": 300.0}
    run = _Run(cfg, ops, counters)
    assert _reader("kv_window_trimmed_pct")(run) == 45.0
    # three output tokens at contexts 8001..8003: 6 windowed layers x 4096
    # positions x 2048 B each, over 2 s of window, against 3 ms of the
    # window kind's kernel in the two decode programs a traced second
    least = 3 * 6 * 4096 * 2048 / 819e9 / 2.0
    assert _reader("attn_window_decode_roofline")(run) == pytest.approx(
        100.0 * least / 0.003)
    # attn_decode_roofline still sums both kinds' calls
    both = _reader("attn_decode_roofline")(run)
    assert both == pytest.approx(100.0 * (
        work.decode_kv_bytes(cfg, [8001, 8002, 8003]) / 819e9 / 2.0) / 0.503)
    # a program without layer kinds: no such kernel, no such counters, and
    # neither reader raises (the parent commit under this benchmark)
    old = _Run(config_of("qwen3-1.7b"),
               {"jit_decode_burst/ragged_paged_attention_kvsplit": 0.1}, {})
    assert _reader("attn_window_decode_roofline")(old) is None
    assert _reader("kv_window_trimmed_pct")(old) is None
    # and an architecture whose file has no windowed count
    odd = _Run(config_of("qwen3-1.7b"), ops, counters)
    assert _reader("attn_window_decode_roofline")(odd) is None


def test_the_sparse_attention_readers_on_a_recorded_run(work):
    cfg = config_of("keye-vl2-30b-a3b")
    mod = work.load_arch(work.arch_path(cfg))
    ops = {"jit_decode_burst/indexer_paged_scores": 0.004,
           "jit_fused_step/indexer_paged_scores": 0.2,
           "jit_decode_burst/sparse_paged_attention": 0.01,
           "jit_fused_step/sparse_paged_attention": 0.3,
           "jit_prefill/sparse_paged_attention": 5.0,
           "jit_decode_burst/sparse_select": 0.02,
           "jit_fused_step/sparse_select": 0.08}
    counters = {"fusioninfer:dsa_positions_scored_total": 4.0e9,
                "fusioninfer:dsa_positions_selected_total": 2.4e8}
    run = _Run(cfg, ops, counters)
    assert _reader("dsa_selected_share_pct")(run) == pytest.approx(6.0)
    # three output tokens at 8001..8003 and no first token in the window:
    # the indexer over their contexts, FLOP-bound at the bf16 peak,
    # against 204 ms of the kernel a traced second (every program)
    contexts = [8001, 8002, 8003]
    least = max(mod.indexer_bytes(cfg, contexts) / 819e9,
                mod.indexer_flops(cfg, contexts) / 197e12) / 2.0
    assert _reader("dsa_indexer_roofline")(run) == pytest.approx(
        100.0 * least / 0.204)
    # a first token inside the window adds its prompt's causal triangle
    # of FLOPs and its keys, read once
    run.records.append(_Record(1000, [10.5, 11.0]))
    least = max(mod.indexer_bytes(cfg, contexts + [1001, 1000])
                / 819e9, (mod.indexer_flops(cfg, contexts + [1001])
                          + mod.prompt_indexer_flops(cfg, 1000)) / 197e12) / 2.0
    assert _reader("dsa_indexer_roofline")(run) == pytest.approx(
        100.0 * least / 0.204)
    # the chosen K/V rows of the output tokens, against the kernel in the
    # two decode programs
    contexts += [1001]
    assert _reader("dsa_sparse_attn_roofline")(run) == pytest.approx(
        100.0 * mod.sparse_attn_bytes(cfg, contexts) / 819e9 / 2.0 / 0.31)
    # every scored position's float32 score read once, against the
    # selection kernel in every program
    assert _reader("dsa_select_roofline")(run) == pytest.approx(
        100.0 * 4.0e9 * 4 / 819e9 / 2.0 / 0.1)
    # a program without sparse attention (the parent commit under this
    # benchmark): nothing to read, and no exception
    old = _Run(cfg, {"jit_fused_step/ragged_paged_attention": 0.1}, {})
    for name in ("dsa_selected_share_pct", "dsa_indexer_roofline",
                 "dsa_sparse_attn_roofline", "dsa_select_roofline"):
        assert _reader(name)(old) is None, name


def test_reading_the_counts_imports_neither_jax_nor_the_program():
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {BENCH!r})\n"
        "import work\n"
        "bad = []\n"
        "for name in ('qwen3-1.7b', 'deepseek-v2-ep4', 'longcat-flash-ep32', "
        "'smallthinker-21b-a3b', 'keye-vl2-30b-a3b'):\n"
        f"    cfg = json.load(open({os.path.join(BENCH, 'configs')!r} + '/' + name + '.json'))\n"
        "    assert work.prompt_flops(cfg, 8) > 0\n"
        "bad = [m for m in sys.modules if m.split('.')[0] in "
        "('jax', 'jaxlib', 'fusioninfer_tpu')]\n"
        "print(json.dumps(bad))\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60, check=True)
    assert json.loads(out.stdout.strip().splitlines()[-1]) == []


# the counters the program renders at the window's close (zero at its
# open), and what each reader makes of them
HOST_COUNTERS = {
    "fusioninfer:sched_steps_total": 400.0,
    "fusioninfer:stream_cpu_seconds_total": 2.0,
    "fusioninfer:stream_delay_seconds_sum": 30.0,
    "fusioninfer:stream_delay_seconds_count": 6000.0,
    "fusioninfer:stream_chunks_total": 6000.0,
    "fusioninfer:stream_writes_total": 2500.0,
    "fusioninfer:host_step_dispatch_seconds_total": 8.0,
    "fusioninfer:engine_cpu_step_dispatch_seconds_total": 3.0,
    "fusioninfer:gc_seconds_total": 0.2,
    "fusioninfer:engine_stall_seconds_total": 0.75,
}


@pytest.mark.parametrize("name, want", [
    ("stream_cpu_ms_per_step", 5.0), ("stream_delay_mean_ms", 5.0),
    ("host_dispatch_offcpu_pct", 62.5), ("gc_ms_per_step", 0.5),
    ("engine_stall_s_in_window", 0.75), ("stream_chunks_per_write", 2.4)])
def test_the_host_readers_on_a_recorded_run(name, want):
    cfg = config_of("qwen3-1.7b")
    assert _reader(name)(_Run(cfg, {}, dict(HOST_COUNTERS))) == \
        pytest.approx(want)
    # a program without the family (the parent commit under this
    # benchmark): nothing to read, and no exception
    older = {k: v for k, v in HOST_COUNTERS.items()
             if k.startswith(("fusioninfer:sched_", "fusioninfer:host_"))}
    assert _reader(name)(_Run(cfg, {}, older)) is None
