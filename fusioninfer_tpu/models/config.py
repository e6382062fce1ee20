"""Model architecture configs for the native TPU engine.

Decoder-only transformer family covering the architectures the BASELINE
ladder serves (Qwen3-style with QK-norm and tied embeddings at small
sizes; Llama-3-style GQA at 70B shapes) plus a mixture-of-experts variant
for expert-parallel coverage.  Shapes are chosen MXU-friendly: head_dim
and d_ff multiples of 128, bfloat16 weights.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What one layer of the period is, as STATIC values a block is
    traced with: the positions a query sees (``window``; None = full
    causal), whether q and k are rotated (False = NoPE), and where the
    layer caches: ``pool`` is the suffix of its kind's pool in the cache
    tree ("" = ``cache["k"]``, "_win" = ``cache["k_win"]``), ``rank`` its
    index among the period's ``per_period`` layers of that pool."""

    window: int | None
    rope: bool
    pool: str = ""
    rank: int = 0
    per_period: int = 1


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "qwen3-tiny"
    vocab_size: int = 4096
    d_model: int = 256
    n_layers: int = 2
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 64
    d_ff: int = 512
    rope_theta: float = 1_000_000.0
    rms_eps: float = 1e-6
    qk_norm: bool = True  # Qwen3-style per-head RMSNorm on Q and K
    tie_embeddings: bool = True
    dtype: str = "bfloat16"
    max_seq_len: int = 4096
    # Attention implementation: "auto" (Pallas kernels on TPU, jnp
    # reference elsewhere), "flash", or "reference".  Sharded multi-device
    # paths pin "reference" — see fusioninfer_tpu.ops.dispatch.
    attn_impl: str = "auto"
    # Weight quantization: "none" (bf16) or "int8" (weight-only symmetric
    # per-channel — the single-chip fit story for 8B models; see
    # fusioninfer_tpu.models.quantization).
    quantization: str = "none"
    # Mixture of experts (0 experts == dense)
    n_experts: int = 0
    n_experts_active: int = 2
    moe_d_ff: int = 0  # per-expert FFN width; defaults to d_ff when 0
    # Sliding-window attention (Mistral-style): each token attends to the
    # previous `sliding_window` positions (itself included).  None = full
    # causal attention.  Applied in every execution path — full forward,
    # paged prefill/suffix, decode, verify — as a static mask bound, so
    # kernels skip out-of-window pages instead of reading them.
    sliding_window: int | None = None
    # A per-layer pattern of ONE period, repeated down the stack: for
    # each layer of the period ``(windowed, rotary)``.  A windowed layer
    # sees ``sliding_window`` positions, a layer without rotary adds no
    # position at all (NoPE).  None = a period of one layer of the kind
    # ``sliding_window`` already says, rotated: the homogeneous decoder
    # is the degenerate case.  SmallThinker: ((False, False), (True,
    # True), (True, True), (True, True)).
    layer_pattern: tuple | None = None
    # Router of a mixture-of-experts layer.  ``norm_topk``: the chosen
    # experts' weights are a softmax over the chosen logits (Qwen3-MoE);
    # False = the softmax over ALL experts is kept as it is and scaled by
    # ``routed_scaling`` (DeepSeek-V2).  ``n_group`` > 1: group-limited
    # greedy selection, the experts in ``n_group`` equal groups, a token
    # keeps the ``topk_group`` groups whose best expert scores highest and
    # chooses its ``n_experts_active`` among those.
    norm_topk: bool = True
    routed_scaling: float = 1.0
    n_group: int = 1
    topk_group: int = 1
    # shared experts: one SwiGLU of width n_shared_experts x expert_d_ff
    # that every token goes through, beside the routed ones
    n_shared_experts: int = 0
    # the leading ``first_k_dense`` layers keep a dense SwiGLU of width
    # d_ff; the layers after them are expert layers (two weight stacks)
    first_k_dense: int = 0
    # this process's share of the routed experts (expert parallelism told
    # to the layer): it routes over all ``n_experts`` and computes the
    # part of the result that experts [expert_offset, expert_offset +
    # n_experts_held) give.  0 held = all of them.
    n_experts_held: int = 0
    expert_offset: int = 0
    # Multi-head latent attention (DeepSeek-V2): kv_lora_rank > 0.  Queries
    # go through a q_lora_rank bottleneck; a position's cache is ONE row
    # of kv_lora_rank + qk_rope_dim values shared by every head.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # YaRN rotary scaling as (factor, original_max_position, beta_fast,
    # beta_slow, mscale, mscale_all_dim); None = plain rotary embedding
    rope_yarn: tuple | None = None
    # LongCat-Flash's latent attention: the query is scaled by
    # sqrt(d_model / q_lora_rank) and the normed compressed KV by
    # sqrt(d_model / kv_lora_rank) (``mla_scale_q_lora`` /
    # ``mla_scale_kv_lora`` of its config.json)
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    # identity ("zero-compute") experts: the router has ``n_experts +
    # n_zero_experts`` outputs, and a token that chooses one of the last
    # ``n_zero_experts`` gets its own input back, weighted, with no
    # weights read and nothing exchanged
    n_zero_experts: int = 0
    # the router's score-correction bias (one float32 a router output,
    # ``e_score_correction_bias``): added for the CHOICE of experts only,
    # the weights stay the uncorrected scores
    router_score_bias: bool = False
    # "single": attention, then the FFN.  "shortcut_double"
    # (LongCat-Flash's shortcut-connected layer): attention 0, then dense
    # FFN 0 AND, from the same normed input, the expert layer whose
    # result is held back; attention 1, dense FFN 1; only then the expert
    # layer's result is added.  One layer = two cache layers.
    block: str = "single"
    # the gate of an expert: ``W_down(act(W_gate m) * W_up m)``, "silu"
    # (SwiGLU) or "relu" (SmallThinker's ReLU-gated experts)
    expert_act: str = "silu"
    # what the router reads: "mlp_norm", the normed input of the expert
    # layer, or "layer_input", the layer's RAW input before attention
    # (SmallThinker: the routing is known while attention still runs)
    router_input: str = "mlp_norm"
    # learned sparse attention (DeepSeek Sparse Attention's indexer): a
    # query attends over the ``index_topk`` positions of highest indexer
    # score alone.  ``index_n_heads`` indexer heads of ``index_head_dim``
    # (rotary on the leading half) score against ONE indexer key a
    # position, cached beside K/V in ``cache["k_idx"]``.  0 = dense.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0

    @property
    def is_sparse(self) -> bool:
        return self.index_topk > 0

    @property
    def index_row_width(self) -> int:
        """Width an indexer key is STORED at: whole 128-lane tiles."""
        return -(-self.index_head_dim // 128) * 128

    @property
    def layer_kinds(self) -> tuple[LayerKind, ...]:
        """The period's layers in order.  Full and windowed layers of
        one model cache in pools of their own (the windowed kind's is
        "_win"); a model of one kind keeps the one pool."""
        pattern = self.layer_pattern or (
            (self.sliding_window is not None, True),)
        mixed = len({bool(w) for w, _ in pattern}) > 1
        kinds, seen = [], {}
        for windowed, rope in pattern:
            pool = "_win" if mixed and windowed else ""
            kinds.append((windowed, rope, pool, seen.get(pool, 0)))
            seen[pool] = seen.get(pool, 0) + 1
        return tuple(
            LayerKind(self.sliding_window if windowed else None, bool(rope),
                      pool, rank, seen[pool])
            for windowed, rope, pool, rank in kinds)

    @property
    def period(self) -> int:
        return len(self.layer_pattern) if self.layer_pattern else 1

    @property
    def cache_by_kind(self) -> bool:
        """Full and windowed layers both: two pools, a page list a kind."""
        return any(k.pool for k in self.layer_kinds)

    def n_pool_layers(self, pool: str = "") -> int:
        """Cache layers of the pool ``pool`` ("" or "_win")."""
        per_period = sum(k.pool == pool for k in self.layer_kinds)
        return self.n_cache_layers // self.period * per_period

    @property
    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_dim(self) -> int:
        """Width of one cached latent row: compressed KV + shared rope key."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def latent_row_width(self) -> int:
        """Width a latent row is STORED at: ``latent_dim`` rounded up to
        whole 128-lane tiles (576 -> 640; the rest is zeros).  The device
        pads the minor dimension to that anyway; stating it lets a page
        move as whole tiles."""
        return -(-self.latent_dim // 128) * 128

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    @property
    def router_width(self) -> int:
        """The router's outputs: the routed experts, then the identity
        experts."""
        return self.n_experts + self.n_zero_experts

    @property
    def sublayers(self) -> int:
        """Attentions (and dense FFNs) in one layer of the stack."""
        return 2 if self.block == "shortcut_double" else 1

    @property
    def n_cache_layers(self) -> int:
        """Layers of the KV pool: one for every attention."""
        return self.n_layers * self.sublayers

    @property
    def mla_q_scale(self) -> float:
        return ((self.d_model / self.q_lora_rank) ** 0.5
                if self.mla_scale_q_lora else 1.0)

    @property
    def mla_kv_scale(self) -> float:
        return ((self.d_model / self.kv_lora_rank) ** 0.5
                if self.mla_scale_kv_lora else 1.0)

    @property
    def n_dense_layers(self) -> int:
        """Layers with a dense FFN: all of them without experts."""
        return min(self.first_k_dense, self.n_layers) if self.is_moe else self.n_layers

    @property
    def attn_out_dim(self) -> int:
        return self.n_heads * (self.v_head_dim if self.is_mla else self.head_dim)

    def validate(self) -> "ModelConfig":
        assert self.n_heads % self.n_kv_heads == 0, "GQA requires n_heads % n_kv_heads == 0"
        assert self.d_model % self.n_heads == 0 or self.head_dim, "need explicit head_dim"
        assert self.quantization in ("none", "int8"), f"unknown quantization {self.quantization!r}"
        assert self.sliding_window is None or self.sliding_window >= 1
        assert self.expert_act in ("silu", "relu"), self.expert_act
        assert self.router_input in ("mlp_norm", "layer_input"), \
            self.router_input
        if self.layer_pattern is not None:
            assert self.layer_pattern and not self.is_mla, \
                "a layer pattern is drawn for GQA attention"
            assert self.n_layers % self.period == 0, \
                "the stack is whole periods of the layer pattern"
            assert (self.sliding_window is not None
                    or not any(w for w, _ in self.layer_pattern)), \
                "a windowed layer needs sliding_window"
        assert self.router_input == "mlp_norm" or (
            self.is_moe and not self.is_mla), \
            "only a GQA expert layer routes from the layer's input"
        if self.is_moe:
            assert self.n_experts_active <= self.n_experts
            assert self.n_experts % self.n_group == 0, "n_group must divide n_experts"
            assert 1 <= self.topk_group <= self.n_group
            assert (self.n_experts_active
                    <= self.topk_group * (self.n_experts // self.n_group)), \
                "the kept groups hold fewer experts than a token chooses"
            assert 0 <= self.expert_offset and (
                self.expert_offset + self.experts_held <= self.n_experts), \
                "held experts lie outside the router's width"
        else:
            assert not (self.first_k_dense or self.n_shared_experts
                        or self.n_experts_held or self.n_zero_experts
                        or self.router_score_bias), \
                "expert fields without experts"
        assert self.block in ("single", "shortcut_double"), self.block
        if self.block == "shortcut_double":
            assert self.is_mla and self.is_moe and not self.first_k_dense, \
                "a shortcut-connected double layer has latent attention, " \
                "an expert layer and no leading dense layers"
        assert not ((self.n_zero_experts or self.router_score_bias)
                    and (self.norm_topk or self.n_group > 1)), \
            "identity experts and the score bias go with plain softmax scores"
        if self.is_sparse:
            assert (self.index_n_heads > 0 and self.index_head_dim > 0
                    and self.index_head_dim % 4 == 0), \
                "sparse attention needs indexer heads of an even rotary half"
            assert not (self.is_mla or self.layer_pattern is not None
                        or self.sliding_window is not None), \
                "the indexer is drawn for full GQA attention of one kind"
        else:
            assert not (self.index_n_heads or self.index_head_dim), \
                "indexer fields without index_topk"
        if self.is_mla:
            assert min(self.q_lora_rank, self.qk_nope_dim, self.qk_rope_dim,
                       self.v_head_dim) > 0, "MLA needs all of its widths"
            assert self.qk_rope_dim % 2 == 0
            assert self.sliding_window is None and not self.qk_norm, \
                "latent attention has neither a window nor per-head QK norm"
        else:
            assert not (self.mla_scale_q_lora or self.mla_scale_kv_lora)
            assert not self.first_k_dense, \
                "leading dense layers are drawn only for a latent-attention model"
        return self


_PRESETS: dict[str, ModelConfig] = {}


def register_preset(cfg: ModelConfig) -> ModelConfig:
    _PRESETS[cfg.name] = cfg.validate()
    return cfg


def get_preset(name: str) -> ModelConfig:
    try:
        return _PRESETS[name]
    except KeyError:
        raise KeyError(f"unknown model preset {name!r}; known: {sorted(_PRESETS)}") from None


def list_presets() -> list[str]:
    return sorted(_PRESETS)


# -- presets -----------------------------------------------------------------

# Tiny configs: CI / CPU-mesh tests and the driver's compile checks.
register_preset(ModelConfig(name="qwen3-tiny"))
register_preset(
    ModelConfig(
        name="mistral-tiny",
        qk_norm=False,
        tie_embeddings=False,
        rope_theta=10_000.0,
        sliding_window=24,  # small enough that tests exercise the window
    )
)
register_preset(
    ModelConfig(
        name="moe-tiny",
        n_experts=4,
        n_experts_active=2,
        d_ff=512,
        moe_d_ff=512,
    )
)

# Qwen3-8B-shaped: the BASELINE north-star model (config 2/3).
register_preset(
    ModelConfig(
        name="qwen3-8b",
        vocab_size=151_936,
        d_model=4096,
        n_layers=36,
        n_heads=32,
        n_kv_heads=8,
        head_dim=128,
        d_ff=12_288,
        qk_norm=True,
        tie_embeddings=False,
        max_seq_len=32_768,
    )
)

# A ~1.7B config that fits one v5e chip (16 GiB HBM) comfortably in bf16
# with KV cache headroom — the single-chip bench model.
register_preset(
    ModelConfig(
        name="qwen3-1.7b",
        vocab_size=151_936,
        d_model=2048,
        n_layers=28,
        n_heads=16,
        n_kv_heads=8,
        head_dim=128,
        d_ff=6144,
        qk_norm=True,
        tie_embeddings=True,
        max_seq_len=32_768,
    )
)

# Qwen3-30B-A3B-shaped: MoE at production scale — 128 experts, 8 active
# (~3B active params), the expert-parallel (ep) showcase config.
register_preset(
    ModelConfig(
        name="qwen3-30b-a3b",
        vocab_size=151_936,
        d_model=2048,
        n_layers=48,
        n_heads=32,
        n_kv_heads=4,
        head_dim=128,
        d_ff=6144,
        qk_norm=True,
        tie_embeddings=False,
        max_seq_len=32_768,
        n_experts=128,
        n_experts_active=8,
        moe_d_ff=768,
    )
)

# Mistral-7B-shaped: the sliding-window-attention family — each token
# attends only to the trailing 4096 positions, bounding attention cost
# and (eventually) KV residency for long contexts.
register_preset(
    ModelConfig(
        name="mistral-7b",
        vocab_size=32_768,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        head_dim=128,
        d_ff=14_336,
        rope_theta=1_000_000.0,
        qk_norm=False,
        tie_embeddings=False,
        max_seq_len=32_768,
        sliding_window=4096,
    )
)

# Llama-3-70B-shaped: the multi-host TP target (configs 4/5).
register_preset(
    ModelConfig(
        name="llama3-70b",
        vocab_size=128_256,
        d_model=8192,
        n_layers=80,
        n_heads=64,
        n_kv_heads=8,
        head_dim=128,
        d_ff=28_672,
        qk_norm=False,
        tie_embeddings=False,
        rope_theta=500_000.0,
        max_seq_len=8192,
    )
)

# DeepSeek-V2 (huggingface.co/deepseek-ai/DeepSeek-V2 config.json): latent
# attention, a leading dense layer, then shared + group-routed experts.
_DEEPSEEK_V2 = dict(
    qk_norm=False,
    tie_embeddings=False,
    rope_theta=10_000.0,
    n_experts_active=6,
    norm_topk=False,
    routed_scaling=16.0,
    n_shared_experts=2,
    first_k_dense=1,
    rope_yarn=(40.0, 4096, 32.0, 1.0, 0.707, 0.707),
)

# One chip's share of a four-chip expert-parallel deployment at published
# widths (PERF.md section 4): layer 0 and four expert layers, experts
# 0-39 of the 160 (groups 0 and 1 of 8), a quarter of the vocabulary.
register_preset(
    ModelConfig(
        name="deepseek-v2-ep4",
        vocab_size=25_600,
        d_model=5120,
        n_layers=5,
        n_heads=128,
        n_kv_heads=128,
        head_dim=192,  # qk_nope_dim + qk_rope_dim
        d_ff=12_288,
        max_seq_len=163_840,
        n_experts=160,
        moe_d_ff=1536,
        n_group=8,
        topk_group=3,
        n_experts_held=40,
        expert_offset=0,
        kv_lora_rank=512,
        q_lora_rank=1536,
        qk_nope_dim=128,
        qk_rope_dim=64,
        v_head_dim=128,
        **_DEEPSEEK_V2,
    )
)

# The same architecture at a test size: 16 experts in 4 groups (best 2),
# 3 a token, experts 4-7 held; 1 dense + 2 expert layers.
register_preset(
    ModelConfig(
        name="deepseek-v2-tiny",
        vocab_size=512,
        d_model=128,
        n_layers=3,
        n_heads=4,
        n_kv_heads=4,
        head_dim=48,
        d_ff=256,
        max_seq_len=4096,
        n_experts=16,
        moe_d_ff=64,
        n_group=4,
        topk_group=2,
        n_experts_held=4,
        expert_offset=4,
        kv_lora_rank=64,
        q_lora_rank=96,
        qk_nope_dim=32,
        qk_rope_dim=16,
        v_head_dim=32,
        **{**_DEEPSEEK_V2, "n_experts_active": 3, "routed_scaling": 4.0},
    )
)

# LongCat-Flash-Chat (huggingface.co/meituan-longcat/LongCat-Flash-Chat
# config.json): shortcut-connected double layers (two latent attentions,
# two dense FFNs, one expert layer on the shortcut), 512 routed + 256
# identity experts, 12 a token, scaled latent attention, plain rotary.
_LONGCAT_FLASH = dict(
    qk_norm=False,
    tie_embeddings=False,
    rope_theta=10_000_000.0,
    rms_eps=1e-5,
    norm_topk=False,
    block="shortcut_double",
    router_score_bias=True,
    mla_scale_q_lora=True,
    mla_scale_kv_lora=True,
)

# One chip's share of a 32-chip expert-parallel deployment at published
# widths (PERF.md section 4): four double layers (eight cache layers),
# experts 0-15 of the 512, an eighth of the vocabulary.
register_preset(
    ModelConfig(
        name="longcat-flash-ep32",
        vocab_size=16_384,
        d_model=6144,
        n_layers=4,
        n_heads=64,
        n_kv_heads=64,
        head_dim=192,  # qk_nope_dim + qk_rope_dim
        d_ff=12_288,
        max_seq_len=131_072,
        n_experts=512,
        n_zero_experts=256,
        n_experts_active=12,
        routed_scaling=6.0,
        moe_d_ff=2048,
        n_experts_held=16,
        expert_offset=0,
        kv_lora_rank=512,
        q_lora_rank=1536,
        qk_nope_dim=128,
        qk_rope_dim=64,
        v_head_dim=128,
        **_LONGCAT_FLASH,
    )
)

# The same architecture at a test size: 2 double layers, 16 routed + 8
# identity experts, 4 a token, experts 4-7 held; deepseek-v2-tiny's
# latent-attention sizes.
register_preset(
    ModelConfig(
        name="longcat-flash-tiny",
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=4,
        head_dim=48,
        d_ff=256,
        max_seq_len=4096,
        n_experts=16,
        n_zero_experts=8,
        n_experts_active=4,
        routed_scaling=3.0,
        moe_d_ff=64,
        n_experts_held=4,
        expert_offset=4,
        kv_lora_rank=64,
        q_lora_rank=96,
        qk_nope_dim=32,
        qk_rope_dim=16,
        v_head_dim=32,
        **_LONGCAT_FLASH,
    )
)

# SmallThinker-21BA3B-Instruct (huggingface.co/PowerInfer/
# SmallThinker-21BA3B-Instruct config.json; arXiv:2507.20984): every
# fourth layer full causal attention with no positional encoding, the
# three after it rotary over a 4096-position window; 64 ReLU-gated
# experts of width 768, 6 a token, routed from the layer's raw input.
_SMALLTHINKER = dict(
    qk_norm=False,
    tie_embeddings=False,
    rope_theta=1_500_000.0,
    layer_pattern=((False, False), (True, True), (True, True), (True, True)),
    expert_act="relu",
    router_input="layer_input",
    norm_topk=True,
)

# Stage 0 of a 7-stage pipeline at published widths (PERF.md section 4):
# two whole periods of the 52 layers, all 64 experts of each, the whole
# vocabulary and the head.
register_preset(
    ModelConfig(
        name="smallthinker-21b-a3b",
        vocab_size=151_936,
        d_model=2560,
        n_layers=8,
        n_heads=28,
        n_kv_heads=4,
        head_dim=128,
        d_ff=768,
        max_seq_len=16_384,
        sliding_window=4096,
        n_experts=64,
        n_experts_active=6,
        moe_d_ff=768,
        **_SMALLTHINKER,
    )
)

# The same architecture at a test size: 2 periods, a window of 24 (a
# 3-page context of 16-token pages passes it), 8 experts, 2 a token.
register_preset(
    ModelConfig(
        name="smallthinker-tiny",
        vocab_size=512,
        d_model=128,
        n_layers=8,
        n_heads=4,
        n_kv_heads=2,
        head_dim=32,
        d_ff=64,
        max_seq_len=4096,
        sliding_window=24,
        n_experts=8,
        n_experts_active=2,
        moe_d_ff=64,
        **_SMALLTHINKER,
    )
)

# Keye-VL-2.0-30B-A3B's language model (huggingface.co/Kwai-Keye/
# Keye-VL-2.0-30B-A3B config.json): Qwen3-30B-A3B's widths (GQA 32 / 4
# heads with QK-norm, 128 experts of width 768, 8 a token, softmax over
# the chosen) with a DeepSeek-Sparse-Attention indexer in every layer
# (``sa_config``: 16 indexer heads of 64, one indexer key head, top
# 2048).  Text positions: the three M-RoPE sections carry one position,
# which is 1-D rotary at theta 1e7.
_KEYE_VL2 = dict(
    qk_norm=True,
    tie_embeddings=False,
    rope_theta=10_000_000.0,
    norm_topk=True,
    index_n_heads=16,
    index_head_dim=64,
)

# Stage 0 of a 12-stage pipeline at published widths (PERF.md section
# 4): 4 of the 48 layers, all 128 experts of each, the whole vocabulary
# and the head.
register_preset(
    ModelConfig(
        name="keye-vl2-30b-a3b",
        vocab_size=151_936,
        d_model=2048,
        n_layers=4,
        n_heads=32,
        n_kv_heads=4,
        head_dim=128,
        d_ff=6144,
        max_seq_len=65_536,
        n_experts=128,
        n_experts_active=8,
        moe_d_ff=768,
        index_topk=2048,
        **_KEYE_VL2,
    )
)

# The same architecture at a test size: top 24 of contexts to 512, so a
# 16-token page is crossed by the selection; 8 experts, 2 a token.
register_preset(
    ModelConfig(
        name="keye-vl2-tiny",
        vocab_size=512,
        d_model=128,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        head_dim=32,
        d_ff=256,
        max_seq_len=4096,
        n_experts=8,
        n_experts_active=2,
        moe_d_ff=64,
        index_topk=24,
        **{**_KEYE_VL2, "index_n_heads": 4, "index_head_dim": 16},
    )
)
