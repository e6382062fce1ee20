"""Sharding rules: model pytree → ``NamedSharding`` per leaf, DERIVED.

This is the TPU replacement for the reference's delegated tensor
parallelism (vLLM `--tensor-parallel-size` passthrough, SURVEY §2.2): we
annotate shardings on the weight pytree and let XLA's SPMD partitioner
insert the ICI collectives — the scaling-book recipe, not hand-written
NCCL.

Since the logical-axis refactor, this module owns NO ``PartitionSpec``
literals: every parameter and activation names its axes ONCE from the
canonical logical vocabulary (:mod:`fusioninfer_tpu.parallel.axes` —
the T5X recipe, SNIPPETS.md [2]) and the specs are derived by mapping
those names through one :class:`~fusioninfer_tpu.parallel.axes.AxisRules`
table.  The default :data:`~fusioninfer_tpu.parallel.axes.MEGATRON_RULES`
reproduces the hand-wired Megatron layout leaf-for-leaf (golden test:
``tests/test_axis_rules.py``):

* qkv projections  ``[L, D, H·Hd]``  → column-parallel (heads split)
* attn output      ``[L, H·Hd, D]``  → row-parallel (psum after)
* FFN gate/up      ``[L, D, F]``     → column-parallel
* FFN down         ``[L, F, D]``     → row-parallel
* embedding        ``[V, D]``        → vocab-parallel rows
* lm head          ``[D, V]``        → vocab-parallel columns
* norms            replicated
* MoE expert weights additionally shard the expert axis over ``ep``.

Activations: batch over ``dp``, sequence over ``sp``; the hidden axis
stays unsharded so layernorms need no collectives.  One rules table
serves every mesh shape (1-chip, tp-only, tp×ep, tp×sp): a rule naming
a size-1 mesh axis degenerates to replication.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh

from fusioninfer_tpu.models.config import ModelConfig
from fusioninfer_tpu.parallel.axes import AxisRules, default_rules

Params = dict[str, Any]

# a leaf in the logical-axes trees: one logical name (or None) per array
# axis.  jax.tree treats tuples as pytrees, so every tree.map below
# passes ``is_leaf=_is_axes``.
LogicalAxes = Tuple[Optional[str], ...]


def _is_axes(x: Any) -> bool:
    return isinstance(x, tuple)


def param_axes(cfg: ModelConfig) -> Params:
    """Logical-axes pytree congruent with ``transformer.init_params``:
    the ONE place each parameter's axes are named."""
    layers: Params = {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv"),
        "wv": ("layers", "embed", "kv"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "embed"),
    }
    if cfg.qk_norm:
        layers["q_norm"] = ("layers", "head_dim")
        layers["k_norm"] = ("layers", "head_dim")
    if cfg.is_moe:
        # the router [L, D, E] is deliberately REPLICATED on its expert
        # axis: every shard computes routing probabilities for its own
        # tokens, and the array is tiny beside the expert weights
        layers["router"] = ("layers", "embed", None)
        layers["w_gate"] = ("layers", "expert", "embed", "mlp")
        layers["w_up"] = ("layers", "expert", "embed", "mlp")
        layers["w_down"] = ("layers", "expert", "mlp", "embed")
    else:
        layers["w_gate"] = ("layers", "embed", "mlp")
        layers["w_up"] = ("layers", "embed", "mlp")
        layers["w_down"] = ("layers", "mlp", "embed")

    axes: Params = {
        "embed": ("vocab", "embed"),
        "layers": layers,
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def param_specs(cfg: ModelConfig, rules: AxisRules | None = None) -> Params:
    """PartitionSpec pytree congruent with ``transformer.init_params``,
    derived from :func:`param_axes` through ``rules``."""
    rules = rules or default_rules()
    return jax.tree.map(lambda ax: rules.spec(*ax), param_axes(cfg),
                        is_leaf=_is_axes)


def spmd_cfg(cfg: ModelConfig, mesh: Mesh) -> ModelConfig:
    """Pin the jnp attention for auto-SPMD multi-device paths (training,
    sp/ep meshes): un-shard_mapped Pallas calls cannot run under the SPMD
    partitioner.  The one exception is a tp-only serving mesh, where the
    engine runs the kernels per shard via ``ops.sharded`` instead of
    calling this (``ops.sharded.tp_compatible`` is the gate)."""
    import dataclasses

    if mesh.size > 1 and cfg.attn_impl != "reference":
        return dataclasses.replace(cfg, attn_impl="reference")
    return cfg


def param_shardings(cfg: ModelConfig, mesh: Mesh,
                    rules: AxisRules | None = None) -> Params:
    rules = rules or default_rules()
    return jax.tree.map(
        lambda ax: rules.sharding(mesh, *ax), param_axes(cfg),
        is_leaf=_is_axes)


def _expand_quantized_axes(axes_tree: Any, param_tree: Any,
                           path: tuple = ()) -> Any:
    """Logical-axes tree congruent with a (possibly int8-quantized)
    param tree.

    A quantized leaf is ``{"_q8": int8[...], "_scale": f32[...]}``
    (:mod:`fusioninfer_tpu.models.quantization`): ``_q8`` keeps the bf16
    leaf's axes; ``_scale`` keeps them too EXCEPT on the reduced axis
    (size 1 — the contraction axis for per-channel weights, the row
    axis for the embedding table), which must be unsharded.  This is
    what lets int8 weights ride the same Megatron layout as bf16
    (VERDICT r3 ask #3 — int8 was single-device by guard).  Expansion
    happens at the LOGICAL level so the rules table stays the only spec
    minting point."""
    from fusioninfer_tpu.models.quantization import is_quantized

    if _is_axes(axes_tree):
        if not is_quantized(param_tree):
            return axes_tree
        q8 = param_tree["_q8"]
        nd = len(q8.shape)
        base = tuple(axes_tree) + (None,) * (nd - len(axes_tree))
        # quantize_rows (embedding) reduces the LAST axis; everything
        # else is quantize_int8 over the contraction (second-to-last)
        reduced = nd - 1 if path and path[-1] == "embed" else nd - 2
        scale = list(base)
        scale[reduced] = None
        return {"_q8": base, "_scale": tuple(scale)}
    return {
        k: _expand_quantized_axes(axes_tree[k], v, path + (k,))
        for k, v in param_tree.items()
    }


def shardings_for_tree(cfg: ModelConfig, mesh: Mesh, params: Params,
                       rules: AxisRules | None = None) -> Params:
    """``NamedSharding`` pytree congruent with ``params`` — quantized or
    not.  ``params`` may be real arrays or ``jax.eval_shape`` structs."""
    rules = rules or default_rules()
    axes = _expand_quantized_axes(param_axes(cfg), params)
    return jax.tree.map(
        lambda ax: rules.sharding(mesh, *ax), axes, is_leaf=_is_axes)


def token_spec(rules: AxisRules | None = None):
    """[B, S] token ids: batch over dp, sequence over sp."""
    return (rules or default_rules()).spec("batch", "length")


def activation_spec(rules: AxisRules | None = None):
    """[B, S, D] hidden states."""
    return (rules or default_rules()).spec("batch", "length", "embed")


def logit_spec(rules: AxisRules | None = None):
    """[B, S, V] logits: vocab over tp (vocab-parallel lm head)."""
    return (rules or default_rules()).spec("batch", "length", "vocab")


def kv_cache_spec(rules: AxisRules | None = None):
    """[L, KV, pages, page_size, Hd] paged KV cache: KV heads over tp.

    Head-major layout (KV ahead of pages) so the paged-attention kernel's
    per-head page DMA slices only leading dims (Mosaic tiling constraint).
    With tp ≤ n_kv_heads each tensor-parallel shard owns whole KV heads —
    the attention kernel then needs no cross-device communication during
    decode. (tp > n_kv_heads would replicate KV heads; guard in caller.)
    """
    return (rules or default_rules()).spec(
        "layers", "kv", "pages", "page", "head_dim")


def kv_scale_spec(rules: AxisRules | None = None):
    """[L, KV, n_pages, 1, ps] int8-KV per-token scale planes: the KV
    axis shards over tp exactly like the pages, so each shard's kernel
    folds its own heads' scales; the squeezed dim is replicated."""
    return (rules or default_rules()).spec(
        "layers", "kv", "pages", None, "page")


def sharded_kv_cache(cfg: ModelConfig, cache_cfg, mesh: Mesh,
                     rules: AxisRules | None = None) -> dict:
    """Allocate the paged KV cache directly in its sharded layout.  The
    pool is sized per DEVICE (``auto_cache_config(tp=...)``), so the
    whole of it does not fit one device: building it unsharded and
    ``device_put``-ing it afterwards exhausts device 0 on a real slice."""
    from fusioninfer_tpu.engine.kv_cache import init_kv_cache

    def build():
        return init_kv_cache(cfg, cache_cfg)

    def spec(name):
        if name == "moe_stats":  # the expert layers' counters: replicated
            return (rules or default_rules()).spec(None)
        return (kv_scale_spec(rules) if name.endswith("_scale")
                else kv_cache_spec(rules))

    shardings = {name: jax.sharding.NamedSharding(mesh, spec(name))
                 for name in jax.eval_shape(build)}
    return jax.jit(build, out_shardings=shardings)()


def shard_params(cfg: ModelConfig, mesh: Mesh, params: Params,
                 rules: AxisRules | None = None) -> Params:
    """Place an existing (host/replicated) param pytree onto the mesh —
    bf16 or int8-quantized (quantized leaves shard ``_q8`` like the bf16
    weight and replicate the reduced scale axis)."""
    return jax.device_put(params, shardings_for_tree(cfg, mesh, params,
                                                     rules=rules))


def sharded_init(cfg: ModelConfig, mesh: Mesh, key: jax.Array,
                 rules: AxisRules | None = None) -> Params:
    """Initialize parameters directly into their sharded layout — no
    host-side full copy, so 70B-scale weights never exist unsharded.
    ``cfg.quantization="int8"`` builds the quantized tree under the same
    jit: bf16 intermediates exist only shard-local and transiently."""
    from fusioninfer_tpu.models.transformer import init_params

    if cfg.quantization == "int8":
        from fusioninfer_tpu.models.quantization import quantize_params

        def build(k):
            return quantize_params(cfg, init_params(cfg, k))
    else:
        def build(k):
            return init_params(cfg, k)

    shapes = jax.eval_shape(build, key)
    init = jax.jit(build, out_shardings=shardings_for_tree(cfg, mesh, shapes,
                                                           rules=rules))
    return init(key)
