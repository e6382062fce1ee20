"""The checked-in jit/shard_map entry-point registry.

Every ``jax.jit`` / ``shard_map`` entry point in the package is
enumerated here with its **expected static/traced argument split** and
its **compile-signature family**.  Two consumers read the same table:

* ``tools/fusionlint`` (the ``jit-registry`` pass) scans the package AST
  for jit/shard_map sites and diffs them against this registry — a new
  entry point, a removed one, or a changed ``static_argnums`` /
  ``static_argnames`` split is a lint error until this file is updated.
  The split is the compile contract: moving an argument between the
  static and traced sides silently changes what mints compile
  signatures, which is exactly the class of drift PRs 4-6 made
  expensive (an un-bucketed value reaching a static slot retraces per
  distinct value; a config object reaching a traced slot is a tracer
  error at best).
* ``fusioninfer_tpu.utils.compile_ledger`` (the runtime twin) resolves
  every entry with a ``runtime`` path and reads its jit-cache size
  after a ``make fast`` run; ``tools/check_compile_budget.py`` fails
  the build when a family exceeds ``FAMILY_BUDGETS`` — the static pass
  proves the discipline is *written*, the ledger proves it *held*.

Keys are ``"<repo-relative module>::<qualname>"``.  ``kind``:

* ``jit`` — a module-level jitted callable (decorated def, or a
  ``partial(jax.jit, ...)(impl)`` assignment whose ``impl`` names the
  traced body).
* ``factory-jit`` — ``jax.jit(...)`` called inside a function that
  builds and returns the jitted callable (one cache per factory call;
  the ledger cannot see these, the lint pass still pins their
  existence).
* ``shard_map`` — a per-call ``shard_map`` wrapper (traces inside the
  calling jit's cache; no cache of its own).

This module is PURE DATA (no jax import) so the lint side can load it
without the accelerator stack.
"""

from __future__ import annotations

# family -> max compiled signatures across the family during `make fast`
# (tools/check_compile_budget.py).  Budgets are the measured `make fast`
# footprint plus bounded headroom — small enough that one stray
# signature family (a shape that skipped its bucket, a weak-type flip,
# an env knob resolved at trace time) trips the gate.  Measured on this
# round's fast tier: kernels 32, sampler 26, fused 26, prefill 17,
# kvsplit 12, engine-helpers 7, decode 0 — the flash-decode PR
# grew fused (the decode_hidden fused-sampling variants beside the
# logits variants), sampler (sample_topk + lm_head_topk + the "topk"
# sample mode) and added the kvsplit family (the split-count axis of
# test_paged_attention's invariance grid).  A breach means find the
# retrace, or grow the budget HERE in the same diff that grows the
# tier — never silently.
FAMILY_BUDGETS: dict[str, int] = {
    "decode": 16,
    "prefill": 24,
    "fused": 36,
    "sampler": 40,
    "engine-helpers": 12,
    "kernels": 48,
    # the flash-decode KV-split kernel (r15): split-count × shape
    # signatures from the kernel/engine bit-identity grids; measured 12
    # on this round's fast tier (the header's per-family line is the
    # same measurement)
    "kvsplit": 20,
    "model": 12,
}

ENTRY_POINTS: dict[str, dict] = {
    # -- engine/model_runner.py: the serving forwards -------------------
    "fusioninfer_tpu/engine/model_runner.py::prefill": {
        "kind": "jit",
        "family": "prefill",
        "static_argnums": (0, 1),
        "static_argnames": ("mesh",),
        "runtime": "fusioninfer_tpu.engine.model_runner:prefill",
    },
    "fusioninfer_tpu/engine/model_runner.py::decode_step": {
        "kind": "jit",
        "family": "decode",
        "impl": "_decode_step_impl",
        "static_argnums": (0, 1),
        "static_argnames": ("mesh", "coalesce", "kv_splits"),
        "runtime": "fusioninfer_tpu.engine.model_runner:decode_step",
    },
    "fusioninfer_tpu/engine/model_runner.py::decode_burst": {
        "kind": "jit",
        "family": "decode",
        "static_argnums": (0, 1),
        "static_argnames": ("mesh", "n_steps", "sample_mode", "coalesce",
                            "kv_splits"),
        "runtime": "fusioninfer_tpu.engine.model_runner:decode_burst",
    },
    "fusioninfer_tpu/engine/model_runner.py::fused_step": {
        "kind": "jit",
        "family": "fused",
        "static_argnums": (0, 1),
        "static_argnames": ("mesh", "coalesce", "kv_splits", "decode_hidden"),
        "runtime": "fusioninfer_tpu.engine.model_runner:fused_step",
    },
    # -- engine/sampler.py: the device sampling chain -------------------
    "fusioninfer_tpu/engine/sampler.py::apply_penalties": {
        "kind": "jit",
        "family": "sampler",
        "static_argnums": (),
        "static_argnames": (),
        "runtime": "fusioninfer_tpu.engine.sampler:apply_penalties",
    },
    "fusioninfer_tpu/engine/sampler.py::sample": {
        "kind": "jit",
        "family": "sampler",
        "static_argnums": (),
        "static_argnames": ("mode",),
        "runtime": "fusioninfer_tpu.engine.sampler:sample",
    },
    "fusioninfer_tpu/engine/sampler.py::sample_topk": {
        "kind": "jit",
        "family": "sampler",
        "static_argnums": (),
        "static_argnames": ("mode",),
        "runtime": "fusioninfer_tpu.engine.sampler:sample_topk",
    },
    "fusioninfer_tpu/engine/sampler.py::spec_window_draws": {
        "kind": "jit",
        "family": "sampler",
        "static_argnums": (),
        "static_argnames": (),
        "runtime": "fusioninfer_tpu.engine.sampler:spec_window_draws",
    },
    "fusioninfer_tpu/engine/sampler.py::sample_first": {
        "kind": "jit",
        "family": "sampler",
        "static_argnums": (),
        "static_argnames": ("mode",),
        "runtime": "fusioninfer_tpu.engine.sampler:sample_first",
    },
    "fusioninfer_tpu/engine/sampler.py::make_row_keys": {
        "kind": "jit",
        "family": "sampler",
        "static_argnums": (),
        "static_argnames": (),
        "runtime": "fusioninfer_tpu.engine.sampler:make_row_keys",
    },
    "fusioninfer_tpu/engine/sampler.py::count_prompt_tokens": {
        "kind": "jit",
        "family": "sampler",
        "static_argnums": (),
        "static_argnames": (),
        "runtime": "fusioninfer_tpu.engine.sampler:count_prompt_tokens",
    },
    # -- engine/engine.py: jitted device-state helpers ------------------
    "fusioninfer_tpu/engine/engine.py::_bump_count_rows": {
        "kind": "jit",
        "family": "engine-helpers",
        "static_argnums": (),
        "static_argnames": (),
        "runtime": "fusioninfer_tpu.engine.engine:_bump_count_rows",
    },
    "fusioninfer_tpu/engine/engine.py::_suppress_early_rows": {
        "kind": "jit",
        "family": "engine-helpers",
        "static_argnums": (),
        "static_argnames": (),
        "runtime": "fusioninfer_tpu.engine.engine:_suppress_early_rows",
    },
    "fusioninfer_tpu/engine/engine.py::_histogram": {
        "kind": "jit",
        "family": "engine-helpers",
        "static_argnums": (),
        "static_argnames": ("vocab",),
        "runtime": "fusioninfer_tpu.engine.engine:_histogram",
    },
    "fusioninfer_tpu/engine/engine.py::_install_slot_rows": {
        "kind": "jit",
        "family": "engine-helpers",
        "static_argnums": (),
        "static_argnames": (),
        "runtime": "fusioninfer_tpu.engine.engine:_install_slot_rows",
    },
    "fusioninfer_tpu/engine/engine.py::_mask_guided_rows": {
        "kind": "jit",
        "family": "engine-helpers",
        "static_argnums": (),
        "static_argnames": (),
        "runtime": "fusioninfer_tpu.engine.engine:_mask_guided_rows",
    },
    # -- models/transformer.py ------------------------------------------
    "fusioninfer_tpu/models/transformer.py::forward": {
        "kind": "jit",
        "family": "model",
        "static_argnums": (0,),
        "static_argnames": (),
        "runtime": "fusioninfer_tpu.models.transformer:forward",
    },
    "fusioninfer_tpu/models/transformer.py::embed_sequences": {
        "kind": "jit",
        "family": "model",
        "static_argnums": (0,),
        "static_argnames": (),
        "runtime": "fusioninfer_tpu.models.transformer:embed_sequences",
    },
    # -- ops/: the Pallas kernels ---------------------------------------
    "fusioninfer_tpu/ops/paged_attention.py::ragged_paged_attention": {
        "kind": "jit",
        "family": "kernels",
        "static_argnums": (),
        "static_argnames": ("sm_scale", "interpret", "window", "block_q",
                            "coalesce", "name"),
        "runtime": "fusioninfer_tpu.ops.paged_attention:"
                   "ragged_paged_attention",
    },
    "fusioninfer_tpu/ops/paged_attention.py::ragged_paged_attention_kvsplit": {
        "kind": "jit",
        "family": "kvsplit",
        "static_argnums": (),
        "static_argnames": ("sm_scale", "interpret", "window", "block_q",
                            "kv_splits", "name"),
        "runtime": "fusioninfer_tpu.ops.paged_attention:"
                   "ragged_paged_attention_kvsplit",
    },
    "fusioninfer_tpu/ops/mla_attention.py::mla_ragged_paged_attention": {
        "kind": "jit",
        "family": "kernels",
        "static_argnums": (),
        "static_argnames": ("rank", "interpret", "block_q"),
        "runtime": "fusioninfer_tpu.ops.mla_attention:"
                   "mla_ragged_paged_attention",
    },
    # -- models/transformer.py: seeded initialisation, one signature per
    # matrix shape of the model being built (start-up only; the ledger
    # skips them: their count is the model's, not a serving family's)
    "fusioninfer_tpu/models/transformer.py::_draw": {
        "kind": "jit",
        "family": "model",
        "static_argnums": (),
        "static_argnames": ("shape", "dtype"),
        "runtime": None,
    },
    "fusioninfer_tpu/models/transformer.py::_draw_into": {
        "kind": "jit",
        "family": "model",
        "static_argnums": (),
        "static_argnames": ("shape",),
        "runtime": None,
    },
    "fusioninfer_tpu/ops/lm_head_topk.py::lm_head_topk": {
        "kind": "jit",
        "family": "sampler",
        "static_argnums": (),
        "static_argnames": ("tied", "k", "block_v"),
        "runtime": "fusioninfer_tpu.ops.lm_head_topk:lm_head_topk",
    },
    "fusioninfer_tpu/ops/flash_attention.py::flash_attention": {
        "kind": "jit",
        "family": "kernels",
        "static_argnums": (),
        "static_argnames": ("causal", "sm_scale", "block_q", "block_k",
                            "interpret", "window"),
        "runtime": "fusioninfer_tpu.ops.flash_attention:flash_attention",
    },
    # -- ops/sharded.py: per-call shard_map wrappers (trace inside the
    # calling jit's cache; the lint pass pins the set, the ledger skips)
    "fusioninfer_tpu/ops/sharded.py::flash_attention_tp": {
        "kind": "shard_map",
        "family": "kernels",
        "runtime": None,
    },
    "fusioninfer_tpu/ops/sharded.py::ragged_paged_attention_tp": {
        "kind": "shard_map",
        "family": "kernels",
        "runtime": None,
    },
    "fusioninfer_tpu/ops/sharded.py::lm_head_topk_tp": {
        "kind": "shard_map",
        "family": "sampler",
        "runtime": None,
    },
    # -- parallel/: factory-built jits (one cache per factory call) -----
    "fusioninfer_tpu/parallel/step.py::make_forward": {
        "kind": "factory-jit",
        "family": "model",
        "runtime": None,
    },
    "fusioninfer_tpu/parallel/step.py::make_train_step.init_state": {
        "kind": "factory-jit",
        "family": "model",
        "runtime": None,
    },
    "fusioninfer_tpu/parallel/step.py::make_train_step": {
        "kind": "factory-jit",
        "family": "model",
        "runtime": None,
    },
    "fusioninfer_tpu/parallel/sharding.py::sharded_init": {
        "kind": "factory-jit",
        "family": "model",
        "runtime": None,
    },
    "fusioninfer_tpu/parallel/sharding.py::sharded_kv_cache": {
        "kind": "factory-jit",
        "family": "model",
        "runtime": None,
    },
    "fusioninfer_tpu/parallel/ring.py::make_ring_attention": {
        "kind": "factory-jit",
        "family": "model",
        "runtime": None,
    },
    "fusioninfer_tpu/parallel/ring.py::make_ring_attention#shard_map": {
        "kind": "shard_map",
        "family": "model",
        "runtime": None,
    },
}


def entries_with_runtime() -> dict[str, dict]:
    """Registry entries the compile ledger can resolve at runtime."""
    return {k: v for k, v in ENTRY_POINTS.items() if v.get("runtime")}
