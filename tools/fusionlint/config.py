"""Project configuration for fusionlint passes.

One place for every path-scoped knob, so adding a package to a
discipline is a one-line diff here instead of a constant edit inside a
pass (the wall-clock rule was hard-coded to ``autoscale/`` through PR 2;
it is now the ``WALL_CLOCK_PACKAGES`` table below).  All paths are
repo-relative with forward slashes; ``*_MODULES`` entries are fnmatch
globs matched against ``Module.rel``.
"""

from __future__ import annotations

# what `python -m tools.fusionlint` lints when no paths are given
DEFAULT_TARGETS = [
    "fusioninfer_tpu", "tests", "tools", "bench.py", "chip_smoke.py",
    "__graft_entry__.py",
]

# -- resilience pass ---------------------------------------------------

# package prefix (or exact module path) -> names banned as direct
# `time.X()` calls (and as `from time import X` aliases) inside it.
# Control loops listed here must take an injected clock so chaos/e2e
# suites drive them deterministically; `time.monotonic` as a default
# ARGUMENT is fine, calling it inline is not.  Pacing belongs to
# `Event.wait`.
WALL_CLOCK_PACKAGES: dict[str, tuple[str, ...]] = {
    "fusioninfer_tpu/autoscale": ("time", "sleep"),
    # the token-budget scheduler must stay a pure function of replicated
    # scheduler state (SPMD lockstep): no wall clocks, no sleeps —
    # latency measurement lives engine-side (calibrate_token_budget)
    # and uses perf_counter explicitly, never time()/sleep()
    "fusioninfer_tpu/engine/sched.py": ("time", "sleep"),
    # ragged-batch packing is pure host-side assembly feeding the same
    # SPMD-replicated scheduling decision: same discipline as sched.py
    "fusioninfer_tpu/engine/fused.py": ("time", "sleep"),
    # kernel modules trace into jit caches: a wall clock in kernel or
    # dispatch code would latch a value per compiled signature and
    # silently desynchronize retraces (timing belongs to bench.py)
    "fusioninfer_tpu/ops/paged_attention.py": ("time", "sleep"),
    "fusioninfer_tpu/ops/lm_head_topk.py": ("time", "sleep"),
    "fusioninfer_tpu/ops/dispatch.py": ("time", "sleep"),
    # the engine step loop runs on an injectable clock (NativeEngine
    # clock=..., PR 7's guided-composition deflake): inline
    # monotonic()/time()/sleep() would put scheduling state back on the
    # wall clock.  perf_counter stays legal — calibrate_token_budget's
    # D2H-fenced measurement is explicitly wall-time.
    "fusioninfer_tpu/engine/engine.py": ("time", "sleep", "monotonic"),
    # the host KV tier's visibility ordering (offload commit → restore
    # hit) must be driven by queue joins and locks, never wall-time
    # pacing — a sleep here would turn the chaos suite's deterministic
    # offload/restore schedule into timing soup
    "fusioninfer_tpu/engine/kv_host_tier.py": ("time", "sleep",
                                               "monotonic"),
    # the SLO tier table feeds admission/shed decisions that must be a
    # pure function of queue state (and replay identically in tests):
    # deadlines are stamped on the ENGINE's injectable clock, never here
    "fusioninfer_tpu/engine/slo.py": ("time", "sleep", "monotonic"),
    # evacuation planning (victim order, notice-budget math) must be a
    # pure function of scheduler state under the engine's injected
    # clock — the revocation chaos suite replays park schedules
    # deterministically (docs/design/spot-revocation.md)
    "fusioninfer_tpu/engine/evacuate.py": ("time", "sleep", "monotonic"),
    # the KV fabric's assembly/coverage ledger and pull planning are
    # pure functions of the frames that arrived — pacing lives in the
    # server/connector threads (timeouts), never in fabric state, so
    # the chaos suite replays stream schedules deterministically
    "fusioninfer_tpu/engine/kv_fabric.py": ("time", "sleep", "monotonic"),
}

# -- lock-discipline pass ----------------------------------------------

# packages whose classes are analyzed (tests/tools spin up throwaway
# threads constantly and would drown the signal)
LOCK_DISCIPLINE_MODULES = [
    "fusioninfer_tpu/*.py",
    "fusioninfer_tpu/*/*.py",
]

# -- thread-safety passes (lock-order / lock-blocking) -----------------

# the whole-program lock-acquisition graph's input (the package; tests
# and tools spin up throwaway locks constantly and would drown the
# graph in dead nodes — same scoping rationale as lock-discipline)
LOCK_ORDER_MODULES = [
    "fusioninfer_tpu/*.py",
    "fusioninfer_tpu/*/*.py",
]

# serving-path modules where a blocking call under a held lock stalls
# handler threads / the step loop / the control loop behind one peer —
# the critical-section promotion of the missing-timeout rule
LOCK_BLOCKING_MODULES = [
    "fusioninfer_tpu/engine/*.py",
    "fusioninfer_tpu/router/*.py",
    "fusioninfer_tpu/autoscale/*.py",
    "fusioninfer_tpu/operator/manager.py",
    "fusioninfer_tpu/informers.py",
    "fusioninfer_tpu/fleetsim/*.py",
]

# network-blocking callables never sanctioned under a lock (timeout or
# not — a critical section must not wait on a peer)
LOCK_BLOCKING_NETWORK = (
    "urlopen", "create_connection", "getresponse", "recv", "sendall",
    "accept", "connect",
)

# -- render-purity pass ------------------------------------------------

# manifest-producing modules: the reconciler's idempotency contract is
# that re-rendering the same spec yields byte-identical children, so
# nothing here may consult wall clocks, randomness, the environment, or
# do I/O inside a function body (module level runs once at import and is
# therefore stable for the life of the process).
# workload/bootstrap.py is deliberately absent: it is pod RUNTIME code
# (jax distributed init from the downward API), not a manifest producer.
# operator/manifests.py is the I/O shell that WRITES the rendered tree;
# its builders stay pure and the write helpers are its whole point.
RENDER_PURE_MODULES = [
    # the ragged kernel + packer's bit-identity contract (split and
    # fused dispatches score identical bits) needs the same determinism
    # discipline as manifest renderers: no clocks/env/random/IO inside
    # function bodies — env knobs resolve in ops/dispatch.py module
    # scope or are passed in by the engine
    "fusioninfer_tpu/ops/paged_attention.py",
    # the fused-sampling projection's bit-identity contract (blocked
    # candidates == full top_k) rides the same determinism discipline
    "fusioninfer_tpu/ops/lm_head_topk.py",
    "fusioninfer_tpu/engine/fused.py",
    "fusioninfer_tpu/operator/render.py",
    "fusioninfer_tpu/workload/lws.py",
    "fusioninfer_tpu/workload/labels.py",
    "fusioninfer_tpu/scheduling/podgroup.py",
    "fusioninfer_tpu/router/epp.py",
    "fusioninfer_tpu/router/epp_schema.py",
    "fusioninfer_tpu/router/httproute.py",
    "fusioninfer_tpu/router/inferencepool.py",
    "fusioninfer_tpu/router/strategy.py",
    "fusioninfer_tpu/api/crd.py",
    "fusioninfer_tpu/api/modelloader.py",
]

# -- metrics-conventions pass ------------------------------------------

# modules that render Prometheus exposition text
METRICS_MODULES = [
    "fusioninfer_tpu/engine/metrics.py",
    "fusioninfer_tpu/autoscale/metrics.py",
    "fusioninfer_tpu/operator/manager.py",
]

# -- trace-boundary passes (trace-discipline / tracer-leak / host-sync /
# -- jit-registry) ------------------------------------------------------

# the checked-in entry-point registry (pure data; no jax import) — the
# jit-registry pass diffs the package's actual jit/shard_map sites
# against it, and the trace-discipline pass reads each entry's
# static/traced split to type call sites
JIT_REGISTRY_MODULE = "fusioninfer_tpu/utils/jit_registry.py"

# sharding-discipline pass: the ONE module allowed to construct
# PartitionSpec objects (the logical-axis rules table); everywhere
# else in the package, specs are DERIVED via AxisRules.spec(...) —
# a raw PartitionSpec literal is the refactor's drift vector
AXIS_RULES_MODULE = "fusioninfer_tpu/parallel/axes.py"
SHARDING_SCOPE = ["fusioninfer_tpu/*.py", "fusioninfer_tpu/*/*.py"]
# the module whose aot_signatures() enumerates the AOT warmup's
# lower-and-compile thunks — each lowered callable must be a
# jit_registry entry point (warm start covers the reviewed contract)
AOT_SIGNATURES_MODULE = "fusioninfer_tpu/engine/engine.py"

# modules scanned for jit/shard_map sites (tests/tools/bench create
# ad-hoc jits deliberately — only the package's entry points are the
# compile-discipline surface)
JIT_SCAN_MODULES = ["fusioninfer_tpu/*.py", "fusioninfer_tpu/*/*.py"]

# sanctioned dynamic-dim helpers: a host int that passed through one of
# these is SHAPE-DISCIPLINED (bounded compile-signature family); a raw
# len()/shape-derived int reaching a shape or a static arg is TAINTED
TRACE_DIM_HELPERS = (
    "pow2_rows",        # engine/fused.py — pow2 row/flat-axis buckets
    "pick_bucket",      # engine/model_runner.py — prefill buckets
    "prefill_buckets",
    "_payload_bucket",  # engine/multihost.py — broadcast payload floor
    "_pow2_pad",        # engine/engine.py — pow2 list padding
)

# call sites checked by trace-discipline (where the engine drives the
# jitted entry points)
TRACE_CALLER_MODULES = [
    "fusioninfer_tpu/engine/*.py",
    "fusioninfer_tpu/ops/*.py",
    "fusioninfer_tpu/models/*.py",
    "fusioninfer_tpu/parallel/*.py",
]

# hot-path modules for the host-sync (and host-jnp) rules, mirroring
# WALL_CLOCK_PACKAGES: a device→host fetch (np.asarray / .item() /
# float()/int() / device_get / block_until_ready on a device value)
# inside these stalls the dispatch pipeline.  Values are the SANCTIONED
# fetch-point functions — the step loop's designed blocking points —
# where the rules stay quiet.
HOST_SYNC_MODULES: dict[str, tuple[str, ...]] = {
    # the engine step loop: fetches belong in the designed consume
    # points, never ad hoc mid-step
    "fusioninfer_tpu/engine/engine.py": (
        "_consume_inflight",       # THE dispatch-ahead fetch point
        "_decode_finish",          # step tail: sampled tokens fetch
        "_decode_finish_fused",    # fused-sampling step tail: the
        #                            candidate draw's token fetch (same
        #                            designed blocking point)
        "_spec_draws",             # spec-decode acceptance draws fetch
        "_sample_first_token",     # admission sampling: the non-deferred
        #                            branch IS the fetch (guided/bias rows
        #                            need the token host-side; group
        #                            admission defers via defer_fetch)
        "_activate_group",         # ONE batched fetch for a whole
        #                            admission group (the designed
        #                            coalesced transfer)
        "_activate_finish",        # first-token logprobs readback —
        #                            returned to the client, must land
        "_embed_batch",            # embedding results are the output
        "calibrate_token_budget",  # deliberate D2H-fenced measurement
    ),
    "fusioninfer_tpu/engine/sched.py": (),
    "fusioninfer_tpu/engine/fused.py": (),
    "fusioninfer_tpu/engine/model_runner.py": (),
    # the host KV tier: the ONLY sanctioned device→host fetch is the
    # offload worker's serialization (_store blocks on the page gather
    # the engine dispatched at reclaim); restore-side take() handles
    # host bytes only, and the engine-side restore path
    # (engine._restore_host_blocks) dispatches the H2D inject without
    # fetching — an ad-hoc fetch anywhere else stalls the step loop
    "fusioninfer_tpu/engine/kv_host_tier.py": ("_store",),
    # the tier table is pure queue-state bookkeeping: no device values
    # exist here, so no fetch point is sanctioned
    "fusioninfer_tpu/engine/slo.py": (),
    # evacuation planning is equally pure — the park path's device
    # work lives in engine.py (_park_preempted → the tier's _store)
    "fusioninfer_tpu/engine/evacuate.py": (),
    # the KV fabric: the ONLY sanctioned fetch is frame serialization
    # (frame_to_bytes blocks on the page gather the streamed-prefill
    # extractor dispatched); the decode side parses to host numpy and
    # inject_frame dispatches the H2D scatter without fetching
    "fusioninfer_tpu/engine/kv_fabric.py": ("frame_to_bytes",),
    "fusioninfer_tpu/ops/paged_attention.py": (),
    "fusioninfer_tpu/ops/lm_head_topk.py": (),
    "fusioninfer_tpu/ops/dispatch.py": (),
    "fusioninfer_tpu/ops/sharded.py": (),
    # the revived TP surfaces (PR 6): a stray fetch in the SPMD-lockstep
    # broadcast or the mesh step factories stalls every process in the
    # gang, not just one
    "fusioninfer_tpu/engine/multihost.py": (),
    "fusioninfer_tpu/parallel/step.py": (),
    "fusioninfer_tpu/parallel/ring.py": (),
    "fusioninfer_tpu/parallel/sharding.py": (),
    "fusioninfer_tpu/parallel/mesh.py": (),
}

# -- conditions-vocabulary pass ----------------------------------------

# the module that DECLARES the condition type/reason vocabulary
CONDITIONS_MODULE = "fusioninfer_tpu/operator/conditions.py"
# modules whose condition-setter call sites are checked
CONDITIONS_SCOPE = ["fusioninfer_tpu/*.py", "fusioninfer_tpu/*/*.py"]
# callee name -> positional index of (cond_type, reason); None = not
# passed positionally at that site (kwarg-only)
CONDITION_SETTERS: dict[str, tuple[int | None, int | None]] = {
    "set_condition": (1, 3),
    "set_scaling_limited": (None, 3),
}
