"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding/collective code
is validated on 8 virtual CPU devices exactly the way the driver's
``dryrun_multichip`` does.  The CPU is asked for by name
(``JAX_PLATFORMS=cpu``), before jax is imported.

``FUSIONINFER_TEST_TPU=1`` (the ``make test-tpu`` tier, run through the
chip tool) leaves the TPU backend in place instead — that tier runs the
hardware kernel tests (``tests/test_kernels_tpu.py``) with
``interpret=False`` at serving shapes.
"""

import os
import sys
import tempfile

_ON_TPU_TIER = os.environ.get("FUSIONINFER_TEST_TPU", "") == "1"

if not _ON_TPU_TIER:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()
    # Persistent XLA compilation cache: the tier-1 suite compiles
    # hundreds of jit signatures and compile time dominates its wall
    # clock.  Identical binaries come back from the cache, so
    # bit-identity tests are unaffected.  ONE rule with the serving
    # entry points (fusioninfer_tpu.engine.aot): JAX_COMPILATION_CACHE_DIR
    # where it is set.  Unset, the CPU tiers name a fixed directory
    # OUTSIDE the checkout here — their hundreds of cached signatures
    # must not pile up in the tree the chip tool copies — and every
    # child process the tests start inherits it.  The 0.5 s threshold
    # keeps trivial signatures out.  The TPU tier keeps the program's
    # own in-checkout default, so one chip command shares one cache
    # with chip_smoke.py.
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(tempfile.gettempdir(), "fusioninfer-tpu-test-xla"))

import jax  # noqa: E402 — after the environment above

if not _ON_TPU_TIER:
    # a plugin may have imported jax before this file ran
    jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fusioninfer_tpu.engine.aot import configure_cache  # noqa: E402

configure_cache(min_compile_seconds=None if _ON_TPU_TIER else 0.5)

if os.environ.get("FUSIONINFER_LOCKTRACE", ""):
    # Runtime half of the lock-order gate (``make lock-gate``): trace
    # every lock the covered package constructs during this run; the
    # acquisition-order pairs merge into the static graph in
    # tools/check_lock_order.py.  Installed before any test module
    # imports so no engine lock predates the patch.
    from fusioninfer_tpu.utils import locktrace

    locktrace.install()

import pytest  # noqa: E402 — after the backend bootstrap above

# The sub-2-minute smoke tier (``make fast`` / ``pytest -m fast``, the
# CI quick job that fronts full tier-1; VERDICT #10).  ONE central list
# instead of per-file marks so the tier's runtime budget is auditable in
# a single diff.  Measured ~100 s for 300+ tests on the CI-class CPU —
# keep additions within the 2-minute budget, and keep engine-forward
# heavy suites (fused step, token budget, e2e serving) OUT: they are
# what the full tier is for.
FAST_MODULES = {
    "test_api_types.py", "test_applyconfig.py", "test_axis_rules.py",
    "test_evacuation.py",
    "test_fusionlint.py",
    "test_hash.py", "test_informers.py", "test_kv_host_tier.py",
    "test_leader_election.py",
    "test_manifests.py", "test_metrics.py", "test_names.py",
    "test_paged_attention.py", "test_priority.py", "test_reconciler.py",
    "test_render_cli.py", "test_router.py", "test_schema.py",
    "test_scheduling_podgroup.py", "test_slo_overload.py",
    "test_threads.py", "test_tokenizer.py",
    "test_topology.py", "test_workload_lws.py",
}


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_executables():
    """Every compiled CPU executable holds memory mappings, and jax's
    jit caches keep every executable a test ever built.  One process
    running the whole tier crosses ``vm.max_map_count`` (65530) about
    half way; the next allocation fails and the run dies of a
    segmentation fault inside jax's cache (de)compression.  Dropping
    the jit caches when a module is done gives the mappings back (the
    count stays in the hundreds between modules); what a later module
    needs again comes from the persistent cache.  Clearing only near
    the limit was tried: no faster, and a third of the margin."""
    yield
    jax.clear_caches()


def pytest_collection_modifyitems(config, items):
    for item in items:
        if os.path.basename(str(item.fspath)) in FAST_MODULES:
            item.add_marker(pytest.mark.fast)


def pytest_sessionfinish(session, exitstatus):
    """Write the run's gate artifacts when asked: the compile ledger
    (``FUSIONINFER_COMPILE_LEDGER=path make fast`` — the runtime half
    of the jit-registry discipline, checked by ``make compile-gate``)
    and the lock trace (``FUSIONINFER_LOCKTRACE=path`` — the runtime
    half of the lock-order discipline, merged into the static graph by
    ``make lock-gate``)."""
    path = os.environ.get("FUSIONINFER_COMPILE_LEDGER", "")
    if path:
        from fusioninfer_tpu.utils.compile_ledger import write

        snap = write(path)
        totals = ", ".join(f"{fam}={n}" for fam, n in
                           sorted(snap["families"].items()))
        print(f"\ncompile ledger -> {path} ({totals})")
    from fusioninfer_tpu.utils import locktrace

    snap = locktrace.write_if_enabled()
    if snap is not None:
        print(f"\nlock trace -> {os.environ['FUSIONINFER_LOCKTRACE']} "
              f"({len(snap['locks'])} locks, {len(snap['pairs'])} "
              "ordered pairs)")


def nonzero_adapter(cfg, rank=4, seed=7, scale=2.0):
    """A LoRA adapter whose deltas actually change output —
    ``init_adapter``'s b=0 is an exact no-op by design, so tests that
    need a behavioral adapter fill each projection's ``b`` with small
    noise in the engine's dtype.  Shared here so every suite builds the
    SAME adapter recipe (was copied in three places)."""
    import jax
    import jax.numpy as jnp

    from fusioninfer_tpu.models.lora import LORA_PROJS, init_adapter

    adapter = init_adapter(cfg, rank, jax.random.key(seed), scale=scale)
    keys = jax.random.split(jax.random.key(seed + 1), len(LORA_PROJS))
    for k, proj in zip(keys, LORA_PROJS):
        adapter[proj]["b"] = (jax.random.normal(
            k, adapter[proj]["b"].shape, jnp.float32) * 0.05).astype(
            cfg.jax_dtype)
    return adapter
