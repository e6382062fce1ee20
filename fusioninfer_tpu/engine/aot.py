"""AOT warm start: a freshly scaled pod serves in seconds, not minutes.

PRs 9-11 made scale-out *decisions* instant (autoscaler ramps,
revocation replacement surge), but a replacement pod still paid full
JIT compilation before its first token — scale-up latency was compile
latency.  This module finishes what the PR 7 test-tier XLA cache
started, in three pieces:

* **One persistent cache, placed from outside.** :func:`configure_cache`
  points jax's persistent compilation cache (and the AOT manifests
  below) at ``JAX_COMPILATION_CACHE_DIR`` where that is set — jax's own
  variable, so every process of the program and every child it starts
  agree without a second knob — and otherwise at one fixed, git-ignored
  directory inside the checkout (:data:`DEFAULT_CACHE_DIR`).  The path
  never comes from a temporary name, a pid or the time: a cache that
  moves never hits.

* **AOT build of every serving entry point.** :func:`warmup` walks the
  engine's :meth:`~fusioninfer_tpu.engine.engine.NativeEngine.
  aot_signatures` — the jit-registry entry points at THIS engine's
  exact shape discipline (prefill buckets × pow2 group rows, burst
  spans, the fused ragged layout, the sampler chain) — and
  ``.lower().compile()``s each one *before admission opens*.  Compiled
  executables land in the persistent cache keyed by XLA on the exact
  HLO, so correctness never depends on our bookkeeping: a key mismatch
  just recompiles.

* **A keyed manifest for warm/cold accounting.** The build is stamped
  under :func:`fingerprint` — (model config, cache config, mesh shape +
  axis-rules fingerprint, jit-registry budget signature, jax
  version/backend).  A later pod with the same fingerprint counts its
  entries as ``hits`` (the executables were persisted by a twin) and
  its build is a cache *load*; any fingerprint drift — a config bump, a
  different mesh, an axis-rules change, a registry edit — misses and
  rebuilds.  ``fusioninfer:aot_cache_{hits,misses,build_seconds}`` land
  on /metrics and ``cold_start_to_first_token_s`` in the bench record
  gate the result.

Wire-up: ``fusioninfer-tpu engine serve --aot-warmup`` (and the
``engine warmup`` subcommand that builds the cache and exits),
``chip_smoke.py`` (run twice against one directory: misses, then hits)
and fleetsim's scale-up / revocation replacement pods
(``docs/design/parallelism.md``).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import time
from typing import Callable, Iterable, Optional, Tuple

logger = logging.getLogger(__name__)

# jax's own variable names the directory from outside; unset, the cache
# lives at this fixed path in the checkout (listed in .gitignore)
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".xla_cache")

# one warmup entry: (name, thunk) — the thunk lowers AND compiles the
# entry point at a concrete serving signature
Signature = Tuple[str, Callable[[], object]]


def resolve_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where it is set, else the fixed
    in-checkout default — ONE rule for serve, warmup, bench, smoke,
    their children and the test tier."""
    return os.environ.get(ENV_CACHE_DIR) or DEFAULT_CACHE_DIR


def configure_cache(min_compile_seconds: Optional[float] = None) -> Optional[str]:
    """Point jax's persistent compilation cache at the resolved
    directory and return it.  A directory named from outside
    (``JAX_COMPILATION_CACHE_DIR``) that cannot be used is an error —
    whoever placed the cache must hear that nothing is being kept;
    only the in-checkout default degrades to uncached (None) with a
    warning, since a read-only install has nowhere else to go.

    ``min_compile_seconds`` sets the persistence threshold; ``None``
    leaves the process's active threshold untouched.  Only
    process-boot-time owners set it — the serve/warmup entry points
    pass 0.0 (every warmup build must persist), the test tier passes
    0.5 (trivial signatures stay out of the shared cache) — so a
    mid-process :func:`warmup` can never silently retune another
    owner's threshold."""
    import jax

    path = resolve_cache_dir()
    try:
        os.makedirs(path, exist_ok=True)
        if not os.access(path, os.W_OK | os.X_OK):
            raise PermissionError(f"{path} is not writable")
    except OSError as e:
        if os.environ.get(ENV_CACHE_DIR):
            raise RuntimeError(
                f"{ENV_CACHE_DIR}={path} is unusable: {e}") from e
        logger.warning("persistent compile cache unavailable at %s: %s",
                       path, e)
        return None
    jax.config.update("jax_compilation_cache_dir", path)
    if min_compile_seconds is not None:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_compile_seconds)
    return path


def registry_signature() -> str:
    """Hash of the jit-registry contract (entry points, static/traced
    splits, compile budgets): an edit to the registry changes what the
    warmup is expected to cover, so it must invalidate the manifest."""
    from fusioninfer_tpu.utils import jit_registry

    blob = json.dumps(
        {"entries": {k: {kk: list(vv) if isinstance(vv, tuple) else vv
                         for kk, vv in sorted(v.items())}
                     for k, v in sorted(jit_registry.ENTRY_POINTS.items())},
         "budgets": dict(sorted(jit_registry.FAMILY_BUDGETS.items()))},
        sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def fingerprint(engine) -> str:
    """The AOT cache key: everything that changes the compiled
    executables a pod needs.  Model + cache config (shapes), the mesh
    and the logical→mesh axis rules (partitioning), the jit-registry
    signature (entry-point contract), engine knobs that mint their own
    signatures (batch, burst span, spec window), and the jax
    version/backend pair the executables were built by."""
    import jax

    from fusioninfer_tpu.parallel.axes import default_rules

    mesh = getattr(engine, "_kernel_mesh", None) or getattr(
        engine, "mesh", None)
    mesh_desc = (tuple(zip(mesh.axis_names, mesh.devices.shape))
                 if mesh is not None else ("single-device",))
    # LoRA changes every entry point's operand list (stacked adapter
    # trees ride the forwards — different HLO per entry), so it rides
    # the key: a no-LoRA warming job must never count as a hit for a
    # LoRA-serving pod.  The token budget deliberately does NOT: it
    # only selects WHICH flat-token buckets get warmed (each bucket's
    # executable is budget-independent), and the manifest MERGES
    # per-entry, so pods with different derived budgets share the
    # cache and account hits per entry instead of flapping it.
    lora_set = getattr(engine, "lora_set", None)
    blob = json.dumps({
        "model": repr(engine.cfg),
        "cache": repr(engine.cache_cfg),
        "mesh": repr(mesh_desc),
        "axis_rules": default_rules().fingerprint(),
        "registry": registry_signature(),
        "max_batch": engine.max_batch_size,
        "burst": engine.burst_steps,
        "spec_k": engine.spec_k,
        "fused": engine.fused_step_enabled,
        "buckets": list(engine.buckets),
        "lora": ([n for n in lora_set.names if n], lora_set.rank)
                if lora_set is not None else None,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
    }, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()


def _manifest_path(cache_dir: str, fp: str) -> str:
    return os.path.join(cache_dir, f"aot-manifest-{fp[:16]}.json")


def _load_manifest(cache_dir: Optional[str], fp: str) -> dict:
    """Entries a prior twin-fingerprint build persisted (hit
    accounting).  A stale or unreadable manifest is an empty one —
    correctness lives in XLA's own keying, not here."""
    if not cache_dir:
        return {}
    try:
        with open(_manifest_path(cache_dir, fp)) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}
    if data.get("fingerprint") != fp:
        return {}
    entries = data.get("entries")
    return entries if isinstance(entries, dict) else {}


def _write_manifest(cache_dir: Optional[str], fp: str,
                    entries: dict) -> None:
    """MERGE this build's entries into the fingerprint's manifest —
    pods whose engine knobs select different entry subsets under one
    fingerprint (a derived token budget picks the flat-token buckets)
    accumulate coverage instead of overwriting each other's."""
    if not cache_dir:
        return
    merged = dict(_load_manifest(cache_dir, fp))
    merged.update(entries)
    body = {"fingerprint": fp, "registry": registry_signature(),
            "entries": merged}
    try:
        tmp = _manifest_path(cache_dir, fp) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(body, f, sort_keys=True)
        os.replace(tmp, _manifest_path(cache_dir, fp))
    except OSError as e:
        logger.warning("AOT manifest write failed: %s", e)


def warmup(engine, signatures: Optional[Iterable[Signature]] = None,
           force: bool = False) -> dict:
    """Build (or load) the compiled-executable cache for ``engine``
    BEFORE admission opens; returns the warmup report and stamps it on
    ``engine.aot_stats`` (the /metrics source).

    An entry a prior same-fingerprint build persisted is a **hit**: its
    executable is already on disk, so the warmup skips the
    lower-and-compile entirely and the entry's first live dispatch
    traces (~ms) and loads the binary from the persistent cache instead
    of paying XLA compilation.  Everything else is a **miss**: built
    now, persisted for the next twin pod.  ``build_seconds`` is the
    honest wall time — a warm pod's evidence is hits > 0 AND a small
    build_seconds; ``force=True`` rebuilds hits too (cache repair)."""
    t0 = time.perf_counter()
    path = configure_cache()
    fp = fingerprint(engine)
    prior = _load_manifest(path, fp)
    sigs = list(signatures if signatures is not None
                else engine.aot_signatures())
    entries: dict = {}
    hits = misses = 0
    errors: list[str] = []
    for name, thunk in sigs:
        if name in prior and not force:
            entries[name] = prior[name]
            hits += 1
            continue
        t1 = time.perf_counter()
        try:
            lowered = thunk()
            compiled = getattr(lowered, "compile", None)
            if compiled is not None:
                compiled()
        except Exception as e:  # noqa: BLE001 - collect every failing
            # entry before the caller decides: the serve path treats
            # any error as fatal, and one report naming all the refused
            # signatures beats dying on the first
            errors.append(f"{name}: {type(e).__name__}: {str(e)[:600]}")
            continue
        entries[name] = round(time.perf_counter() - t1, 4)
        misses += 1
    _write_manifest(path, fp, entries)
    report = {
        "cache_dir": path,
        "fingerprint": fp,
        "entries": len(entries),
        "hits": hits,
        "misses": misses,
        "errors": errors,
        "build_seconds": round(time.perf_counter() - t0, 3),
    }
    try:
        engine.aot_stats = report
    except Exception:  # noqa: BLE001 - read-only engine stand-ins
        pass
    logger.info(
        "AOT warmup: %d entries (%d hits, %d misses) in %.2fs -> %s",
        report["entries"], hits, misses, report["build_seconds"],
        path or "<no persistent cache>")
    return report
