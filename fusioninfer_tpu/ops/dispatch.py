"""Attention implementation dispatch.

Selection order: an explicit ``ModelConfig.attn_impl`` (``flash`` /
``reference``) always wins, and the env var must not defeat a pin.  When
the config says ``auto``, the ``FUSIONINFER_ATTN`` env var may choose;
otherwise ``auto`` resolves to the Pallas kernels on TPU and the jnp
reference elsewhere.  Resolution happens at trace time — a process
serves with one implementation.

Multi-device: tp-only serving meshes run the kernels per tensor-parallel
shard via the shard_map wrappers in :mod:`fusioninfer_tpu.ops.sharded`
(see ``tp_compatible``); every other sharded path (training, sp/ep
meshes) pins ``"reference"`` through ``parallel.sharding.spmd_cfg`` and
relies on XLA SPMD.
"""

from __future__ import annotations

import os

import jax


def is_tpu_backend() -> bool:
    """True when compute lands on a TPU."""
    return jax.default_backend() == "tpu"


def require_requested_backend() -> str:
    """The serving entry points' platform guard: returns the default
    backend, or exits non-zero when that backend is the CPU and the CPU
    was not asked for by name (``JAX_PLATFORMS=cpu``).  With
    ``JAX_PLATFORMS`` unset jax falls back to the CPU with a warning
    when it finds no accelerator; ``auto`` would then pick the jnp
    reference and the Pallas interpreter and the program would appear
    to serve."""
    backend = jax.default_backend()
    requested = (jax.config.jax_platforms or "").split(",")
    if backend == "cpu" and "cpu" not in requested:
        raise SystemExit(
            "no accelerator: jax fell back to the CPU backend and the CPU "
            "was not requested (set JAX_PLATFORMS=cpu to run there on "
            "purpose)")
    return backend


def resolve_attn(cfg_impl: str = "auto") -> str:
    impl = cfg_impl
    if impl == "auto":
        impl = os.environ.get("FUSIONINFER_ATTN", "") or "auto"
    if impl == "auto":
        return "flash" if is_tpu_backend() else "reference"
    if impl not in ("flash", "reference"):
        raise ValueError(f"unknown attention impl {impl!r}")
    return impl


def kernel_interpret() -> bool:
    """Pallas kernels interpret-execute off-TPU (CPU tests of the kernel path)."""
    return not is_tpu_backend()


def decode_coalesce() -> bool:
    """The ragged kernel's grid knob: True (default) = one [KV, ps, Hd]
    copy per page covering every KV head, with the score/value dots
    batched over KV (KV× fewer DMA issues); False = the per-(tile, head)
    grid.  Both compute identical per-row math; neither has a chip
    number yet (ROADMAP S4/D7).  ``FUSIONINFER_DECODE_COALESCE=0/1``
    overrides.  The ENGINE resolves this eagerly at every ragged
    dispatch and passes the concrete bool into the jitted step as a
    static argument, so flipping the env var mid-process retraces
    instead of the jit cache serving the variant latched at first
    trace.  The coalesced grids additionally fall back to the per-head
    grid when their double-buffered scratch would exceed the VMEM
    budget (:func:`fusioninfer_tpu.ops.paged_attention.resolve_ragged_grid`)."""
    v = os.environ.get("FUSIONINFER_DECODE_COALESCE", "")
    if not v:
        return True
    if v not in ("0", "1"):
        # loud like resolve_attn's unknown-impl error: a typo'd knob must
        # not silently run the default on both arms of an A/B
        raise ValueError(
            f"FUSIONINFER_DECODE_COALESCE must be '0' or '1', got {v!r}")
    return v == "1"


def flash_seq_ok(seq_len: int) -> bool:
    """Flash tiles need the sequence to divide into full blocks; the
    engine's power-of-two prefill buckets always satisfy this."""
    return seq_len % 128 == 0 or (
        seq_len >= 16 and (seq_len & (seq_len - 1)) == 0
    )
